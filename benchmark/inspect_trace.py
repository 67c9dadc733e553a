"""Look at one trace by hand before trusting a reducer: prints the planes,
their lines, and for the device's "XLA Ops" line the first events with all
their stats and the heaviest event names.

    python3 benchmark/inspect_trace.py <file.xplane.pb> [events]
"""

import collections
import sys


def main(path, show=12):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("plane %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            device = plane.name.startswith("/device:")
            bench = [e for e in events if e.name.startswith("bench.")]
            if not (device or bench):
                continue
            for ev in (events if device else bench)[:show]:
                print("    %r start %d dur %d" % (ev.name, ev.start_ns,
                                                  ev.duration_ns))
                if device:
                    for key, value in ev.stats:
                        print("        %s = %r" % (key, str(value)[:160]))
            if device:
                total = collections.Counter()
                for ev in events:
                    total[ev.name] += ev.duration_ns
                for name, ns in total.most_common(show):
                    print("    heavy %-60s %.3f ms" % (name[:60], ns / 1e6))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
