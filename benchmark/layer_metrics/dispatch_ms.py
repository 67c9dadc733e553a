"""Program -> step: median host milliseconds inside one ``exe.run`` call of
the measured window (feed placement, cache lookup, dispatch; the call does
not wait for the device). From the benchmark's own span around the call."""

import statistics


def read(ctx):
    calls = ctx["run"].spans.durations("exe_run")
    return statistics.median(calls) * 1e3 if calls else None
