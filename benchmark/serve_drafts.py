"""The comparison a serving cell's ``correct`` cannot make: the DRAFTS of a
step program that drafts for itself, against the plain reference's
``draft_logits``. ``serve_check.py`` reads served tokens only, and greedy
acceptance makes them the model's own whatever was drafted, so a prediction
module that computed something else would still come out ``correct`` and
only ``mtp_accept_pct`` would move. For each seed this drives the cell as a
run does (set-up, the schedule at the cell's own load, a window of
``--seconds``) with the decode loop keeping what every verifying step was
fed (``DecodeBatcher.draft_log``), takes the sample a run's check takes,
frees the program, and then, for every draft a sampled request's steps were
fed (the chunk program's first, then the steps' own), reads the reference's
module at the position that draft was made at, over the request's prompt
followed by the tokens it was served: whether the draft IS the reference's
best draft, and the gap by which its logit lies below the reference's best,
as ``token_gap_*`` reads the main model. Never part of a run; ``PERF.md``
records what it read on the chip.

    python3 benchmark/serve_drafts.py --workload <cell> --seeds 1 2 3

A chip serves one process, and a run's set-up builds one engine: each seed
is a process of its own (``--seeds`` starts them one after another).
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402


def _key(prompt):
    return np.asarray(prompt, np.int64).tobytes()


def compare(manifest, workload, seed, seconds, rehearse):
    """{drafts compared, the share of them that ARE the reference's best
    draft, the widest and the mean gap under the reference's best draft
    logit, the share that stood} over the run's sampled requests."""
    import jax.numpy as jnp

    from benchmark.jobs import serve, serve_check

    log = []
    build = serve.Server.__init__

    def keeping(self, run):
        build(self, run)
        for decoder in self.engine._decoders:
            decoder.draft_log = log

    serve.Server.__init__ = keeping
    try:
        run = harness.Run(manifest, workload, seed, seconds, 0, rehearse,
                          time.time())
        _, sampled, weights, args, pad_to = serve.serve_window(run)
    finally:
        serve.Server.__init__ = build
    reference = serve_check.load_reference(run)
    exact = serve_check.precision(run, "exact")
    steps_of = {}
    for req, p, draft, gave in log:
        steps_of.setdefault(_key(req.prompt), []).append((p, draft, gave))
    agree, gaps, stood = [], [], []
    for request in sampled:
        tokens = np.zeros(max(pad_to, request.positions), np.int32)
        n, m = len(request.prompt), len(request.tokens)
        tokens[:n] = request.prompt
        tokens[n:n + m] = request.tokens
        # a step at position p that was fed a draft verifies what the module
        # made at p - 1: its guess of the token at p + 1
        steps = [(p, draft, gave)
                 for p, draft, gave in steps_of[_key(request.prompt)]
                 if draft is not None and 1 <= p and p + 1 < n + m]
        if not steps:
            continue
        # only the rows that are read are kept: a whole [T, vocabulary]
        # of float32 beside the served weights is most of what is free
        rows = reference.draft_logits(weights, tokens, args, exact)[
            jnp.asarray([p - 1 for p, _, _ in steps])]
        drafts = jnp.asarray([draft for _, draft, _ in steps], jnp.int32)
        agree += np.asarray(jnp.argmax(rows, axis=-1) == drafts).tolist()
        gaps += np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
            rows, drafts[:, None], axis=-1)[:, 0]).tolist()
        stood += [int(gave[0]) == 2 for _, _, gave in steps]
        del rows
    return {"drafts_compared": len(gaps),
            "draft_agree_share": float(np.mean(agree)) if agree else None,
            "draft_gap_max": float(np.max(gaps)) if gaps else None,
            "draft_gap_mean": float(np.mean(gaps)) if gaps else None,
            "accepted_share": float(np.mean(stood)) if stood else None}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if len(args.seeds) > 1:  # one process a seed: one engine a process
        failed = 0
        for seed in args.seeds:
            failed += subprocess.call(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 args.workload, "--seeds", str(seed), "--seconds",
                 str(args.seconds), "--manifest", args.manifest]
                + (["--rehearse"] if args.rehearse else [])) != 0
        return 1 if failed else 0
    seed = args.seeds[0]
    row = compare(args.manifest, args.workload, seed, args.seconds,
                  args.rehearse)
    print(json.dumps(dict(row, drafts="module", workload=args.workload,
                          seed=seed)))
    return 0 if row["drafts_compared"] else 1


if __name__ == "__main__":
    sys.exit(main())
