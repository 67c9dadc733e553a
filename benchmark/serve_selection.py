"""How much of the reference's selection the served program also picks.

Seeded weights make attention nearly flat, so a wrong sparse selection can
pass a logit check. This script (never part of a run, like
``serve_control.py``; ``PERF.md`` records what it read on the chip) drives
the cell as a run does (set-up, the schedule, a window of ``--seconds``),
takes the sample a run's check takes with the tokens the program served,
frees the engine, and then replays each followed request twice:

* through the plain reference (``reference/<config>.py``'s ``selections``,
  float32 at ``Precision.HIGHEST``), keeping each ``full`` layer's ``S_t``;
* through the served programs themselves, one row, the prompt by chunks of
  the cell's top chunk rung and the rest by steps as the decode loop feeds
  them, with the indexer ops' outputs fetched beside the caches (a chunk's
  membership mask, a step's positions).

For every ``full`` layer it prints, over the positions where the selection
bites (more than ``index_topk`` cached), the share of the reference's
``S_t`` that the served program also picked: the mean and the least over the
chunks' lanes and over the steps.

    python3 benchmark/serve_selection.py --workload <cell> --seed 1
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402

INDEX_OPS = {"sparse_index": "Index", "sparse_index_chunk": "Mask"}


class Replay:
    """The two served programs over the benchmark's weights, one row, with
    the indexers' outputs among the fetches."""

    def __init__(self, run, weights, args, ctx):
        import jax.numpy as jnp

        import paddle_tpu as fluid
        from paddle_tpu.inference import ProgramPredictor

        builder = harness.load_module(run.path(run.config["builder"]))
        self.scope = fluid.Scope()
        for name, value in weights.items():
            self.scope.set(name, value)
        self.ctx = ctx
        self.kinds = {}
        for kind in ("step", "chunk"):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup), \
                    fluid.unique_name.guard():
                fetch, spec = getattr(builder, kind)(
                    dtype=run.config["served_dtype"], **args)
            picked = [op.output(INDEX_OPS[op.type])
                      for op in main.global_block().ops
                      if op.type in INDEX_OPS]
            feeds = [spec["token_feed"], spec["pos_feed"]] + [
                c["feed"] for c in spec["cache_feeds"]]
            predictor = ProgramPredictor(main, feeds, list(fetch) + picked,
                                         scope=self.scope)
            names = list(predictor.fetch_names)
            self.kinds[kind] = {
                "predictor": predictor, "spec": spec,
                "caches": [(c["feed"], names.index(c["fetch"]))
                           for c in spec["cache_feeds"]],
                "picked": [names.index(v.name) for v in picked]}
        first, count = args["layers_held"]
        self.full = [l for l in range(first, first + count)
                     if args["indexer_types"][l] == "full"]
        self.caches = {c["feed"]: jnp.zeros(
            (1, ctx) + tuple(c["tail"]), c["dtype"])
            for c in self.kinds["step"]["spec"]["cache_feeds"]}

    def _run(self, kind, tokens, positions):
        k = self.kinds[kind]
        feed = dict(self.caches)
        feed[k["spec"]["token_feed"]] = tokens
        feed[k["spec"]["pos_feed"]] = positions
        outs = k["predictor"].run(feed, return_numpy=False)
        for name, idx in k["caches"]:
            self.caches[name] = outs[idx]
        return [outs[i] for i in k["picked"]]

    def chunk(self, tokens, start, rung):
        """Ingest ``tokens`` at positions ``start``.. as one chunk of
        ``rung`` lanes. Returns {layer: [len(tokens), ctx] bool}."""
        n = len(tokens)
        tok = np.zeros((1, rung), np.int64)
        pos = np.full((1, rung), self.ctx, np.int32)
        tok[0, :n] = tokens
        pos[0, :n] = np.arange(start, start + n)
        masks = self._run("chunk", tok, pos)
        return {l: m[0, :n] for l, m in zip(self.full, masks)}

    def step(self, token, position):
        """{layer: [ctx] bool} of one decode step."""
        import jax.numpy as jnp

        index = self._run("step", np.asarray([token], np.int64),
                          np.asarray([position], np.int32))
        return {l: jnp.zeros((self.ctx + 1,), bool).at[i[0]].set(True)[
            :self.ctx] for l, i in zip(self.full, index)}


def overlaps(served, wanted, positions, top_k):
    """Share of each wanted row's members that the served row holds too, for
    the rows whose position holds more than ``top_k``. served, wanted
    [n, ctx] bool, positions [n]."""
    import jax.numpy as jnp

    both = jnp.sum(served & wanted, axis=-1)
    share = np.asarray(both / jnp.maximum(jnp.sum(wanted, axis=-1), 1))
    return share[np.asarray(positions) + 1 > top_k]


def follow(replay, reference, weights, request, args, ops, rung, pad_to):
    """{layer: {"chunk": [shares], "step": [shares]}} of one request."""

    p = len(request.prompt)
    served_tokens = np.asarray(request.tokens)
    tokens = np.zeros(max(pad_to, request.positions), np.int32)
    tokens[:p] = request.prompt
    tokens[p:p + len(served_tokens)] = served_tokens
    wanted = reference.selections(weights, tokens, args, ops)
    ctx, top_k = replay.ctx, int(args["index_topk"])
    out = {l: {"chunk": [], "step": []} for l in replay.full}
    at = 0
    while p - at >= 2:        # the last prompt token goes in by a step
        n = min(rung, p - 1 - at)
        masks = replay.chunk(tokens[at:at + n], at, rung)
        for l, served in masks.items():
            out[l]["chunk"].append(overlaps(
                served, wanted[l][at:at + n, :ctx], np.arange(at, at + n),
                top_k))
        at += n
    fed = p + len(served_tokens) - 1       # the last token is never fed
    for t in range(at, fed):
        sets = replay.step(int(tokens[t]), t)
        for l, served in sets.items():
            out[l]["step"].append(overlaps(
                served[None], wanted[l][t:t + 1, :ctx], np.asarray([t]),
                top_k))
    return {l: {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in kinds.items()} for l, kinds in out.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--requests", type=int, default=2,
                    help="followed requests replayed (the longest first)")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    from benchmark.jobs import serve, serve_check

    run = harness.Run(a.manifest, a.workload, a.seed, a.seconds, 0,
                      a.rehearse, time.time())
    _, sampled, weights, args, pad_to = serve.serve_window(run)
    reference = serve_check.load_reference(run)
    exact = serve_check.precision(run, "exact")
    rung = max(int(k) for k in run.traffic["engine"]["prefill_ladder"])
    replay = Replay(run, weights, args, pad_to)
    for request in sampled[:a.requests]:
        t0 = time.perf_counter()
        shares = follow(replay, reference, weights, request, args, exact,
                        rung, pad_to)
        line = {"workload": a.workload, "seed": a.seed,
                "request": request.index, "prompt": len(request.prompt),
                "answer": len(request.tokens),
                "seconds": round(time.perf_counter() - t0, 1), "layers": {}}
        for l, kinds in shares.items():
            line["layers"][str(l)] = {
                kind: {"positions": int(len(v)),
                       "mean": float(v.mean()) if len(v) else None,
                       "least": float(v.min()) if len(v) else None}
                for kind, v in kinds.items()}
        print("selection " + json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
