"""Microbench: embedding gather/scatter strategies on TPU.

The DeepFM profile shows row-gathers from [100000,16] tables running
~1000x below HBM bandwidth: a 64-byte row is far below the 512-byte
HBM burst and the (8,128) tile, so XLA serializes per-row transfers.
Candidates measured here:
  g_k16     : table[V,16]  f32, plain take            (status quo)
  g_k128    : table[V,128] f32, plain take            (pad to lane width)
  g_pack8   : table[V//8,128] packed 8 rows/tile-row; take + lane-select
  g_onehot  : one-hot matmul over 512-row vocab blocks (MXU route)
  s_k16     : .at[ids].add on [V,16]                  (status quo scatter)
  s_k128    : .at[ids].add on [V,128]
  s_sortseg : sort ids + segment_sum into [V,16]
  s_pallas  : ops/scatter.py VMEM-resident Pallas row scatter (ISSUE 13)
  s_pallas_sorted : same kernel behind the sorted-segment merge
Timing: slope method (chained fori_loop at 2 lengths), f32-scalar sync.

``--write`` commits the measurements to ``ROW_OP_FLOORS.json`` beside
bench.py (the CHIP_CEILING.json pattern): ``models/deepfm.py`` sources
its roofline constants from that record, so one bench-chip run
propagates into every subsequent DeepFM vs_baseline
(tests/test_bench_contract.py pins the sourcing). The committed scatter
floor is the BEST measured scatter — if the Pallas kernel loses to
``.at[].add``, the 15 ns/row claim stands with the numbers on record.
"""
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

V = 100000
N = 212992  # 8192 examples x 26 fields


def slope_time(fn, *args):
    """Per-iteration seconds via chained-loop slope; fn(x, it) -> x-like."""
    def loop(n, x):
        return jax.lax.fori_loop(0, n, lambda i, c: fn(c, i), x)
    jl = jax.jit(loop, static_argnums=0)
    walls = {}
    for n in (4, 24):
        out = jl(n, *args)
        np.asarray(jnp.sum(out[0] if isinstance(out, tuple) else out)
                   .astype(jnp.float32))  # warm compile+run
        ts = []
        for _ in range(4):
            t0 = time.perf_counter()
            out = jl(n, *args)
            np.asarray(jnp.sum(out[0] if isinstance(out, tuple) else out)
                       .astype(jnp.float32))
            ts.append(time.perf_counter() - t0)
        walls[n] = min(ts)
    return (walls[24] - walls[4]) / 20


def main():
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, V, size=(N,)).astype(np.int32))
    t16 = jnp.asarray(rng.randn(V, 16).astype(np.float32))
    t128 = jnp.asarray(rng.randn(V, 128).astype(np.float32))
    vals16 = jnp.asarray(rng.randn(N, 16).astype(np.float32))
    vals128 = jnp.asarray(rng.randn(N, 128).astype(np.float32))
    # packed: pad V to multiple of 8, 8 rows of 16 per 128-lane row
    Vp = (V + 7) // 8
    tpack = jnp.reshape(jnp.resize(t16, (Vp * 8, 16)), (Vp, 128))

    def g_k16(c, i):
        out, = c if isinstance(c, tuple) else (c,)
        g = t16[(ids + i) % V]
        return (jnp.sum(g, axis=0) + out[:16],)

    def g_k128(c, i):
        out, = c
        g = t128[(ids + i) % V]
        return (jnp.sum(g, axis=0) + out[:128],)

    def g_pack8(c, i):
        out, = c
        idv = (ids + i) % V
        rows = tpack[idv // 8]                      # [N,128] burst gather
        sub = (idv % 8)[:, None]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        mask = (lane // 16) == sub                  # [N,128]
        picked = jnp.where(mask, rows, 0.0)
        g = jnp.sum(picked.reshape(N, 8, 16), axis=1)   # [N,16]
        return (jnp.sum(g, axis=0) + out[:16],)

    def g_onehot(c, i):
        # blocked one-hot matmul: FLOPs = N*V*16*2 = 6.8e14 -> hopeless at
        # V=100k, included to calibrate the MXU route's actual cost
        out, = c
        idv = (ids[:4096] + i) % V
        oh = jax.nn.one_hot(idv, V, dtype=jnp.bfloat16)
        g = jnp.dot(oh, t16.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        return (jnp.sum(g, axis=0) + out[:16],)

    def s_k16(c, i):
        acc, = c
        return (acc.at[(ids + i) % V].add(vals16),)

    def s_k128(c, i):
        acc, = c
        return (acc.at[(ids + i) % V].add(vals128),)

    def s_sortseg(c, i):
        acc, = c
        idv = (ids + i) % V
        order = jnp.argsort(idv)
        return (acc + jax.ops.segment_sum(vals16[order], idv[order],
                                          num_segments=V),)

    # ISSUE 13: the purpose-built challenge to the 15 ns/row floor — the
    # VMEM-resident packed Pallas scatter (ops/scatter.py), unsorted
    # (duplicate-safe serial accumulate) and behind the sorted-segment
    # merge. On non-TPU platforms the gate falls back to .at[].add, so
    # these rows only mean something from a bench-chip run.
    from paddle_tpu.ops.scatter import scatter_add_rows

    def s_pallas(c, i):
        acc, = c
        return (scatter_add_rows(acc, (ids + i) % V, vals16, sort=False),)

    def s_pallas_sorted(c, i):
        acc, = c
        return (scatter_add_rows(acc, (ids + i) % V, vals16, sort=True),)

    # the DeepFM bench's REAL fused table is [V, 32] f32 (embedding_size
    # 16 pads to pow2 32) — 12.8 MB packed, over the default VMEM
    # budget; this case runs with the budget raised to 14 MB so the
    # on-chip A/B answers whether Mosaic fits it (ops/scatter.py note)
    vals32 = jnp.asarray(rng.randn(N, 32).astype(np.float32))

    def s_pallas_w32(c, i):
        acc, = c
        os.environ["PADDLE_TPU_SCATTER_VMEM_MB"] = "14"
        try:
            return (scatter_add_rows(acc, (ids + i) % V, vals32,
                                     sort=False),)
        finally:
            os.environ.pop("PADDLE_TPU_SCATTER_VMEM_MB", None)

    cases = [
        ("g_k16", g_k16, (jnp.zeros(16),), N * 16 * 4),
        ("g_k128", g_k128, (jnp.zeros(128),), N * 128 * 4),
        ("g_pack8", g_pack8, (jnp.zeros(16),), N * 128 * 4),
        ("g_onehot(4096)", g_onehot, (jnp.zeros(16),), 0),
        ("s_k16", s_k16, (jnp.zeros((V, 16)),), N * 16 * 4 * 2),
        ("s_k128", s_k128, (jnp.zeros((V, 128)),), N * 128 * 4 * 2),
        ("s_sortseg", s_sortseg, (jnp.zeros((V, 16)),), N * 16 * 4 * 2),
        ("s_pallas", s_pallas, (jnp.zeros((V, 16)),), N * 16 * 4 * 2),
        ("s_pallas_sorted", s_pallas_sorted, (jnp.zeros((V, 16)),),
         N * 16 * 4 * 2),
        ("s_pallas_w32", s_pallas_w32, (jnp.zeros((V, 32)),),
         N * 32 * 4 * 2),
    ]
    write = "--write" in sys.argv
    only = [a for a in sys.argv[1:] if not a.startswith("--")] or None
    measured = {}
    for name, fn, init, bytes_ in cases:
        if only and not any(o in name for o in only):
            continue
        try:
            dt = slope_time(fn, init)
        except Exception as e:
            print("%-16s FAILED %s" % (name, str(e)[:80]))
            measured[name] = None
            continue
        gbs = bytes_ / dt / 1e9 if bytes_ else 0
        ns_row = dt / N * 1e9
        measured[name] = round(ns_row, 2)
        print("%-16s %9.3f ms  %7.1f GB/s  (%.0f ns/row)"
              % (name, dt * 1e3, gbs, ns_row))
    if write:
        _write_floors(measured)


def _write_floors(measured):
    """Commit ROW_OP_FLOORS.json (beside bench.py). Operative constants =
    the best measured gather / scatter; the per-case matrix rides along
    so losing kernels stay on record (the honest-negative-result form)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("--write refused: floors are chip properties and this is "
              "platform=%r (run on the bench chip)" % dev.platform)
        return
    gathers = {k: v for k, v in measured.items()
               if k.startswith("g_") and "onehot" not in k and v}
    scatters = {k: v for k, v in measured.items()
                if k.startswith("s_") and v}
    if not gathers or not scatters:
        print("--write refused: need at least one gather and one scatter "
              "measurement (got %s)" % sorted(measured))
        return
    g_best = min(gathers, key=gathers.get)
    s_best = min(scatters, key=scatters.get)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ROW_OP_FLOORS.json")
    rec = {
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "gather_ns_per_row": gathers[g_best],
        "scatter_ns_per_row": scatters[s_best],
        "gather_kernel": g_best,
        "scatter_kernel": s_best,
        "matrix_ns_per_row": measured,
        "provenance": "tools/bench_gather.py --write (V=%d, N=%d)"
                      % (V, N),
    }
    line = json.dumps(rec)
    print(line)
    with open(out, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
