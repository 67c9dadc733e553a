"""Measure the bench chip's REAL ceilings (matmul TF/s, HBM GB/s) and
emit one JSON line, so every round's vs_baseline can be read against the
same measured roofline (VERDICT r3 ask #9; the r3 numbers lived only in
NOTES prose).

Method: chained on-device loops inside one jit; sync by pulling one f32
scalar to the host. Per-iteration time is the slope between a short and a
long chain, which cancels dispatch latency.

HBM MATRIX (ISSUE 12): the single ``rmw`` loop (read+write of ONE
buffer) that produced the 552 GB/s figure is only one access pattern,
and real workloads stream *several* buffers per pass (a conv reads x and
w and writes y — a triad). The matrix falsifies-or-confirms 552 as THE
ceiling by measuring five patterns:

  rmw      1R+1W, same buffer            (the legacy 552 figure)
  copy     1R+1W, distinct buffers       (ping-pong)
  triad    2R+1W, distinct buffers       (a = b + s*c; STREAM triad)
  read     1R, reduction only            (pure read rate)
  stream4  4R+1W, five distinct buffers  (multi-buffer gather epilogues)

``hbm_operative_gbs`` = max over the measured matrix — the hardest
honest floor basis (a bytes floor computed at a rate the chip never
sustained would flatter x_floor ratios). bench.py reads this field into
every resnet50 record's ``config`` and
``tests/test_bench_contract.py`` pins the sourcing, so a re-derivation
on the bench chip propagates everywhere in one run.

Usage: python tools/chip_ceiling.py [--out CHIP_CEILING.json]
       [--mbytes 512] [--skip-matmul]
"""

import argparse
import json
import os
import time

import numpy as np


def _slope(make_loop, args, n_lo=2, n_hi=12, tries=5):
    import jax

    f_lo, f_hi = jax.jit(make_loop(n_lo)), jax.jit(make_loop(n_hi))
    np.asarray(f_lo(*args))
    np.asarray(f_hi(*args))

    def wall(f):
        best = 1e9
        for _ in range(tries):
            t0 = time.perf_counter()
            np.asarray(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    return (wall(f_hi) - wall(f_lo)) / (n_hi - n_lo)


def matmul_ceiling(dtype, n=8192):
    """Chained n^3 matmuls; returns sustained FLOPs/s."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(np.random.randn(n, n) * 0.01, dtype)
    b = jnp.asarray(np.random.randn(n, n) * 0.01, dtype)

    def make_loop(iters):
        def run(a, b):
            def body(i, x):
                return jax.lax.dot(x, b).astype(dtype) * jnp.asarray(
                    0.999, dtype)
            out = jax.lax.fori_loop(0, iters, body, a)
            return jnp.sum(out.astype(jnp.float32))
        return run

    dt = _slope(make_loop, (a, b))
    return 2.0 * n * n * n / dt


def hbm_ceiling(mbytes=512):
    """Chained elementwise passes over a large f32 array; returns
    sustained read+write bytes/s (the legacy single-buffer RMW pattern)."""
    import jax
    import jax.numpy as jnp

    n = mbytes * 1024 * 1024 // 4
    x = jnp.ones((n,), jnp.float32)

    def make_loop(iters):
        def run(x):
            def body(i, v):
                return v * 1.0000001 + 1e-9
            out = jax.lax.fori_loop(0, iters, body, x)
            return out[0]
        return run

    dt = _slope(make_loop, (x,))
    return 2.0 * n * 4 / dt  # one read + one write per pass


def hbm_copy(mbytes=512):
    """1R+1W across DISTINCT buffers (ping-pong): each iteration reads one
    array and writes a fresh one. Distinguishes same-buffer RMW (which the
    memory controller can stream in place) from a true copy."""
    import jax
    import jax.numpy as jnp

    n = mbytes * 1024 * 1024 // 8  # two live buffers
    a = jnp.ones((n,), jnp.float32)
    b = jnp.full((n,), 1.0000001, jnp.float32)

    def make_loop(iters):
        def run(a, b):
            def body(i, carry):
                x, y = carry
                return y * 1.0000001, x
            x, y = jax.lax.fori_loop(0, iters, body, (a, b))
            return x[0] + y[0]
        return run

    dt = _slope(make_loop, (a, b))
    return 2.0 * n * 4 / dt


def hbm_triad(mbytes=512):
    """STREAM triad: a = b + s*c — 2 reads + 1 write across three
    buffers, the access pattern of a conv/matmul epilogue pass."""
    import jax
    import jax.numpy as jnp

    n = mbytes * 1024 * 1024 // 12  # three live buffers
    bufs = tuple(jnp.full((n,), v, jnp.float32)
                 for v in (1.0, 0.5, 0.25))

    def make_loop(iters):
        def run(a, b, c):
            def body(i, carry):
                a, b, c = carry
                return b, c, b + 0.123456 * c
            a, b, c = jax.lax.fori_loop(0, iters, body, (a, b, c))
            return a[0] + b[0] + c[0]
        return run

    dt = _slope(make_loop, bufs)
    return 3.0 * n * 4 / dt


def hbm_read(mbytes=512):
    """Pure read rate: one full-array reduction per iteration. The
    s-dependent bias term defeats loop-invariant hoisting / algebraic
    refactoring of the reduction."""
    import jax
    import jax.numpy as jnp

    n = mbytes * 1024 * 1024 // 4
    x = jnp.ones((n,), jnp.float32)

    def make_loop(iters):
        def run(x):
            def body(i, s):
                return s * 1e-30 + jnp.sum(jnp.abs(x + s * 1e-30))
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))
        return run

    dt = _slope(make_loop, (x,))
    return 1.0 * n * 4 / dt


def hbm_stream4(mbytes=512):
    """4R+1W across five distinct buffers — the many-operand fusion
    pattern (residual merges, multi-buffer gather epilogues)."""
    import jax
    import jax.numpy as jnp

    n = mbytes * 1024 * 1024 // 20  # five live buffers
    bufs = tuple(jnp.full((n,), 1.0 + 0.1 * i, jnp.float32)
                 for i in range(4))

    def make_loop(iters):
        def run(a, b, c, d):
            def body(i, carry):
                a, b, c, d = carry
                new = 0.25 * a + 0.25 * b + 0.25 * c + 0.25 * d
                return b, c, d, new
            a, b, c, d = jax.lax.fori_loop(0, iters, body, (a, b, c, d))
            return a[0] + b[0] + c[0] + d[0]
        return run

    dt = _slope(make_loop, bufs)
    return 5.0 * n * 4 / dt


def hbm_matrix(mbytes=512):
    """The copy/triad/multi-buffer stream matrix, GB/s per pattern."""
    return {
        "rmw": round(hbm_ceiling(mbytes) / 1e9, 1),
        "copy": round(hbm_copy(mbytes) / 1e9, 1),
        "triad": round(hbm_triad(mbytes) / 1e9, 1),
        "read": round(hbm_read(mbytes) / 1e9, 1),
        "stream4": round(hbm_stream4(mbytes) / 1e9, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="CHIP_CEILING.json")
    ap.add_argument("--mbytes", type=int, default=512,
                    help="total live HBM footprint per stream pattern")
    ap.add_argument("--skip-matmul", action="store_true",
                    help="HBM matrix only (fast re-derivation)")
    args = ap.parse_args()

    import sys

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import _peak_flops  # the per-chip bf16 peak table

    dev = jax.devices()[0]
    matrix = hbm_matrix(args.mbytes)
    prior = {}
    if args.skip_matmul:
        # fast HBM-only re-derivation must MERGE, not clobber: keep the
        # previously measured matmul ceiling in the record
        try:
            with open(args.out) as f:
                prior = json.load(f)
        except (OSError, ValueError):
            prior = {}
    result = {
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "bf16_matmul_tflops": prior.get("bf16_matmul_tflops")
        if args.skip_matmul else round(
            matmul_ceiling(jax.numpy.bfloat16) / 1e12, 1),
        "int8_matmul_tops": None,  # dot(int8) unsupported via this path
        "hbm_stream_gbs": matrix["rmw"],  # legacy field = rmw pattern
        "hbm_matrix": matrix,
        # the operative floor constant: the best rate the chip actually
        # sustained across the matrix (a floor computed at less than this
        # flatters x_floor ratios; at more, it is fiction)
        "hbm_operative_gbs": max(v for v in matrix.values()
                                 if v is not None),
        "nominal_bf16_tflops": round(_peak_flops(dev) / 1e12, 1),
        "nominal_hbm_gbs": 819.0,  # v5e spec; informational only
    }
    if result["bf16_matmul_tflops"]:
        result["fraction_of_nominal_matmul"] = round(
            result["bf16_matmul_tflops"] / result["nominal_bf16_tflops"], 3)
    line = json.dumps(result)
    print(line)
    with open(args.out, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
