"""A/B harness for the dense attention kernel at bench shapes.

Times fwd and fwd+bwd of the repo kernel on the real chip. Calls are
chained on-device inside one jit (output fed back as input) so
dispatch latency cancels out; reported per-iteration time is
(t(N iters) - t(1 iter)) / (N - 1).

Usage: python tools/bench_attention.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

N_ITERS = 50


def timeit_chain(make_loop, *args):
    f1 = jax.jit(make_loop(1))
    fn = jax.jit(make_loop(N_ITERS))
    jax.block_until_ready(f1(*args))
    jax.block_until_ready(fn(*args))

    def wall(f):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        return time.perf_counter() - t0

    t1 = min(wall(f1) for _ in range(3))
    tn = min(wall(fn) for _ in range(3))
    return (tn - t1) / (N_ITERS - 1) * 1e3


def main():
    from paddle_tpu.ops import flash_attention as fa

    B, H, T, D = 128, 8, 256, 64
    HD = H * D
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, HD) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, HD) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, HD) * 0.3, jnp.bfloat16)
    bias = jnp.asarray(np.where(rng.rand(B, T) > 0.2, 0.0, -1e9),
                       jnp.float32)
    scale = 1.0 / np.sqrt(D)

    for causal, use_bias, rate in [(False, True, 0.0), (True, False, 0.0),
                                   (False, True, 0.1), (True, False, 0.1)]:
        tag = "causal=%d bias=%d drop=%.1f" % (causal, use_bias, rate)
        bb = bias if use_bias else None

        def kernel(qq, kk, vv):
            return fa._dense_attention(qq, kk, vv, bb, jnp.uint32(7), H,
                                       causal, scale, rate)

        def make_fwd(n):
            def run(q, k, v):
                def body(i, qq):
                    return kernel(qq, k, v)
                return jax.lax.fori_loop(0, n, body, q)
            return run

        def make_fwdbwd(n):
            def run(q, k, v):
                def body(i, carry):
                    qq, kk, vv = carry
                    def loss(a, b, c):
                        o = kernel(a, b, c)
                        return jnp.sum(o.astype(jnp.float32) ** 2)
                    g = jax.grad(loss, argnums=(0, 1, 2))(qq, kk, vv)
                    return tuple(x.astype(jnp.bfloat16) * 1e-3 for x in g)
                return jax.lax.fori_loop(0, n, body, (q, k, v))
            return run

        print("%s  fwd %.3f ms   fwd+bwd %.3f ms"
              % (tag, timeit_chain(make_fwd, q, k, v),
                 timeit_chain(make_fwdbwd, q, k, v)))


if __name__ == "__main__":
    main()
