"""Device-time measurement of the block flash kernels at long-context
shapes, via jax.profiler xplane parsing (kernel time is the sum of the
kernel's device events, not a host clock).

Usage: python tools/attn_device_time.py [variant ...]
Variants: fwd/bwd x causal/full x drop0/drop1, fakeexp ablations.
"""
import os
import sys
import shutil
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

BH, T, D = 128, 2048, 64
STEPS = 10


def device_ms(fn, args, tag):
    """Total 'XLA Ops' device seconds per invocation of fn."""
    from tools.profile_bench import parse_xplane

    jfn = jax.jit(fn)
    out = jfn(*args)
    np.asarray(jnp.sum(out[0] if isinstance(out, tuple) else out)
               .astype(jnp.float32))
    td = "/tmp/attn-prof-%s" % tag
    shutil.rmtree(td, ignore_errors=True)
    jax.profiler.start_trace(td)
    for _ in range(STEPS):
        out = jfn(*args)
    np.asarray(jnp.sum(out[0] if isinstance(out, tuple) else out)
               .astype(jnp.float32))
    jax.profiler.stop_trace()
    rows = [r for r in parse_xplane(td) if r[1] == "XLA Ops"]
    total = sum(r[3] for r in rows)
    bycat = defaultdict(float)
    for _, _, name, dur in rows:
        key = ("pallas" if ("custom-call" in name.lower()
                            or "flash" in name.lower()) else "other")
        bycat[key] += dur
    return (total / STEPS * 1e3, bycat["pallas"] / STEPS * 1e3,
            bycat["other"] / STEPS * 1e3)


def main():
    from paddle_tpu.ops import flash_attention as mod

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(BH, T, D) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(BH, T, D) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(BH, T, D) * 0.3, jnp.bfloat16)
    scale = 1.0 / np.sqrt(D)
    real_exp, real_log = jnp.exp, jnp.log

    def fwd(causal, drop):
        return lambda qq, kk, vv: mod._flash_attention(
            qq, kk, vv, None, jnp.uint32(7), causal, scale, drop)

    def fwdbwd(causal, drop):
        def f(qq, kk, vv):
            def loss(a, b, c):
                o = mod._flash_attention(a, b, c, None, jnp.uint32(7),
                                         causal, scale, drop)
                return jnp.sum(o.astype(jnp.float32) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(qq, kk, vv)
        return f

    cases = []
    for name, mk in (("fwd", fwd), ("fwdbwd", fwdbwd)):
        for causal in (False, True):
            for drop in (0.0, 0.1):
                cases.append(("%s_c%d_d%d" % (name, causal, int(drop * 10)),
                              mk(causal, drop), False))
    cases.append(("fwd_c0_d0_FAKEEXP", fwd(False, 0.0), True))
    cases.append(("fwdbwd_c1_d0_FAKEEXP", fwdbwd(True, 0.0), True))

    only = sys.argv[1:] or None
    for tag, fn, fake in cases:
        if only and not any(o in tag for o in only):
            continue
        if fake:
            jnp.exp = lambda x: x * 1.0009 + 0.1
            jnp.log = lambda x: x * 0.999
        try:
            tot, pallas, other = device_ms(fn, (q, k, v), tag)
        finally:
            jnp.exp, jnp.log = real_exp, real_log
        print("%-22s total %7.3f ms  pallas %7.3f  other %7.3f"
              % (tag, tot, pallas, other))


if __name__ == "__main__":
    main()
