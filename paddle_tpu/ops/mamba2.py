"""Mamba-2 state-space scan (the SSD core of a Mamba-2 mixer), chunked.

A head ``h`` of ``P`` channels keeps a state ``S`` [P, N], from zero. Its
group ``g = h // (H / G)`` gives the input and output maps ``B_t``, ``C_t``
[N]; per token ``t`` with step ``dt_t > 0`` and ``A_h < 0``::

    S   <- exp(dt_t * A_h) * S + dt_t * x_t (x) B_t
    y_t  = S C_t + D_h * x_t

:func:`recurrent_mamba2` computes exactly that, token by token (a
``lax.scan``; what the tests hold the chunked form to).
:func:`chunk_mamba2` computes the same in chunks of ``chunk`` tokens (the
state-space-duality form of the Mamba-2 paper): inside a chunk ``y`` is a
masked matrix product, ``(C B^T * decay) (dt x)``; every chunk's own
addition to the state is one product; and because the state enters
linearly, the state that enters each chunk of a group is one more product
over the decays between the group's chunks. No loop runs over tokens or
over chunks: the only sequential part is the walk over groups of chunks
(``chunk_scan.scan_groups``, 16 chunks a group, a static trip count, each
group recomputed in the backward pass), so reverse mode needs no loop of
dynamic length and keeps the inputs and one state a group.

Matrix products take operands in ``mxu_dtype`` (bfloat16 under AMP) and
accumulate in float32; steps, decays, cumulative sums and the carried state
are float32. :func:`mamba2_ssd` is the op's entry: packed heads, ``dt =
softplus(raw + dt_bias)``, ``A = -exp(A_log)``.
"""

import jax
import jax.numpy as jnp

from .chunk_scan import in_chunk_decays, scan_groups
from .gates import GateDecision, GateReason

__all__ = ["recurrent_mamba2", "chunk_mamba2", "mamba2_ssd", "plan_for"]

_HIGHEST = jax.lax.Precision.HIGHEST


def recurrent_mamba2(x, dt, a, bm, cm, d):
    """x: [B, T, H, P]; dt: [B, T, H] (> 0); a: [H] (< 0); bm, cm:
    [B, T, G, N]; d: [H]. Returns [B, T, H, P], float32. One token at a
    time."""
    f32 = jnp.float32
    x, dt, a, bm, cm, d = (v.astype(f32) for v in (x, dt, a, bm, cm, d))
    b, _, h, p = x.shape
    rep = h // bm.shape[2]

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        s = s * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t)
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + d[:, None] * x_t

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, p, bm.shape[-1]), f32), xs)
    return jnp.moveaxis(out, 0, 1)


def _chunks_from(state, x, dt, g, bm, cm, chunk, mx):
    """The chunked scan over ``n * chunk`` tokens from ``state`` [B, G, R,
    P, N] (R heads a group). x: [B, T, H, P]; dt, g: [B, T, H] float32, the
    step and the log-decay ``dt * A``; bm, cm: [B, T, G, N]. Returns (final
    state, y [B, T, H, P] float32, without the ``D x`` term)."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    groups = bm.shape[2]
    rep = h // groups
    n = t // chunk

    def mm(spec, *operands):
        return jnp.einsum(spec, *(v.astype(mx) for v in operands),
                          preferred_element_type=f32)

    def chunks(v):  # [B, T, ...] -> [B, n, C, ...]
        return v.reshape((b, n, chunk) + v.shape[2:])

    def heads(v):   # [B, n, C, H] -> [B, G, R, n, C]
        return jnp.moveaxis(v, 3, 1).reshape(b, groups, rep, n, chunk)

    gc, decay = in_chunk_decays(heads(chunks(g)))  # [B,G,R,n,C], [..,C,C]
    # dt x: what a token writes, [B, n, C, G, R, P]
    xw = chunks(x.astype(f32) * dt[..., None]).reshape(
        b, n, chunk, groups, rep, p)
    bm, cm = chunks(bm), chunks(cm)                # [B, n, C, G, N]
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) decay_ij (dt x)_j
    scores = mm("bnigs,bnjgs->bgnij", cm, bm)      # [B, G, n, C, C]
    y = mm("bgrnij,bnjgrp->bnigrp", scores[:, :, None] * decay, xw)
    # each chunk's own addition to the state, decayed to the chunk's end
    g_last = gc[..., -1]                           # [B, G, R, n]
    to_end = jnp.exp(g_last[..., None] - gc)       # [B, G, R, n, C]
    added = mm("bnjgrp,bnjgs->bngrps",
               xw * jnp.moveaxis(to_end, (1, 2), (3, 4))[..., None], bm)
    # the state that enters chunk c: what entered the group, decayed over
    # chunks 0..c-1, plus every earlier chunk's addition, decayed over the
    # chunks between (the state enters linearly, so this is one product)
    total = jnp.cumsum(g_last, axis=-1)            # through chunk c
    before = total - g_last                        # through chunk c - 1
    at = jnp.arange(n)
    earlier = at[:, None] > at[None, :]
    between = jnp.where(earlier, jnp.exp(jnp.where(
        earlier, before[..., :, None] - total[..., None, :], 0.0)), 0.0)
    entering = jnp.einsum("bgrcz,bzgrps->bcgrps", between, added,
                          precision=_HIGHEST) \
        + jnp.moveaxis(jnp.exp(before), 3, 1)[..., None, None] \
        * state[:, None]
    y = y + mm("bnigs,bngrps->bnigrp", cm, entering) \
        * jnp.moveaxis(jnp.exp(gc), (1, 2), (3, 4))[..., None]
    leave = jnp.exp(total[..., -1:] - total)       # chunk c's end to the last
    state = jnp.einsum("bgrz,bzgrps->bgrps", leave, added,
                       precision=_HIGHEST) \
        + jnp.exp(total[..., -1])[..., None, None] * state
    return state, y.reshape(b, t, h, p)


def chunk_mamba2(x, dt, a, bm, cm, d, chunk=128, mxu_dtype=None, group=16):
    """Same contract as :func:`recurrent_mamba2`, in chunks. ``T`` need not
    be a multiple of ``chunk``: the tail is padded with tokens of step 0,
    which neither decay nor write."""
    f32 = jnp.float32
    mx = mxu_dtype or f32
    b, t, h, p = x.shape
    groups, n_state = bm.shape[2], bm.shape[3]
    dt = dt.astype(f32)
    group = min(group, -(-t // chunk))

    def one_group(state, xs):
        x_g, dt_g, b_g, c_g = xs
        return _chunks_from(state, x_g, dt_g, dt_g * a.astype(f32), b_g, c_g,
                            chunk, mx)

    y = scan_groups(
        one_group, jnp.zeros((b, groups, h // groups, p, n_state), f32),
        (x, dt, bm, cm), group * chunk)
    return y + d.astype(f32)[:, None] * x.astype(f32)


def plan_for(x, num_heads, chunk):
    """The decision of a ``mamba2_ssd`` site. One form computes it
    everywhere (the chunked ``jnp`` form); the record says so and at which
    shape."""
    _, t, hp = x.shape
    return GateDecision(True, "chunked_jnp", reasons=[GateReason(
        "shape", "%d heads of %d over %d tokens in chunks of %d, groups of "
        "16 chunks recomputed in the backward pass; no kernel for it"
        % (num_heads, hp // num_heads, t, chunk), blocking=False)])


def mamba2_ssd(x, bm, cm, dt, a_log, dt_bias, d, num_heads, num_groups,
               chunk=128, mxu_dtype=None):
    """The Mamba-2 core on packed heads. x: [B, T, H*P] (after the causal
    convolution and SiLU); bm, cm: [B, T, G*N]; dt: [B, T, H], the raw step
    projection; a_log, dt_bias, d: [H]. ``dt = softplus(raw + dt_bias)``,
    ``A = -exp(a_log)``. Returns [B, T, H*P] in float32."""
    f32 = jnp.float32
    b, t, hp = x.shape
    n_state = bm.shape[-1] // num_groups
    step = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    out = chunk_mamba2(
        x.reshape(b, t, num_heads, hp // num_heads), step,
        -jnp.exp(a_log.astype(f32)),
        bm.reshape(b, t, num_groups, n_state),
        cm.reshape(b, t, num_groups, n_state), d, chunk, mxu_dtype)
    return out.reshape(b, t, hp)
