"""Gated delta rule (Gated DeltaNet linear attention), chunked.

A head keeps a state ``S`` [Dk, Dv], from zero, and per token ``t`` with
decay ``g_t <= 0`` and write strength ``beta_t``::

    S <- exp(g_t) * S
    r  = S^T k_t
    S <- S + k_t (x) (beta_t * (v_t - r))
    o_t = S^T q_t

:func:`recurrent_gated_delta_rule` computes exactly that, token by token (a
``lax.scan``; what the tests hold the chunked form to).
:func:`chunk_gated_delta_rule` computes the same in chunks of ``chunk``
tokens (the WY form): inside a chunk the updates of all its tokens are
solved at once from a unit lower-triangular system (``(I + A) U = rhs``,
``A = strict_tril(beta k k^T * decay)``, inverted block-wise by matrix
products), and only the state crosses from
chunk to chunk, so the sequential part is ``T / chunk`` steps of four small
matrix products a head instead of ``T`` rank-one updates. The chunks are
walked in groups of 16: what does not read the state (the triangular
systems, the in-chunk scores) is computed for all chunks of a group at once
before the group's scan.

The backward pass is plain autodiff through the chunk scan (no
``custom_vjp``), with groups of 16 chunks recomputed in it
(``jax.checkpoint``), so a layer keeps its inputs and one state a group.
There is no Pallas kernel yet; the chunked ``jnp`` form is what runs on
every placement, and the site's gate says so.

Matrix products take operands in ``mxu_dtype`` (bfloat16 under AMP) and
accumulate in float32; decays, the triangular solve and the carried state
are float32.
"""

import jax
import jax.numpy as jnp

__all__ = ["chunk_gated_delta_rule", "recurrent_gated_delta_rule",
           "gated_delta_attention"]


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """q, k: [B, T, H, Dk]; v: [B, T, H, Dv]; g, beta: [B, T, H].
    Returns [B, T, H, Dv], float32. One token at a time."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - r))
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(out, 0, 1)


_HIGHEST = jax.lax.Precision.HIGHEST
_BASE = 16


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C], in
    float32, by matrix products (XLA's batched ``triangular_solve`` took
    66 of 126 ms a step on the v5e; my chip run, PR 26). Diagonal blocks of
    16 are inverted by forward substitution, a row at a time (row ``i`` of
    the inverse is ``e_i - A[i, :i] @ rows[:i]``: exact, no power of ``A``
    is formed, so nothing grows where keys repeat); blocks are then merged
    two by two, ``[[P, 0], [-Q A21 P, Q]]``."""
    c = a.shape[-1]
    base = min(_BASE, c)
    if c % base or (c // base) & (c // base - 1):
        base = c        # no power-of-two number of blocks: one block

    def block(i, j, size):
        """Block (i, j) of ``a`` cut into ``size`` x ``size`` blocks (static
        slices: their transpose is a pad, where a gather's is a scatter)."""
        return a[..., i * size:(i + 1) * size, j * size:(j + 1) * size]

    diag = jnp.stack([block(i, i, base) for i in range(c // base)], -3)
    eye = jnp.eye(base, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (base,))]
    for i in range(1, base):
        done = jnp.stack(rows, axis=-2)                    # [.., i, base]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], done, precision=_HIGHEST))
    inv = jnp.stack(rows, axis=-2)                         # [.., n, b, b]
    size = base
    while size < c:
        p, q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        a21 = jnp.stack([block(2 * i + 1, 2 * i, size)
                         for i in range(c // (2 * size))], -3)
        low = -jnp.einsum("...ij,...jk,...kl->...il", q, a21, p,
                          precision=_HIGHEST)
        top = jnp.concatenate([p, jnp.zeros_like(p)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([low, q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _chunks_from(state, q, k, v, g, beta, chunk, mx):
    """The chunked rule over ``n * chunk`` tokens from ``state`` [B, H, Dk,
    Dv]. Returns (final state, out [B, n * chunk, H, Dv])."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(mx), y.astype(mx),
                          preferred_element_type=f32)

    def chunks(x):  # [B, T, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = (chunks(x.astype(f32)) for x in (q, k, v))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gc = jnp.cumsum(g, axis=-1)                          # [B, H, N, C]
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    strict = at[:, None] > at[None, :]
    # decay from token j to token i of a chunk, i >= j (elsewhere the
    # difference is positive and is never exponentiated)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta = k * beta[..., None]
    a = jnp.where(strict, mm("bhnik,bhnjk->bhnij", k_beta, k) * decay, 0.0)
    rhs = jnp.concatenate(
        [v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1)
    solved = jnp.einsum("...ij,...jd->...id", _unit_lower_inverse(a), rhs,
                        precision=_HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    scores = mm("bhnik,bhnjk->bhnij", q, k) * decay
    q_in = q * jnp.exp(gc)[..., None]                    # reads the state
    g_last = gc[..., -1]                                 # [B, H, N]
    k_out = k * jnp.exp(g_last[..., None] - gc)[..., None]

    def step(s, xs):
        u_i, w_i, scores_i, q_i, k_i, decay_i = xs
        v_new = u_i - mm("bhck,bhkv->bhcv", w_i, s)
        o_i = mm("bhck,bhkv->bhcv", q_i, s) \
            + mm("bhij,bhjv->bhiv", scores_i, v_new)
        s = s * decay_i[..., None, None] \
            + mm("bhck,bhcv->bhkv", k_i, v_new)
        return s, o_i

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in
               (u, w.astype(mx), scores.astype(mx), q_in.astype(mx),
                k_out.astype(mx), jnp.exp(g_last)))
    state, out = jax.lax.scan(step, state, xs)
    out = jnp.moveaxis(out, 0, 2)                        # [B, H, N, C, Dv]
    return state, jnp.moveaxis(out, 1, 3).reshape(b, t, h, dv)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, mxu_dtype=None,
                           group=16, prepare=None, heads=None):
    """Same contract as :func:`recurrent_gated_delta_rule`, in chunks.
    ``T`` need not be a multiple of ``chunk``: the tail is padded with
    tokens that neither decay nor write. The chunks are walked in groups of
    ``group``: a group's in-chunk work is done for all its chunks at once,
    and each group is recomputed in the backward pass (``jax.checkpoint``),
    so that what is kept a layer is the inputs and one state a group, not
    every in-chunk tensor of the whole row (2.9 GB a layer at T = 8192,
    compiled for a v5e). ``prepare``, where given, maps a group's slices
    of the five inputs to the rule's (q, k, v, g, beta) inside the
    recomputed region (``heads``: the heads it gives), so that what it
    makes (normalised, repeated float32 heads) is not kept either."""
    f32 = jnp.float32
    mx = mxu_dtype or f32
    b, t = q.shape[:2]
    h, dk, dv = heads or q.shape[2], q.shape[-1], v.shape[-1]
    n = -(-t // chunk)
    group = min(group, n)
    span = group * chunk
    pad = (-t) % span
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))

    def groups(x):  # [B, T, ...] -> [T / span, B, span, ...]
        return jnp.moveaxis(
            x.reshape((b, (t + pad) // span, span) + x.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_group(state, xs):
        if prepare is not None:
            xs = prepare(*xs)
        return _chunks_from(state, *xs, chunk, mx)

    _, out = jax.lax.scan(one_group, jnp.zeros((b, h, dk, dv), f32),
                          tuple(groups(x) for x in (q, k, v, g, beta)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, dv)
    return out[:, :t]


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_attention(q, k, v, a, b, a_log, dt_bias, num_k_heads,
                          num_v_heads, chunk=64, mxu_dtype=None):
    """The Gated DeltaNet core on packed heads. q, k: [B, T, Hk*Dk] (after
    the causal convolution and SiLU); v: [B, T, Hv*Dv]; ``a``, ``b``:
    [B, T, Hv], the raw decay and write-strength projections; ``a_log``,
    ``dt_bias``: [Hv]. ``beta = sigmoid(b)``, ``g = -exp(a_log) *
    softplus(a + dt_bias)``; q and k are L2-normalised over the head
    dimension, repeated to the value heads (value head ``h`` reads key head
    ``h // (Hv / Hk)``), q scaled by ``Dk ** -0.5``. Returns
    [B, T, Hv*Dv] in float32."""
    f32 = jnp.float32
    bsz, t, _ = q.shape
    dk = q.shape[-1] // num_k_heads
    dv = v.shape[-1] // num_v_heads
    rep = num_v_heads // num_k_heads

    def prepare(q, k, v, a, b):
        beta = jax.nn.sigmoid(b.astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))

        def heads(x):
            x = _l2norm(x.astype(f32))
            return jnp.repeat(x, rep, axis=2) if rep > 1 else x

        return heads(q) * (dk ** -0.5), heads(k), v, g, beta

    # tail padding (to whole groups of chunks) is raw zeros: such a token
    # has k = 0, so it writes nothing, and it decays a state that nothing
    # reads any more
    out = chunk_gated_delta_rule(
        q.reshape(bsz, t, num_k_heads, dk), k.reshape(bsz, t, num_k_heads,
                                                      dk),
        v.reshape(bsz, t, num_v_heads, dv), a, b, chunk, mxu_dtype,
        prepare=prepare, heads=num_v_heads)
    return out.reshape(bsz, t, num_v_heads * dv)
