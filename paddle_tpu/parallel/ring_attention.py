"""Ring attention: sequence/context parallelism over the mesh.

Absent from the 2019 reference (SURVEY.md §5.7) but first-class here: the
sequence axis is sharded over the ``sp`` mesh axis; K/V blocks rotate around
the ring via ``ppermute`` while each device accumulates online-softmax
partial results for its local Q block. Communication rides ICI and overlaps
with the per-block attention compute.
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention"]


def _block_attn(q, k, v, m_i, l_i, acc, scale, mask=None):
    """One online-softmax accumulation step. q:[B,H,Tq,D] k,v:[B,H,Tk,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m_i), m_i - m_safe, -jnp.inf))
    alpha = jnp.where(jnp.isfinite(alpha), alpha, 0.0)
    l_new = alpha * l_i + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                  p.astype(v.dtype), v)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None):
    """q,k,v: [B, H, T, D] with T sharded over ``axis``. Returns same shape.

    Each of the N ring steps: attend to the currently-held K/V block, then
    ppermute K/V to the next neighbor. Causal masking uses global positions
    derived from the ring step."""
    n = mesh.shape[axis]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def local_fn(ql, kl, vl):
        my = jax.lax.axis_index(axis)
        t_local = ql.shape[2]
        b, h = ql.shape[0], ql.shape[1]
        m_i = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
        l_i = jnp.zeros((b, h, t_local), jnp.float32)
        acc = jnp.zeros(ql.shape, jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(s, carry):
            kb, vb, m_i, l_i, acc = carry
            # block s currently holds K/V originally from shard (my - s) % n
            src = (my - s) % n
            if causal:
                q_pos = my * t_local + jnp.arange(t_local)
                k_pos = src * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                mask = mask[None, None]
            else:
                mask = None
            m_i, l_i, acc = _block_attn(ql.astype(jnp.float32),
                                        kb.astype(jnp.float32),
                                        vb.astype(jnp.float32),
                                        m_i, l_i, acc, scale, mask)
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return kb, vb, m_i, l_i, acc

        kb, vb = kl, vl
        carry = (kb, vb, m_i, l_i, acc)
        for s in range(n):  # unrolled: n is small (mesh axis size)
            carry = step(s, carry)
        _, _, m_i, l_i, acc = carry
        out = acc / jnp.maximum(l_i, 1e-30)[..., None]
        return out.astype(q.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
    )(q, k, v)
