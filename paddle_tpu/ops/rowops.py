"""TPU-native row gather for narrow embedding tables.

A row of a ``[V, K<128]`` f32 table occupies one (8,128) tile row padded
to 128 lanes, so XLA's row gather fetches 512 bytes per row to return
``4*K`` useful ones, and per-row DMA latency dominates: measured 8 ns/row
(7.8 GB/s useful) on v5e regardless of K (round 5, an earlier installation).

``packed_take`` reshapes the table so ``P = 128 // K`` logical rows share
one physical 128-lane row; each gathered 512-byte burst then carries P
candidate rows and a lane-select keeps the wanted one. Measured 2 ns/row,
213 GB/s — 4x faster than the plain gather, ~17x faster than what XLA
emits for the AMP-fused bf16 gather in DeepFM (bf16 sublane-packed tiles
gather ~7x slower than f32, so callers should gather f32 and cast the
[N, K] output instead — ``opimpl/tensor_ops._lookup_table`` does).

Reference capability: ``paddle/fluid/operators/lookup_table_op.cc`` (the
gather kernel; the reference's perf answer to high-dim sparse is the
pserver/pslib path — ours is keeping single-chip row ops at HBM burst
efficiency and sharding tables over the mesh, parallel/sharded_embedding).
"""

import jax
import jax.numpy as jnp

__all__ = ["packed_take", "pack_factor"]

_LANES = 128


def pack_factor(k):
    """How many logical K-rows fit one 128-lane physical row (1 = no
    packing possible; K must divide 128)."""
    if k <= 0 or k >= _LANES or _LANES % k:
        return 1
    return _LANES // k


def packed_take(w, ids):
    """``w[ids]`` for a 2-D ``[V, K]`` table, packing narrow rows so the
    gather moves full 128-lane bursts. Exact (the lane-select adds only
    zeros). Falls back to ``jnp.take`` when K doesn't divide 128.

    ids: any integer shape; returns ``ids.shape + (K,)``.

    Differentiation: custom_vjp — the cotangent of the gather is the row
    scatter-add ``dW[ids] += g``, routed through
    :func:`ops.scatter.scatter_add_rows` (the Pallas VMEM-resident
    kernel when the table qualifies, XLA's ``.at[].add`` otherwise —
    identical math to jax's native vjp of the packed formulation either
    way, so flipping the kernel gate never changes numerics class).
    """
    v, k = w.shape
    p = pack_factor(k)
    if p == 1:
        return jnp.take(w, ids, axis=0)
    idf = ids.reshape(-1).astype(jnp.int32)
    out = _packed_take_flat(w, idf)
    return out.reshape(tuple(ids.shape) + (k,))


def _packed_take_impl(w, idf):
    v, k = w.shape
    p = pack_factor(k)
    n = idf.shape[0]
    vp = -(-v // p)
    pad = vp * p - v
    wp = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
    wp = wp.reshape(vp, p * k)
    rows = wp[idf // p]                              # [n, 128] burst gather
    sub = idf % p
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, p * k), 1) // k
    picked = jnp.where(lane_row == sub[:, None], rows,
                       jnp.zeros((), w.dtype))
    return jnp.sum(picked.reshape(n, p, k), axis=1)


@jax.custom_vjp
def _packed_take_flat(w, idf):
    return _packed_take_impl(w, idf)


def _packed_take_fwd(w, idf):
    # w rides in the residuals only for its shape/dtype: it is a live
    # parameter buffer either way, so this saves nothing extra
    return _packed_take_impl(w, idf), (w, idf)


def _packed_take_bwd(res, g):
    from .scatter import scatter_add_rows

    w, idf = res
    dw = scatter_add_rows(jnp.zeros_like(w), idf, g.astype(w.dtype))
    return dw, None


_packed_take_flat.defvjp(_packed_take_fwd, _packed_take_bwd)
