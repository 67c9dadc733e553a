"""What the chunked linear recurrences share (``gated_delta.py``: the gated
delta rule; ``mamba2.py``: the state-space scan). Both keep a state a head
that decays by ``exp(g_t)``, ``g_t <= 0``, a token, and both are computed in
chunks: inside a chunk everything is matrix products over the decays between
its tokens, and only the state crosses from chunk to chunk.

:func:`in_chunk_decays`: the cumulative log-decays of a chunk and the decay
from token ``j`` to token ``i`` of it. :func:`scan_groups`: the walk over a
row in groups of chunks, the state carried from group to group and each
group recomputed in the backward pass.
"""

import jax
import jax.numpy as jnp

__all__ = ["in_chunk_decays", "scan_groups"]


def in_chunk_decays(g):
    """g: [..., C] float32, the log-decay of each token of a chunk. Returns
    (``gc`` [..., C]: the inclusive cumulative sum; ``decay`` [..., C, C]:
    ``exp(gc_i - gc_j)`` for ``i >= j``, 0 above the diagonal, where the
    difference is positive and is never exponentiated)."""
    gc = jnp.cumsum(g, axis=-1)
    at = jnp.arange(g.shape[-1])
    lower = at[:, None] >= at[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    return gc, jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)


def scan_groups(one_group, state, xs, span):
    """Walk ``xs`` ([B, T, ...] arrays) in groups of ``span`` tokens:
    ``one_group(state, group's slices) -> (state, out [B, span, ...])``.
    ``T`` need not be a multiple of ``span``: the tail is padded with zeros
    (``one_group`` has to take a zero token as one that changes nothing
    anyone reads). Each group is recomputed in the backward pass
    (``jax.checkpoint``), so what is kept is the inputs and one state a
    group. Returns out [B, T, ...]."""
    b, t = xs[0].shape[:2]
    pad = (-t) % span
    if pad:
        xs = tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                   for x in xs)

    def groups(x):  # [B, T, ...] -> [T / span, B, span, ...]
        return jnp.moveaxis(
            x.reshape((b, (t + pad) // span, span) + x.shape[2:]), 1, 0)

    _, out = jax.lax.scan(jax.checkpoint(one_group), state,
                          tuple(groups(x) for x in xs))
    out = jnp.moveaxis(out, 0, 1).reshape((b, t + pad) + out.shape[3:])
    return out[:, :t]
