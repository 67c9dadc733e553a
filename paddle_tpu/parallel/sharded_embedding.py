"""Sharded embedding lookup — the pserver / distributed-lookup-table analog.

Reference: params sliced across pservers (``distribute_transpiler.py:84``
slice_variable), trainers pull ONLY the rows they need via prefetch RPC
(``operators/distributed/parameter_prefetch.cc:26`` splits ids by section,
sends each pserver its id packet, receives the matching rows). TPU-native:
the table is row-sharded over a mesh axis and the lookup runs under
shard_map with two formulations:

* **id-routed all-to-all** (default — the faithful prefetch analog): each
  shard takes a 1/mp slice of the replicated id list, bins its ids by
  owning shard (sort-by-owner + within-owner rank -> a [mp, cap] slot
  buffer), ``all_to_all``s the id packets, gathers ONLY the rows it owns
  through the ``packed_take`` fast path, ``all_to_all``s the [cap, D] row
  payloads back, unpermutes, and ``all_gather``s the per-shard slices into
  the replicated output the surrounding program expects. Per-shard ICI
  volume: ``n*D`` row payload + ``n`` ids + the ``(mp-1)/mp * n*D``
  output replication — O(n*D + n), independent of mp. Per-destination
  capacity is the skew-proof ``cap = ceil(n/mp)`` (a shard holds at most
  its whole slice of ids), so ANY id distribution — including every id
  hashing to one shard — is exact; skew costs load imbalance only in the
  valid-slot counts, never correctness. (A sub-``cap`` MoE-style capacity
  factor would cut the padded-slot traffic by ~mp in the balanced case,
  but without ragged collectives overflowed rows would silently drop;
  this framework does not trade correctness for bytes.)
* **psum-of-partials** (the path chosen for degenerate slices, and for a
  caller that names it with ``strategy="psum"``): every shard gathers ALL n
  ids against its local slice (zeros for rows it doesn't own) and one
  psum merges the [n, D] partials — mp redundant full-output gathers and
  O(mp * n * D) total reduced volume, which is what capped mp=8+ scaling
  (ROADMAP item 3).

``choose_strategy`` picks per call: psum only when the per-shard slice is
too small for the sort/route overhead to amortize (``cap < _MIN_CHUNK`` —
the capacity-factor heuristic's degenerate regime). ``comm_bytes_model``
is the analytic bytes of both (re-derivable, not measured).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.op_registry import register, get, put

__all__ = ["sharded_lookup", "choose_strategy", "comm_bytes_model"]

_MIN_CHUNK = 8


def choose_strategy(n_ids, n_shards, width=None):
    """'alltoall' | 'psum' for a lookup of ``n_ids`` over ``n_shards``.

    The routed path wins whenever each shard's id slice (= the skew-proof
    per-destination capacity) is big enough to amortize the on-device
    binning sort and the collective hops; tiny slices (the degenerate
    capacity regime) keep the single fused psum."""
    del width  # volume ratio is width-independent; kept for future tuning
    cap = -(-int(n_ids) // max(int(n_shards), 1))
    if cap < _MIN_CHUNK:
        return "psum"
    return "alltoall"


def comm_bytes_model(n_ids, width, n_shards, esize=4):
    """Analytic per-step ICI bytes of both formulations (re-derivable,
    not measured). DELEGATES to the single comm model in
    ``analysis.cost`` (ISSUE 15): the static SPMD pass's per-collective
    volumes and this module can never disagree about the bytes."""
    from ..analysis.cost import comm_bytes_model as model

    return model(n_ids, width, n_shards, esize=esize)


def _psum_lookup(table, ids, mesh, axis):
    """Legacy formulation: each shard contributes the rows it owns, zeros
    elsewhere — one reduce over the axis, O(mp * n * D) total volume."""
    n_shards = mesh.shape[axis]
    v = table.shape[0]
    rows_per = v // n_shards

    def local_lookup(tab, ids_):
        from ..ops.rowops import packed_take

        idx = jax.lax.axis_index(axis)
        lo = idx * rows_per
        local = ids_ - lo
        mask = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        # shard-local table is unsharded inside shard_map: the packed
        # narrow-row gather applies (ops/rowops.py, 4x the plain rate)
        rows = packed_take(tab, safe)
        rows = rows * mask[..., None].astype(rows.dtype)
        return jax.lax.psum(rows, axis)

    return jax.shard_map(
        local_lookup, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
    )(table, ids)


def _alltoall_lookup(table, ids, mesh, axis):
    """Id-routed formulation (see module docstring). ``ids`` arrives
    replicated (P()); each shard serves the slice it is responsible for
    and the output is re-replicated by one tiled all_gather."""
    m = mesh.shape[axis]
    v, d = table.shape
    rows_per = v // m

    def routed(tab, ids_):
        from ..ops.rowops import packed_take

        n = ids_.shape[0]
        cap = -(-n // m)           # skew-proof per-destination capacity
        n_pad = cap * m
        if n_pad != n:
            # pad with an invalid id: routed to shard 0, masked to a zero
            # row there, sliced off after the gather
            ids_ = jnp.concatenate(
                [ids_, jnp.full((n_pad - n,), -1, jnp.int32)])
        my = jax.lax.axis_index(axis)
        mine = jax.lax.dynamic_slice(ids_, (my * cap,), (cap,))
        # bin by owning shard: out-of-range ids keep the psum path's
        # contract (zero rows) — clip the owner so they route SOMEWHERE
        # and fail the owner-side range mask there
        owner = jnp.clip(mine // max(rows_per, 1), 0, m - 1)
        order = jnp.argsort(owner)
        ids_sorted = mine[order]
        owner_sorted = owner[order]
        first = jnp.searchsorted(owner_sorted, owner_sorted, side="left")
        rank = jnp.arange(cap, dtype=jnp.int32) - first.astype(jnp.int32)
        slot = owner_sorted * cap + rank      # rank < cap by construction
        send_ids = jnp.full((m * cap,), -1, jnp.int32).at[slot].set(
            ids_sorted)
        # route the id packets: recv[s] = the bucket shard s addressed to me
        recv_ids = jax.lax.all_to_all(
            send_ids.reshape(m, cap), axis, 0, 0).reshape(m * cap)
        lo = my * rows_per
        local = recv_ids - lo
        valid = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        # each shard gathers ONLY rows it owns — the packed fast path
        rows = packed_take(tab, safe)
        rows = rows * valid[:, None].astype(rows.dtype)
        # route the row payloads back and unpermute
        back = jax.lax.all_to_all(
            rows.reshape(m, cap, d), axis, 0, 0).reshape(m * cap, d)
        got = back[slot][jnp.argsort(order)]         # [cap, D], my slice
        out = jax.lax.all_gather(got, axis, axis=0, tiled=True)
        return out[:n]

    return jax.shard_map(
        routed, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )(table, ids)


def sharded_lookup(table, ids, mesh, axis="mp", strategy=None):
    """table: [V, D] sharded (axis, None); ids: [...] int32 global ids.
    Returns [..., D] rows (replicated over ``axis``). ``strategy``:
    'alltoall' | 'psum' | None (auto via :func:`choose_strategy`)."""
    idf = ids.reshape(-1).astype(jnp.int32)
    n = idf.shape[0]
    if strategy in (None, "auto"):
        strategy = choose_strategy(n, mesh.shape[axis], table.shape[1])
    if strategy == "psum":
        out = _psum_lookup(table, idf, mesh, axis)
    elif strategy == "alltoall":
        out = _alltoall_lookup(table, idf, mesh, axis)
    else:
        raise ValueError("unknown sharded_lookup strategy %r" % (strategy,))
    return out.reshape(tuple(ids.shape) + (table.shape[1],))


@register("sharded_lookup_table")
def _sharded_lookup_op(env, op):
    """Symbolic op form used when a program is transpiled with
    sharded_embeddings: falls back to plain gather when no mesh is active
    (single chip), so programs are portable."""
    w = get(env, op.input("W"))
    ids = get(env, op.input("Ids")).astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids.squeeze(-1)
    padding_idx = op.attr("padding_idx", -1)
    from .mesh import get_mesh

    mesh = get_mesh()
    axis = op.attr("mesh_axis", "mp")
    if mesh is not None and axis in mesh.axis_names and mesh.shape[axis] > 1:
        out = sharded_lookup(w, ids, mesh, axis,
                             strategy=op.attr("emb_strategy", None))
    else:
        from ..ops.rowops import packed_take

        out = packed_take(w, ids) if w.ndim == 2 else jnp.take(w, ids,
                                                               axis=0)
    if padding_idx is not None and padding_idx >= 0:
        # same contract as lookup_table: padding rows read as zeros (the
        # autodiff sparse sites already zero their gradient slots)
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    from ..core.op_registry import amp_out_cast
    put(env, op.output("Out"), amp_out_cast(out))
