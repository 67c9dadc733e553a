"""TPU-native row scatter-add for narrow embedding tables.

The backward of every embedding-bound model is a row scatter-add
(``grad_table.at[ids].add(grad_rows)``), and round 5 read XLA's
lowering at ~15 ns/row regardless of row width — a per-row HBM
read-modify-write DMA each (set on an installation that is gone; not
measured since: ROADMAP.md R9). This module is the purpose-built
challenge to that figure: for tables whose PACKED layout
fits VMEM, the scatter runs as a Pallas kernel that

  1. streams the table HBM->VMEM once (as the kernel's aliased output
     block) in the packed ``P = 128 // K`` rows-per-128-lane layout of
     ``ops/rowops.py`` — so a [100000, 16] f32 table is a 6.4 MB VMEM
     resident, not a 51 MB lane-padded one;
  2. accumulates every (row, value) pair into the VMEM-resident table with
     a lane-positioned masked add (row -> (row // P, lanes (row % P)*K..)),
     so duplicate ids cost a VMEM add, never an HBM round trip; and
  3. streams the table back VMEM->HBM once.

Total HBM traffic: ``2*V*K + N*K`` bytes instead of N serialized row RMW
DMAs — at the DeepFM BASELINE shape (V=100k, K=16, N=212992) that is ~26 MB
of streaming vs 212992 latency-bound DMAs, a ~50x headroom if the VMEM
accumulate loop keeps up. The sorted-segment formulation the ISSUE names
(sort ids, segment-reduce duplicates, one dense store per unique row) is
kept as a variant a caller names (``sort=True``): sorting buys store
locality but costs an argsort (~7 ms/step at that shape then — see
``control_ops`` merge note), so the default path is unsorted and
duplicate-safe by serial accumulation. Neither has been timed on the chip
against ``.at[ids].add``: no cell runs a sparse step (ROADMAP R9, D17).

Reference capability: ``operators/math/selected_rows_functor.cc`` MergeAdd
+ the SelectedRows optimizer kernels — the reference's answer to sparse
rows is pserver-side partial tables; ours is keeping the whole narrow
table VMEM-resident for the duration of one scatter pass.
"""

import functools

import jax
import jax.numpy as jnp

from .gates import GateDecision, GateReason, platform_reason
from .kernel_names import named_pallas_call

__all__ = ["scatter_add_rows", "gate", "use_pallas", "packed_vmem_bytes"]

_LANES = 128
_INTERPRET = False  # tests flip this to run the kernel on CPU

# The packed table + one double-buffered vals block must fit comfortably;
# leave headroom for the vals stream and compiler temporaries. 10 MB
# admits the [100k, 16] f32 microbench table (6.4 MB packed) but NOT the
# DeepFM BASELINE shape's [100k, 32] f32 fused table (12.8 MB packed).
_VMEM_BUDGET = 10 * 1024 * 1024
_CHUNK = 1024  # (rows, vals) slots processed per grid step
# The whole int32 row-id vector is the kernel's scalar-prefetch operand and
# must sit in the chip's 1 MiB of SMEM beside Mosaic's own scalars; the
# compiler refuses the program otherwise. _SMEM_IDS_BYTES is what the gate
# lets the ids take.
_SMEM_IDS_BYTES = 768 * 1024


def packed_vmem_bytes(v, k, esize):
    """VMEM bytes of the [Vp, P*K] packed table block (the kernel's
    resident accumulator)."""
    from .rowops import pack_factor

    p = pack_factor(k)
    vp = -(-v // p)
    width = p * k
    # lane dim pads to 128, sublane to the dtype tile height
    width_pad = -(-width // _LANES) * _LANES
    sub = {2: 16, 4: 8}.get(esize, 8)
    vp_pad = -(-vp // sub) * sub
    return vp_pad * width_pad * esize


def gate(v, k, n, dtype, static_only=False):
    """Structured gate (``ops.gates.GateDecision``): the packed table
    fits the VMEM budget, the row width packs (or is already
    lane-aligned), and we are on a single TPU (a mesh would make the
    custom call fight GSPMD) or under the test interpreter.
    ``static_only=True`` evaluates ONLY the shape/dtype/VMEM checks —
    the platform-independent view the static resource pass wants."""
    from .rowops import pack_factor

    reasons = []
    if k <= 0 or v <= 0 or n <= 0:
        reasons.append(GateReason(
            "geometry", "degenerate table/update shape [%d, %d] x %d rows"
            % (v, k, n)))
    elif pack_factor(k) == 1 and k % _LANES:
        reasons.append(GateReason(
            "geometry", "row width %d neither packs into 128 lanes nor "
            "aligns to them: lane padding would explode VMEM" % k))
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        reasons.append(GateReason(
            "dtype", "%s table: grad surfaces are float; int tables keep "
            "the XLA scatter" % dt))
        esize = 4
    else:
        esize = dt.itemsize
        if esize not in (2, 4):
            reasons.append(GateReason(
                "dtype", "%d-byte float rows unsupported" % esize))
    if not reasons:
        need = packed_vmem_bytes(v, k, esize) \
            + 2 * _CHUNK * max(k, _LANES) * esize
        if need > _VMEM_BUDGET:
            reasons.append(GateReason(
                "vmem", "packed [%d, %d] table + vals stream needs %.1f "
                "MB VMEM, budget is %.1f MB"
                % (v, k, need / 2**20, _VMEM_BUDGET / 2**20)))
        ids_bytes = 4 * _padded_ids(n)
        if ids_bytes > _SMEM_IDS_BYTES:
            reasons.append(GateReason(
                "smem", "%d prefetched row ids need %.0f KiB of SMEM, the "
                "kernel may take %.0f KiB"
                % (n, ids_bytes / 1024, _SMEM_IDS_BYTES / 1024)))
    if not static_only and not reasons:
        platform = platform_reason(_INTERPRET)
        if platform is not None:
            reasons.append(platform)
    if reasons:
        return GateDecision(False, "xla_at_add", fallback="pallas_rowbin",
                            reasons=reasons)
    return GateDecision(True, "pallas_rowbin")


def use_pallas(v, k, n, dtype):
    """Boolean view of :func:`gate` (the pre-ISSUE-15 surface)."""
    return gate(v, k, n, dtype).admitted


def record_choice(op, v, k, n, dtype):
    """Evaluate the gate and record the structured decision in the
    consuming op's attrs (``_kernel_choice``) — trace-time, so a built
    program carries which kernel its sparse updates actually take and
    why (the ISSUE 15 no-silent-fallback contract)."""
    from .gates import note

    decision = note("scatter_add_rows", gate(v, k, n, dtype))
    if op is not None:
        op.attrs["_kernel_choice"] = decision.to_dict()
    return decision


def _scatter_kernel(rows_ref, vals_ref, tab_in_ref, out_ref, *, chunk, p, k,
                    vp):
    """One grid step: fold ``chunk`` (row, value) pairs into the
    VMEM-resident packed table. ``rows_ref`` is scalar-prefetched so the
    serial accumulate loop reads indices from SMEM; sentinel rows
    (>= vp*p: out-of-range ids, padding slots, merged-duplicate parking)
    skip the store. out_ref aliases the packed table input — pallas
    streams it HBM->VMEM once, every add below is a VMEM op, and the
    final writeback is the only other HBM pass."""
    from jax.experimental import pallas as pl

    del tab_in_ref  # aliased with out_ref; present only for the alias slot
    base = pl.program_id(0) * chunk
    lane_grp = jax.lax.broadcasted_iota(jnp.int32, (1, p * k), 1) // k

    def body(i, carry):
        r = rows_ref[base + i]

        @pl.when(r < vp * p)
        def _():
            v = vals_ref[i, :].reshape(1, k)
            v_tiled = jnp.concatenate([v] * p, axis=1) if p > 1 else v
            pos = jnp.where(lane_grp == r % p, v_tiled,
                            jnp.zeros_like(v_tiled))
            out_ref[pl.ds(r // p, 1), :] += pos

        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def _chunk(n):
    return min(_CHUNK, n) if n % _CHUNK else _CHUNK


def _padded_ids(n):
    """Length of the id vector the kernel prefetches: n rounded up to
    whole grid steps."""
    chunk = _chunk(n)
    return -(-n // chunk) * chunk


def _scatter_packed_call(bp, rows, vals, p, k, vp):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = rows.shape[0]
    chunk = _chunk(n)
    n_pad = _padded_ids(n)
    if n_pad != n:
        rows = jnp.concatenate(
            [rows, jnp.full((n_pad - n,), vp * p, jnp.int32)])
        vals = jnp.concatenate(
            [vals, jnp.zeros((n_pad - n, k), vals.dtype)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // chunk,),
        in_specs=[pl.BlockSpec((chunk, k), lambda i, rr: (i, 0)),
                  pl.BlockSpec((vp, p * k), lambda i, rr: (0, 0))],
        out_specs=pl.BlockSpec((vp, p * k), lambda i, rr: (0, 0)),
    )
    return named_pallas_call(
        "pallas_rowbin.scatter",
        functools.partial(_scatter_kernel, chunk=chunk, p=p, k=k, vp=vp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vp, p * k), bp.dtype),
        input_output_aliases={2: 0},
        interpret=_INTERPRET,
    )(rows, vals, bp)


def _sorted_merge(rows, vals, sentinel):
    """The ISSUE's sorted-segment formulation: sort ids, segment-reduce
    duplicates, leaving one dense store per unique row (duplicate slots
    parked on the dropped sentinel). The argsort costs more than the
    serial-accumulate default saves at the bench shapes."""
    from ..core.op_registry import merge_sparse_rows

    return merge_sparse_rows(rows, vals, sentinel)


def scatter_add_rows(base, rows, vals, sort=False):
    """``base.at[rows].add(vals, mode="drop")`` for a 2-D ``[V, K]``
    table — via the VMEM-resident Pallas kernel when :func:`use_pallas`
    admits the shape, else the XLA scatter. Exact: out-of-range rows
    drop, duplicate rows accumulate.

    rows: [N] integer; vals: [N, K] (or broadcastable leading shape that
    flattens to it). ``sort=True`` routes through the sorted-segment
    merge first.
    """
    v, k = base.shape
    rows = rows.reshape(-1).astype(jnp.int32)
    vals = vals.reshape(-1, k)
    if vals.dtype != base.dtype:
        vals = vals.astype(base.dtype)
    if not use_pallas(v, k, rows.shape[0], base.dtype):
        return base.at[rows].add(vals, mode="drop")
    from .rowops import pack_factor

    p = pack_factor(k)
    vp = -(-v // p)
    # exact ``.at[].add(mode="drop")`` index semantics: negative rows in
    # [-V, 0) wrap python-style, anything else out of range parks on the
    # sentinel (dropped by the kernel) — so the packed row/sub
    # decomposition below always sees in-range or sentinel rows
    rows = jnp.where((rows >= -v) & (rows < 0), rows + v, rows)
    rows = jnp.where((rows >= 0) & (rows < v), rows, vp * p)
    if sort:
        rows, vals = _sorted_merge(rows, vals, vp * p)
    pad = vp * p - v
    bp = jnp.pad(base, ((0, pad), (0, 0))) if pad else base
    bp = bp.reshape(vp, p * k)
    out = _scatter_packed_call(bp, rows, vals, p, k, vp)
    return out.reshape(vp * p, k)[:v]
