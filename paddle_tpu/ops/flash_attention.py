"""Flash attention: Pallas TPU kernels + pure-jax reference.

The reference framework has no fused attention (2019-era; attention is
composed from matmul/softmax layers, e.g. ``tests/unittests/dist_transformer.py``)
— this is where the TPU build beats it: VMEM-resident kernels with online
softmax, no [T, T] HBM materialization in forward OR backward.

Kernel set (see /opt/skills/guides/pallas_guide.md):
  * forward: grid (q blocks); K/V streamed in k blocks; running
    (max, sum, acc) online-softmax state; per-key additive bias (the
    padding-mask case), causal masking, and in-kernel dropout on the
    attention weights via the TPU PRNG (pltpu.prng_*), seeded per
    (batch*head, q block, k block) so the backward regenerates identical
    masks.
  * backward: ONE fused kernel (grid over k blocks) producing dK/dV/dB
    per block and accumulating dQ into a revisited full-T VMEM output —
    using the saved row logsumexp and D = rowsum(dO * O), the standard
    flash formulation; probabilities are recomputed per block, never
    stored, and never twice (a separate dQ kernel would redo st and dp
    for every block pair).

CPU/tests: ``mha_reference`` is the numerics oracle; the kernels also run
under ``interpret=True`` for hermetic CI (all paths except dropout, whose
PRNG primitives are TPU-only).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import gates
from .gates import GateDecision, GateReason
from .kernel_names import named_pallas_call, traced_once

_INTERPRET = False  # tests flip this to run kernels on CPU


# ---------------------------------------------------------------------------
# reference (and CPU-fallback) implementation
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, bias=None, causal=False, scale=None,
                  dropout_rate=0.0, rng=None):
    """q,k,v: [B, H, T, D]; bias broadcastable to [B, H, Tq, Tk].
    Dropout (like the kernels) applies to the attention WEIGHTS."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        t_q, t_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

def _dropout_keep(shape, rate, seed, tags):
    """In-kernel dropout keep-mask from the TPU PRNG. ``tags`` are python/
    traced ints mixed into the seed so every (bh, q block, k block) gets an
    independent, regenerable stream. Tags fold into ONE scalar (multi-
    operand prng_seed hits a Mosaic lowering bug)."""
    from jax.experimental.pallas import tpu as pltpu

    mixed = seed.astype(jnp.int32)
    for mult, tag in zip((1000003, 7919, 104729), tags):
        mixed = mixed + jnp.int32(mult) * jnp.asarray(tag, jnp.int32)
    pltpu.prng_seed(mixed)
    bits = pltpu.prng_random_bits(shape)
    # uniform in [0, 2^23): keep iff below keep_prob * 2^23
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32) & jnp.uint32(0x7FFFFF)
    thresh = jnp.uint32(int((1.0 - rate) * float(1 << 23)))
    return u < thresh


def _kv_mask_lo(num_kb, q_idx, block_q, block_k, kv_len, kv_pad, causal):
    """First k-block index needing a mask, for the forward's k-loop
    split: interior blocks run the lean body; only diagonal blocks
    (causal) and the padded kv tail are masked."""
    mask_lo = num_kb
    if causal:
        # clamp to num_kb: for t_q > t_k the diagonal can lie beyond the
        # last k block, and the lean prefix must never read past kv_pad
        mask_lo = jnp.minimum(num_kb, (q_idx * block_q) // block_k)
    if kv_len < kv_pad:
        mask_lo = jnp.minimum(mask_lo, kv_len // block_k)
    return mask_lo


def _kv_mask(kb, q_idx, block_q, block_k, kv_len, kv_pad, causal):
    """[block_k, block_q] keep-mask for a masked k-block iteration —
    the kv-tail bound and/or the causal triangle (None if neither
    applies)."""
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    mask = k_pos < kv_len if kv_len < kv_pad else None
    if causal:
        q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        keep = q_pos >= k_pos
        mask = keep if mask is None else mask & keep
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref, *,
                block_k, causal, scale, kv_len, dropout_rate):
    from jax.experimental import pallas as pl

    q = q_ref[...]
    block_q, d = q.shape
    kv_pad = k_ref.shape[0]
    bh_idx = pl.program_id(0)
    q_idx = pl.program_id(1)

    # TRANSPOSED scores [bk, bq] (cf. _dense_fwd_kernel): the online
    # max/sum run over SUBLANES (vreg adds, no cross-lane shuffles) and
    # the running per-query stats are [1, bq] LANE vectors that broadcast
    # for free; the accumulator is kept transposed [d, bq] so its
    # per-iteration rescale is also a lane-broadcast. One [d, bq]
    # transpose per PROGRAM at the end, instead of lane reductions per
    # k-block iteration.
    m_i = jnp.full((1, block_q), -jnp.inf, jnp.float32)
    l_i = jnp.zeros((1, block_q), jnp.float32)
    acc = jnp.zeros((d, block_q), jnp.float32)

    num_kb = kv_pad // block_k
    if causal:
        # blocks strictly above the diagonal are fully masked — skip them
        num_kb = jnp.minimum(
            num_kb, ((q_idx + 1) * q.shape[0] + block_k - 1) // block_k)

    # mask specialization: interior blocks need NO mask at all — only the
    # diagonal block (causal) and the padded kv tail do. The two [bk, bq]
    # iotas + compares + selects per iteration are pure VPU overhead, so
    # the loop is split into an unmasked prefix and a masked remainder.
    kv_partial = kv_len < kv_pad          # static
    mask_lo = _kv_mask_lo(num_kb, q_idx, block_q, block_k, kv_len,
                          kv_pad, causal)

    def make_body(masked):
        def body(kb, carry):
            m_i, l_i, acc = carry
            k = k_ref[pl.dslice(kb * block_k, block_k), :]
            v = v_ref[pl.dslice(kb * block_k, block_k), :]
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bk, bq]
            if bias_ref is not None:
                b = bias_ref[0, pl.dslice(kb * block_k, block_k)]
                st = st + b.astype(jnp.float32)[:, None]
            if masked:
                mask = _kv_mask(kb, q_idx, block_q, block_k, kv_len,
                                kv_pad, causal)
                st = jnp.where(mask, st, -jnp.inf)
            m_new = jnp.maximum(m_i, jnp.max(st, axis=0, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            # exp(-inf - m_safe) is exactly 0 (m_safe finite), so masked
            # slots vanish without a second select
            p = jnp.exp(st - m_safe)
            alpha = jnp.where(jnp.isfinite(m_i), jnp.exp(m_i - m_safe), 0.0)
            l_new = alpha * l_i + jnp.sum(p, axis=0, keepdims=True)
            p_use = p
            if dropout_rate > 0.0:
                keep = _dropout_keep((block_k, block_q), dropout_rate,
                                     seed_ref[0, 0], (bh_idx, q_idx, kb))
                p_use = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            acc_new = acc * alpha + jax.lax.dot_general(
                v, p_use.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [d, bq]
            return m_new, l_new, acc_new
        return body

    carry = (m_i, l_i, acc)
    if causal or kv_partial:
        carry = jax.lax.fori_loop(0, mask_lo, make_body(False), carry)
        carry = jax.lax.fori_loop(mask_lo, num_kb, make_body(True), carry)
    else:
        carry = jax.lax.fori_loop(0, num_kb, make_body(False), carry)
    m_i, l_i, acc = carry
    l_safe = jnp.maximum(l_i, 1e-30)
    o_ref[...] = (acc / l_safe).T.astype(o_ref.dtype)
    # row logsumexp for the backward's prob recomputation; the stats ref
    # holds the FULL row axis (Mosaic-friendly layout), sliced per program
    lse = jnp.where(jnp.isfinite(m_i), m_i + jnp.log(l_safe), -jnp.inf)
    lse_ref[0, pl.dslice(q_idx * block_q, block_q)] = \
        lse[0].astype(jnp.float32)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, db_ref, dq_ref, *,
                    block_q, causal, scale, kv_len, kv_pad, q_len,
                    dropout_rate):
    from jax.experimental import pallas as pl

    k = k_ref[...]
    v = v_ref[...]
    block_k, d = k.shape
    q_pad = q_ref.shape[0]
    bh_idx = pl.program_id(0)
    k_idx = pl.program_id(1)

    # TRANSPOSED scores [bk, bq] (cf. _fwd_kernel): per-query lse/delta
    # broadcast along lanes; the per-key bias-grad reduction rides the
    # MXU as a ones-column dot instead of a per-iteration lane reduce
    bias_blk = None
    if bias_ref is not None:
        bias_blk = bias_ref[0, pl.dslice(k_idx * block_k, block_k)]

    # dQ FUSION: dq accumulates here instead of in a separate kernel
    # that would recompute st and dp per (q, k) block pair (~35% of the
    # backward dots; measured bwd 5.86 -> 4.46 ms at T=2048). The dq
    # output block maps to the SAME full-T buffer for every k_idx
    # (Mosaic output revisiting keeps it VMEM-resident across the k grid
    # for a fixed bh); zero it on the first k step. VMEM note: the f32
    # full-T dq (+ bf16 q/do + stats) bounds the single-chip streaming
    # path at roughly T ~16k for d=64; longer contexts are the
    # sequence-parallel ring's job (parallel/ring_attention.py).
    @pl.when(k_idx == 0)
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    # Mask specialization: padded k rows only produce dk/dv/db rows the
    # caller's unpad discards, so no per-iteration kv-tail mask — but a
    # once-per-program [bk, 1] row-validity select keeps them FINITE
    # (exp(st - lse) can overflow to inf for garbage rows, and debug-nans
    # style finiteness checks see the pre-slice kernel outputs). The
    # causal mask applies only to diagonal q blocks (the segment head)
    # and the q-pad mask only to the final q block (the tail) — interior
    # q blocks run the lean body.
    kvalid = None
    if kv_len < kv_pad:                    # static
        kvalid = (k_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len

    def make_body(masked):
        def body(qb, carry):
            dk, dv, db = carry
            q = q_ref[pl.dslice(qb * block_q, block_q), :]
            do = do_ref[pl.dslice(qb * block_q, block_q), :]
            lse = lse_ref[0, pl.dslice(qb * block_q, block_q)]
            delta = delta_ref[0, pl.dslice(qb * block_q, block_q)]
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bk, bq]
            if bias_blk is not None:
                st = st + bias_blk.astype(jnp.float32)[:, None]
            lse_okf = jnp.isfinite(lse).astype(jnp.float32)
            lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
            p = jnp.exp(st - lse_safe[None, :]) * lse_okf[None, :]
            if kvalid is not None:
                p = jnp.where(kvalid, p, 0.0)   # sublane-broadcast select
            if masked:
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                mask = q_pos < q_len if q_len < q_pad else None
                if causal:
                    k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0)
                    keep = q_pos >= k_pos
                    mask = keep if mask is None else mask & keep
                if mask is not None:
                    p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, bq]
            p_drop = p
            if dropout_rate > 0.0:
                keep = _dropout_keep((block_k, block_q), dropout_rate,
                                     seed_ref[0, 0], (bh_idx, qb, k_idx))
                inv = 1.0 / (1.0 - dropout_rate)
                p_drop = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dp * inv, 0.0)
            ds = p * (dp - delta[None, :])  # [bk, bq]
            # bf16 operands on the transposed contractions: the MXU runs
            # f32 dots at a fraction of its bf16 rate
            dv = dv + jax.lax.dot_general(
                p_drop.astype(v.dtype), do.astype(v.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, d]
            dk = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if db is not None:
                db = db + jax.lax.dot_general(
                    ds, jnp.ones((1, block_q), jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [bk, 1]
            # dq[qb] += ds^T k (contract bk; masked/padded k rows have
            # ds == 0, so no kv mask is needed here)
            sl = pl.dslice(qb * block_q, block_q)
            dq_ref[sl, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return dk, dv, db
        return body

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    db0 = (jnp.zeros((block_k, 1), jnp.float32)
           if db_ref is not None else None)
    qb_end = q_pad // block_q
    qb_lo = (k_idx * block_k) // block_q if causal else 0
    carry = (dk0, dv0, db0)
    q_partial = q_len < q_pad             # static
    if causal or q_partial:
        # segment head: diagonal (causal) blocks, masked
        if causal:
            first_full = (k_idx * block_k + block_k - 1
                          + block_q - 1) // block_q
            a_hi = jnp.minimum(first_full, qb_end)
        else:
            a_hi = qb_lo
        # segment middle: lean; segment tail: q-padded block(s), masked
        pad_lo = (q_len // block_q) if q_partial else qb_end
        b_hi = jnp.maximum(a_hi, jnp.minimum(pad_lo, qb_end))
        carry = jax.lax.fori_loop(qb_lo, a_hi, make_body(True), carry)
        carry = jax.lax.fori_loop(a_hi, b_hi, make_body(False), carry)
        carry = jax.lax.fori_loop(b_hi, qb_end, make_body(True), carry)
    else:
        carry = jax.lax.fori_loop(qb_lo, qb_end, make_body(False), carry)
    dk, dv, db = carry
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    if db_ref is not None:
        db_ref[0, pl.dslice(k_idx * block_k, block_k)] = \
            db[:, 0].astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call drivers — [BH, T, D] layout, one program per (bh, block)
# ---------------------------------------------------------------------------

def _pad_t(x, m):
    r = (-x.shape[1]) % m
    return jnp.pad(x, ((0, 0), (0, r), (0, 0))) if r else x


def _pad_vec(x, m):
    r = (-x.shape[1]) % m
    return jnp.pad(x, ((0, 0), (0, r))) if r else x


def _block_sizes(t, t_k):
    """Block sizes of the streaming kernels, forward and backward alike
    (dropout masks regenerate per (bh, q-block, k-block) tile, so the two
    share block geometry). Mosaic wants the lane (last) dim of 1-D stats
    blocks divisible by 128, so real-TPU blocks are 128-multiples capped
    at 512 (bigger blocks amortize the per-iteration MXU/VPU
    serialization; on an earlier installation 128 was 2.5x worse at
    T=2048 and 1024 regressed the backward); interpret mode uses
    8-multiples capped at 64, which exercises the padded-edge and
    multi-block paths cheaply."""
    m, cap = (8, 64) if _INTERPRET else (128, 512)

    def r(x):
        return ((x + m - 1) // m) * m

    return min(cap, r(t)), min(cap, r(t_k))


@traced_once("head_split_stream.fwd",
             ("causal", "scale", "dropout_rate", "blocks", "interpret"))
def _flash_fwd_impl(q, k, v, bias, seed, causal, scale, dropout_rate,
                    blocks, interpret):
    """q,k,v: [BH, T, D]; bias [BH, Tk] additive per-key or None;
    ``blocks``: :func:`_block_sizes` of the forward.
    Returns (out [BH, T, D], lse [BH, T])."""
    from jax.experimental import pallas as pl

    bh, t, d = q.shape
    t_k = k.shape[1]
    block_q, block_k = blocks
    qp, kp, vp = _pad_t(q, block_q), _pad_t(k, block_k), _pad_t(v, block_k)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]

    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        kv_len=t_k, dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda b, qi: (b, qi, 0)),
        pl.BlockSpec((None, tk_pad, d), lambda b, qi: (b, 0, 0)),
        pl.BlockSpec((None, tk_pad, d), lambda b, qi: (b, 0, 0)),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, 8, tk_pad),
                                     lambda b, qi: (b, 0, 0)))
        bp = _pad_vec(bias, block_k)
        args.append(jnp.broadcast_to(bp[:, None, :], (bh, 8, tk_pad)))
    in_specs.append(pl.BlockSpec((1, 1), lambda b, qi: (0, 0)))
    args.append(jnp.asarray([[seed]], jnp.uint32))

    def kernel_entry(*refs):
        if bias is not None:
            q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref = refs
        else:
            q_ref, k_ref, v_ref, s_ref, o_ref, l_ref = refs
            b_ref = None
        kernel(q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref)

    out, lse = named_pallas_call(
        "head_split_stream.fwd",
        kernel_entry,
        grid=(bh, t_pad // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi: (b, qi, 0)),
            # stats ride an 8-row sublane-padded block (Mosaic disallows
            # 1-D effective blocks); row 0 is the data
            pl.BlockSpec((None, 8, t_pad), lambda b, qi: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, t_pad), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out[:, :t], lse[:, 0, :t]


@traced_once("head_split_stream.bwd",
             ("causal", "scale", "dropout_rate", "blocks", "interpret"))
def _flash_bwd_impl(q, k, v, bias, seed, out, lse, do, causal, scale,
                    dropout_rate, blocks, interpret):
    """``blocks``: :func:`_block_sizes`, the forward's."""
    from jax.experimental import pallas as pl

    bh, t, d = q.shape
    t_k = k.shape[1]
    block_q, block_k = blocks
    qp, kp, vp = _pad_t(q, block_q), _pad_t(k, block_k), _pad_t(v, block_k)
    dop = _pad_t(do, block_q)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [BH, T]

    def pad8(x):  # [BH, T] -> [BH, 8, T_pad] sublane-padded stats block
        xp = _pad_vec(x, block_q)
        return jnp.broadcast_to(xp[:, None, :], (bh, 8, xp.shape[1]))

    lsep = pad8(lse)
    deltap = pad8(delta)
    if bias is not None:
        bp = _pad_vec(bias, block_k)
        biasp = jnp.broadcast_to(bp[:, None, :], (bh, 8, bp.shape[1]))
    else:
        biasp = None
    seed_arr = jnp.asarray([[seed]], jnp.uint32)

    # one fused kernel: grid over k blocks produces dK/dV/(dB) per block
    # AND accumulates dQ into a revisited full-T output (no separate dQ
    # kernel recomputing st/dp — see _bwd_dkv_kernel)
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, causal=causal, scale=scale,
        kv_len=t_k, kv_pad=tk_pad, q_len=t, dropout_rate=dropout_rate)

    def dkv_entry(*refs):
        if biasp is not None:
            (q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, l_ref, de_ref,
             dk_ref, dv_ref, db_ref, dq_ref) = refs
        else:
            (q_ref, k_ref, v_ref, s_ref, do_ref, l_ref, de_ref,
             dk_ref, dv_ref, dq_ref) = refs
            b_ref = db_ref = None
        dkv_kernel(q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, l_ref,
                   de_ref, dk_ref, dv_ref, db_ref, dq_ref)

    in_specs2 = [
        pl.BlockSpec((None, t_pad, d), lambda b, ki: (b, 0, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, ki: (b, ki, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, ki: (b, ki, 0)),
    ]
    args2 = [qp, kp, vp]
    if biasp is not None:
        in_specs2.append(pl.BlockSpec((None, 8, tk_pad),
                                      lambda b, ki: (b, 0, 0)))
        args2.append(biasp)
    in_specs2.append(pl.BlockSpec((1, 1), lambda b, ki: (0, 0)))
    args2.append(seed_arr)
    in_specs2 += [
        pl.BlockSpec((None, t_pad, d), lambda b, ki: (b, 0, 0)),
        pl.BlockSpec((None, 8, t_pad), lambda b, ki: (b, 0, 0)),
        pl.BlockSpec((None, 8, t_pad), lambda b, ki: (b, 0, 0)),
    ]
    args2 += [dop, lsep, deltap]
    out_specs2 = [
        pl.BlockSpec((None, block_k, d), lambda b, ki: (b, ki, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, ki: (b, ki, 0)),
    ]
    out_shape2 = [
        jax.ShapeDtypeStruct((bh, tk_pad, d), k.dtype),
        jax.ShapeDtypeStruct((bh, tk_pad, d), v.dtype),
    ]
    if biasp is not None:
        out_specs2.append(pl.BlockSpec((None, 8, tk_pad),
                                       lambda b, ki: (b, 0, 0)))
        out_shape2.append(jax.ShapeDtypeStruct((bh, 8, tk_pad),
                                               jnp.float32))
    # dq: full-T f32 accumulator, SAME block for every k step (Mosaic
    # revisiting — written back once per bh)
    out_specs2.append(pl.BlockSpec((None, t_pad, d),
                                   lambda b, ki: (b, 0, 0)))
    out_shape2.append(jax.ShapeDtypeStruct((bh, t_pad, d), jnp.float32))
    res = named_pallas_call(
        "head_split_stream.bwd",
        dkv_entry,
        grid=(bh, tk_pad // block_k),
        in_specs=in_specs2,
        out_specs=out_specs2,
        out_shape=out_shape2,
        interpret=interpret,
    )(*args2)
    if biasp is not None:
        dk, dv, db, dq = res
        db = db[:, 0, :t_k]
    else:
        dk, dv, dq = res
        db = None
    return dq[:, :t].astype(q.dtype), dk[:, :t_k], dv[:, :t_k], db


# ---------------------------------------------------------------------------
# packed STREAMING kernels — [B, T, H*D] layout, heads looped in-kernel
# ---------------------------------------------------------------------------
#
# The head-split streaming path reshapes [B,T,H*D] -> [B*H,T,D] around the
# custom calls, and XLA materializes those relayouts as real HBM copies
# (7 per attention site; 22.9 ms of a 218.7 ms step at 16 x 2048, 8 heads of
# 64). These kernels keep the packed layout the projection matmuls produce
# END TO END. The grid is (batch, lane window, block): a program holds ONE
# window of the packed head dimension, ``_lane_window`` lanes wide (the
# least run of whole heads that fills whole 128-lane tiles: two heads of 64,
# one head of 128 or 256; the whole H*D where no such run divides it), taken
# by the BlockSpecs straight out of the packed arrays. It loops the window's
# heads over static lane slices (like the dense kernels), and the
# online-softmax k-loop streams K/V blocks exactly as the head-split kernels
# do. VMEM holds one window's full-T K/V (fwd) and q/do/dq-f32 (bwd), as
# wide as the window and not as H*D, so the path reaches as far as one
# head's streaming does (gate: _packed_stream_fits; the lengths are in
# kernel_plan's docstring).

def _lane_window(hd, num_heads):
    """Lanes of the packed head dimension one program of the packed
    streaming kernels holds: lcm(D, 128) where that divides H*D, else all
    of H*D (then the one window is the whole array, a legal block at any
    width)."""
    w = math.lcm(hd // num_heads, 128)
    return w if hd % w == 0 else hd


def _packed_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                       lse_ref, *, num_heads, total_heads, block_k, causal,
                       scale, kv_len, dropout_rate):
    """``num_heads``: the heads of this program's lane window;
    ``total_heads``: all H, for the dropout tile tag."""
    from jax.experimental import pallas as pl

    block_q, w = q_ref.shape
    d = w // num_heads
    kv_pad = k_ref.shape[0]
    # the window's first head among all B*H: dropout tiles are tagged by
    # the global head, the tags the head-split kernels draw from
    head0 = pl.program_id(0) * total_heads + pl.program_id(1) * num_heads
    q_idx = pl.program_id(2)

    num_kb = kv_pad // block_k
    if causal:
        num_kb = jnp.minimum(
            num_kb, ((q_idx + 1) * block_q + block_k - 1) // block_k)
    kv_partial = kv_len < kv_pad
    mask_lo = _kv_mask_lo(num_kb, q_idx, block_q, block_k, kv_len,
                          kv_pad, causal)

    for h in range(num_heads):
        sl = pl.dslice(h * d, d)
        q = q_ref[:, sl]
        # same transposed-scores online softmax as _fwd_kernel, with K/V
        # loads lane-sliced to this head's columns (no HBM relayout)
        m_i = jnp.full((1, block_q), -jnp.inf, jnp.float32)
        l_i = jnp.zeros((1, block_q), jnp.float32)
        acc = jnp.zeros((d, block_q), jnp.float32)

        def make_body(masked):
            def body(kb, carry):
                m_i, l_i, acc = carry
                ksl = pl.dslice(kb * block_k, block_k)
                k = k_ref[ksl, sl]
                v = v_ref[ksl, sl]
                st = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if bias_ref is not None:
                    bb = bias_ref[0, ksl]
                    st = st + bb.astype(jnp.float32)[:, None]
                if masked:
                    mask = _kv_mask(kb, q_idx, block_q, block_k, kv_len,
                                    kv_pad, causal)
                    st = jnp.where(mask, st, -jnp.inf)
                m_new = jnp.maximum(m_i, jnp.max(st, axis=0, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(st - m_safe)
                alpha = jnp.where(jnp.isfinite(m_i),
                                  jnp.exp(m_i - m_safe), 0.0)
                l_new = alpha * l_i + jnp.sum(p, axis=0, keepdims=True)
                p_use = p
                if dropout_rate > 0.0:
                    keep = _dropout_keep(
                        (block_k, block_q), dropout_rate, seed_ref[0, 0],
                        (head0 + h, q_idx, kb))
                    p_use = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
                acc_new = acc * alpha + jax.lax.dot_general(
                    v, p_use.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new
            return body

        carry = (m_i, l_i, acc)
        if causal or kv_partial:
            carry = jax.lax.fori_loop(0, mask_lo, make_body(False), carry)
            carry = jax.lax.fori_loop(mask_lo, num_kb, make_body(True),
                                      carry)
        else:
            carry = jax.lax.fori_loop(0, num_kb, make_body(False), carry)
        m_i, l_i, acc = carry
        l_safe = jnp.maximum(l_i, 1e-30)
        o_ref[:, sl] = (acc / l_safe).T.astype(o_ref.dtype)
        lse = jnp.where(jnp.isfinite(m_i), m_i + jnp.log(l_safe), -jnp.inf)
        lse_ref[h, pl.dslice(q_idx * block_q, block_q)] = \
            lse[0].astype(jnp.float32)


def _packed_bwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, db_ref, dq_ref,
                       dq_acc_ref, *, num_heads, total_heads, block_q,
                       causal, scale, kv_len, kv_pad, q_len, dropout_rate):
    """``num_heads`` / ``total_heads``: as in :func:`_packed_fwd_kernel`.
    ``dq_acc_ref``: the float32 scratch dq adds up in, or None where
    ``dq_ref`` is float32 itself."""
    from jax.experimental import pallas as pl

    block_k, w = k_ref.shape
    d = w // num_heads
    q_pad = q_ref.shape[0]
    head0 = pl.program_id(0) * total_heads + pl.program_id(1) * num_heads
    k_idx = pl.program_id(2)

    bias_blk = None
    if bias_ref is not None:
        bias_blk = bias_ref[0, pl.dslice(k_idx * block_k, block_k)]

    # dq adds up in float32 over the k steps, the innermost grid axis, in
    # ONE full-T buffer of this lane window (cf. _bwd_dkv_kernel): zeroed
    # on the first step, and on the last rounded once into the revisited
    # output block, which Mosaic writes back once per (batch, window) — in
    # q's dtype, so no float32 dq goes through HBM to be converted there
    dq_acc = dq_ref if dq_acc_ref is None else dq_acc_ref

    @pl.when(k_idx == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    kvalid = None
    if kv_len < kv_pad:
        kvalid = (k_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len

    qb_end = q_pad // block_q
    qb_lo = (k_idx * block_k) // block_q if causal else 0
    q_partial = q_len < q_pad
    db_total = (jnp.zeros((block_k, 1), jnp.float32)
                if db_ref is not None else None)

    for h in range(num_heads):
        sl = pl.dslice(h * d, d)
        k = k_ref[:, sl]
        v = v_ref[:, sl]

        def make_body(masked):
            def body(qb, carry):
                dk, dv, db = carry
                qsl = pl.dslice(qb * block_q, block_q)
                q = q_ref[qsl, sl]
                do = do_ref[qsl, sl]
                lse = lse_ref[h, qsl]
                delta = delta_ref[h, qsl]
                st = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if bias_blk is not None:
                    st = st + bias_blk.astype(jnp.float32)[:, None]
                lse_okf = jnp.isfinite(lse).astype(jnp.float32)
                lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
                p = jnp.exp(st - lse_safe[None, :]) * lse_okf[None, :]
                if kvalid is not None:
                    p = jnp.where(kvalid, p, 0.0)
                if masked:
                    q_pos = qb * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 1)
                    mask = q_pos < q_len if q_len < q_pad else None
                    if causal:
                        k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                            jnp.int32, (block_k, block_q), 0)
                        keep = q_pos >= k_pos
                        mask = keep if mask is None else mask & keep
                    if mask is not None:
                        p = jnp.where(mask, p, 0.0)
                dp = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                p_drop = p
                if dropout_rate > 0.0:
                    keep = _dropout_keep(
                        (block_k, block_q), dropout_rate, seed_ref[0, 0],
                        (head0 + h, qb, k_idx))
                    inv = 1.0 / (1.0 - dropout_rate)
                    p_drop = jnp.where(keep, p * inv, 0.0)
                    dp = jnp.where(keep, dp * inv, 0.0)
                ds = p * (dp - delta[None, :])
                dv = dv + jax.lax.dot_general(
                    p_drop.astype(v.dtype), do.astype(v.dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk = dk + jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if db is not None:
                    db = db + jax.lax.dot_general(
                        ds, jnp.ones((1, block_q), jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                dq_acc[qsl, sl] += jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                return dk, dv, db
            return body

        carry = (jnp.zeros((block_k, d), jnp.float32),
                 jnp.zeros((block_k, d), jnp.float32),
                 jnp.zeros((block_k, 1), jnp.float32)
                 if db_ref is not None else None)
        if causal or q_partial:
            if causal:
                first_full = (k_idx * block_k + block_k - 1
                              + block_q - 1) // block_q
                a_hi = jnp.minimum(first_full, qb_end)
            else:
                a_hi = qb_lo
            pad_lo = (q_len // block_q) if q_partial else qb_end
            b_hi = jnp.maximum(a_hi, jnp.minimum(pad_lo, qb_end))
            carry = jax.lax.fori_loop(qb_lo, a_hi, make_body(True), carry)
            carry = jax.lax.fori_loop(a_hi, b_hi, make_body(False), carry)
            carry = jax.lax.fori_loop(b_hi, qb_end, make_body(True), carry)
        else:
            carry = jax.lax.fori_loop(qb_lo, qb_end, make_body(False),
                                      carry)
        dk, dv, db = carry
        dk_ref[:, sl] = dk.astype(dk_ref.dtype)
        dv_ref[:, sl] = dv.astype(dv_ref.dtype)
        if db_total is not None:
            db_total = db_total + db  # bias is shared across heads
    # this window's heads' part of the bias gradient; the caller adds the
    # windows up
    if db_ref is not None:
        db_ref[0, pl.dslice(k_idx * block_k, block_k)] = \
            db_total[:, 0].astype(db_ref.dtype)
    if dq_acc_ref is not None:
        @pl.when(k_idx == pl.num_programs(2) - 1)
        def _emit_dq():
            dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


@traced_once("packed_stream.fwd",
             ("num_heads", "causal", "scale", "dropout_rate", "blocks",
              "interpret"))
def _packed_stream_fwd_impl(q, k, v, bias, seed, num_heads, causal, scale,
                            dropout_rate, blocks, interpret):
    """q,k,v: packed [B, T, H*D]; bias [B, Tk] or None; ``blocks``:
    :func:`_block_sizes` of the forward.
    Returns (out [B, T, H*D], lse [B, G, rows, T_pad]): the row logsumexp
    as the kernels hold it, one sublane-padded block a lane window, heads
    ``g * (H // G) + row`` in its first rows (what the backward takes)."""
    from jax.experimental import pallas as pl

    b, t, hd = q.shape
    t_k = k.shape[1]
    block_q, block_k = blocks
    qp, kp, vp = _pad_t(q, block_q), _pad_t(k, block_k), _pad_t(v, block_k)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]
    w = _lane_window(hd, num_heads)
    windows = hd // w
    heads = num_heads // windows
    rows = max(heads, 8)

    kernel = functools.partial(
        _packed_fwd_kernel, num_heads=heads, total_heads=num_heads,
        block_k=block_k, causal=causal, scale=scale, kv_len=t_k,
        dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((None, block_q, w), lambda b, g, qi: (b, qi, g)),
        pl.BlockSpec((None, tk_pad, w), lambda b, g, qi: (b, 0, g)),
        pl.BlockSpec((None, tk_pad, w), lambda b, g, qi: (b, 0, g)),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, 8, tk_pad),
                                     lambda b, g, qi: (b, 0, 0)))
        bp = _pad_vec(bias, block_k)
        args.append(jnp.broadcast_to(bp[:, None, :], (b, 8, tk_pad)))
    in_specs.append(pl.BlockSpec((1, 1), lambda b, g, qi: (0, 0)))
    args.append(jnp.asarray([[seed]], jnp.uint32))

    def entry(*refs):
        if bias is not None:
            q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref = refs
        else:
            q_ref, k_ref, v_ref, s_ref, o_ref, l_ref = refs
            b_ref = None
        kernel(q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref)

    out, lse = named_pallas_call(
        "packed_stream.fwd",
        entry,
        grid=(b, windows, t_pad // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, w), lambda b, g, qi: (b, qi, g)),
            pl.BlockSpec((None, None, rows, t_pad),
                         lambda b, g, qi: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((b, windows, rows, t_pad), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out[:, :t], lse


@traced_once("packed_stream.bwd",
             ("num_heads", "causal", "scale", "dropout_rate", "blocks",
              "interpret"))
def _packed_stream_bwd_impl(q, k, v, bias, seed, out, lse, do, num_heads,
                            causal, scale, dropout_rate, blocks, interpret):
    """``blocks``: :func:`_block_sizes`, the forward's; ``lse``: as
    :func:`_packed_stream_fwd_impl` returns it."""
    from jax.experimental import pallas as pl

    b, t, hd = q.shape
    t_k = k.shape[1]
    block_q, block_k = blocks
    qp, kp, vp = _pad_t(q, block_q), _pad_t(k, block_k), _pad_t(v, block_k)
    dop = _pad_t(do, block_q)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]
    _, windows, rows, _ = lse.shape
    w = hd // windows
    heads = num_heads // windows
    # per-(b, h, t) delta = rowsum_d(do * o) over this head's lanes, laid
    # out like lse; the [B,T,H] reduce + transpose is tiny next to a
    # [B,T,H,D] relayout
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            b, t, windows, heads, hd // num_heads), axis=-1)
    deltap = jnp.pad(delta.transpose(0, 2, 3, 1),
                     ((0, 0), (0, 0), (0, rows - heads), (0, t_pad - t)))
    if bias is not None:
        bp = _pad_vec(bias, block_k)
        biasp = jnp.broadcast_to(bp[:, None, :], (b, 8, bp.shape[1]))
    else:
        biasp = None

    kernel = functools.partial(
        _packed_bwd_kernel, num_heads=heads, total_heads=num_heads,
        block_q=block_q, causal=causal, scale=scale, kv_len=t_k,
        kv_pad=tk_pad, q_len=t, dropout_rate=dropout_rate)

    # dq's float32 sum lives in a scratch unless the output is float32
    narrow = q.dtype != jnp.float32

    def entry(*refs):
        refs = list(refs)
        acc_ref = refs.pop() if narrow else None
        if biasp is not None:
            (q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, l_ref, de_ref,
             dk_ref, dv_ref, db_ref, dq_ref) = refs
        else:
            (q_ref, k_ref, v_ref, s_ref, do_ref, l_ref, de_ref,
             dk_ref, dv_ref, dq_ref) = refs
            b_ref = db_ref = None
        kernel(q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, l_ref, de_ref,
               dk_ref, dv_ref, db_ref, dq_ref, acc_ref)

    def whole(b, g, ki):     # a window's full-T block: q, do, dq
        return b, 0, g

    def block(b, g, ki):     # a window's k block: k, v, dk, dv
        return b, ki, g

    def stats(b, g, ki):     # a window's per-head rows: lse, delta, db
        return b, g, 0, 0

    in_specs = [
        pl.BlockSpec((None, t_pad, w), whole),
        pl.BlockSpec((None, block_k, w), block),
        pl.BlockSpec((None, block_k, w), block),
    ]
    args = [qp, kp, vp]
    if biasp is not None:
        in_specs.append(pl.BlockSpec((None, 8, tk_pad),
                                     lambda b, g, ki: (b, 0, 0)))
        args.append(biasp)
    in_specs.append(pl.BlockSpec((1, 1), lambda b, g, ki: (0, 0)))
    args.append(jnp.asarray([[seed]], jnp.uint32))
    in_specs += [
        pl.BlockSpec((None, t_pad, w), whole),
        pl.BlockSpec((None, None, rows, t_pad), stats),
        pl.BlockSpec((None, None, rows, t_pad), stats),
    ]
    args += [dop, lse, deltap]
    out_specs = [
        pl.BlockSpec((None, block_k, w), block),
        pl.BlockSpec((None, block_k, w), block),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, tk_pad, hd), k.dtype),
        jax.ShapeDtypeStruct((b, tk_pad, hd), v.dtype),
    ]
    if biasp is not None:
        # every head adds to the per-key bias gradient: one partial a
        # window (row 0 of its block), summed below
        out_specs.append(pl.BlockSpec((None, None, 8, tk_pad), stats))
        out_shape.append(jax.ShapeDtypeStruct((b, windows, 8, tk_pad),
                                              jnp.float32))
    # dq: a window's full-T block, the SAME for every k step (Mosaic
    # revisiting: written back once per (b, window))
    out_specs.append(pl.BlockSpec((None, t_pad, w), whole))
    out_shape.append(jax.ShapeDtypeStruct((b, t_pad, hd), q.dtype))
    scratch = []
    if narrow:
        from jax.experimental.pallas import tpu as pltpu

        scratch.append(pltpu.VMEM((t_pad, w), jnp.float32))
    res = named_pallas_call(
        "packed_stream.bwd",
        entry,
        grid=(b, windows, tk_pad // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    if biasp is not None:
        dk, dv, db, dq = res
        db = jnp.sum(db[:, :, 0, :t_k], axis=1)
    else:
        dk, dv, dq = res
        db = None
    return dq[:, :t], dk[:, :t_k], dv[:, :t_k], db


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _packed_stream_attention(q, k, v, bias, seed, num_heads, causal, scale,
                             dropout_rate):
    return _packed_stream_fwd(q, k, v, bias, seed, num_heads, causal, scale,
                              dropout_rate)[0]


def _packed_stream_fwd(q, k, v, bias, seed, num_heads, causal, scale,
                       dropout_rate):
    out, lse = _packed_stream_fwd_impl(
        q, k, v, bias, seed, num_heads, causal, scale, dropout_rate,
        _block_sizes(q.shape[1], k.shape[1]), _INTERPRET)
    return out, (q, k, v, bias, seed, out, lse)


def _packed_stream_bwd(num_heads, causal, scale, dropout_rate, res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv, db = _packed_stream_bwd_impl(
        q, k, v, bias, seed, out, lse, g, num_heads, causal, scale,
        dropout_rate, _block_sizes(q.shape[1], k.shape[1]), _INTERPRET)
    dbias = db.astype(bias.dtype) if bias is not None else None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, None)


_packed_stream_attention.defvjp(_packed_stream_fwd, _packed_stream_bwd)

# The chip's compiler gives one kernel 16 MiB of scoped VMEM and refuses
# the whole program when a kernel asks for more. What a kernel asks for
# also depends on the program around it: the bf16 T=2048 backward of one
# 128-lane window compiles alone under 9.375M and inside the seq-2048 train
# step under 10.25M, its forward under 4.25M and 5.0M (the least
# ``vmem_limit_bytes`` that compiles; the size a refusal prints is the
# running sum at the allocation that failed, not the kernel's whole need).
# So 3 MiB stay free for what the surrounding step adds and for what the
# counts below miss.
_STREAM_VMEM_BUDGET = 13 * 1024 * 1024


def _packed_stream_vmem(t, t_k, hd, esize, num_heads):
    """(forward, backward) bytes of VMEM the packed streaming kernels
    allocate for one lane window. Mosaic double-buffers every operand
    whose block index changes anywhere in the grid — the full-T q/do
    (bwd) and K/V (fwd) blocks change with the batch and window indices,
    so they count twice like the streamed blocks do, and so does the
    revisited dq block. The body's own values are counted as the chip's
    compiler was found to count them (the least ``vmem_limit_bytes`` a
    kernel compiles under, bf16, blocks of 512, in MiB, with this count
    in brackets): backward D=64 T=1024 7.25 (7.5), T=2048 9.375 (9.75),
    T=3072 11.375 (12.0), T=4096 13.0 (14.25); D=128 T=3072 10.75 (12.0);
    D=256 T=1024 12.375 (12.75), T=2048 over 16 (17.0); forward D=64
    T=2048 4.125 (4.75), T=4096 6.5 (7.0), D=256 T=1024 6.0 (6.125). The
    full width (8 heads of 40, T=1024) reads 6.375 against 12.0 here: too
    high, on the safe side (T=4096 read with the family forced past the
    gate). tests/test_tpu_compile.py compiles at these counts."""
    block_q, block_k = _block_sizes(t, t_k)
    w = _lane_window(hd, num_heads)
    d = hd // num_heads
    rows = max(w // d, 8)

    def pad(x, m):
        return ((x + m - 1) // m) * m

    t_pad, tk_pad = pad(t, block_q), pad(t_k, block_k)
    tile = block_q * block_k * 4                # [block_k, block_q] f32
    head = max(block_q, block_k) * max(d, 128) * 4   # [block, D] f32
    fwd = (4 * tk_pad * w * esize               # K/V, two buffers each
           + 4 * block_q * w * esize            # q/o blocks, two each
           + 2 * rows * t_pad * 4               # lse out
           + 2 * 8 * tk_pad * 4                 # key bias
           + tile + 4 * head)                   # scores; q, k, v, acc
    bwd = (4 * t_pad * w * esize                # q/do, two buffers each
           + (t_pad * w * 4 if esize < 4 else 0)   # dq's f32 sum, and
           + 2 * t_pad * w * esize              # its block, two buffers
           + 8 * block_k * w * esize            # k/v/dk/dv blocks, two each
           + 4 * rows * t_pad * 4               # lse/delta
           + 4 * 8 * tk_pad * 4                 # key bias + its grad
           + 2 * tile + 9 * head)               # p, ds; q, do, k, v, dk,
    #                                             dv and the dq update
    return fwd, bwd


def _packed_stream_fits(t, t_k, hd, esize, num_heads):
    return max(_packed_stream_vmem(t, t_k, hd, esize, num_heads)) \
        <= _STREAM_VMEM_BUDGET


# ---------------------------------------------------------------------------
# dense short-sequence kernels — packed [B, T, H*D] layout, whole-sequence
# blocks resident in VMEM
# ---------------------------------------------------------------------------
#
# For t_k up to ~1k the per-head problem fits VMEM outright, so the online-
# softmax streaming machinery above only adds grid/loop overhead (profiled at
# ~5% MXU on transformer-base T=256), and the [B,T,H*D]->[B*H,T,D] head split
# forces XLA transpose copies around the custom call (~7 per attention site).
# These kernels instead take the packed layout the projection matmuls
# naturally produce, loop the heads inside one grid step (static lane slices,
# no HBM relayout), and compute softmax in one shot per head. One grid step
# per batch element amortizes grid overhead ~H*n_block times better.

def _dense_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                      lse_ref, *, num_heads, causal, scale, q_len, kv_len,
                      dropout_rate):
    g_blk, t_pad, hd = q_ref.shape
    tk_pad = k_ref.shape[1]
    d = hd // num_heads
    from jax.experimental import pallas as pl

    b_idx = pl.program_id(0)
    # TRANSPOSED scores [tk, t]: the softmax axis becomes the SUBLANE axis,
    # so max/sum are vreg adds instead of cross-lane shuffle reductions
    # (measured: reductions were ~0.28 ms of a 0.52 ms call in [t, tk]
    # layout). One additive mask tile per grid step, hoisted out of the
    # (g, h) loops: exp(-1e30 - m) underflows to exactly 0, so no per-head
    # compare+select passes. do/q are zero-padded, so padded q rows produce
    # ds == 0 in the backward and only garbage in discarded output rows.
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (tk_pad, t_pad), 0)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (tk_pad, t_pad), 1)
    mask = k_pos < kv_len
    if causal:
        # end-anchored diagonal (matches mha_reference for t_q != t_k)
        mask = mask & (k_pos <= q_pos + (kv_len - q_len))
    mask = jnp.where(mask, 0.0, -1e30)

    # several batch elements per grid step: at T<=512 one element is only
    # a few us of compute, so the per-step fixed cost (DMA issue, loop
    # bookkeeping) dominates a G=1 grid
    for g in range(g_blk):
        mb = mask
        if bias_ref is not None:
            mb = mb + bias_ref[g, 0, :].astype(jnp.float32)[:, None]
        for h in range(num_heads):
            sl = pl.dslice(h * d, d)
            qh = q_ref[g, :, sl]
            kh = k_ref[g, :, sl]
            vh = v_ref[g, :, sl]
            st = jax.lax.dot_general(
                kh, qh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + mb  # [tk, t]
            m = jnp.max(st, axis=0)
            m_safe = jnp.maximum(m, -1e30)  # fully-masked rows: exp -> 0
            p = jnp.exp(st - m_safe[None, :])
            l = jnp.maximum(jnp.sum(p, axis=0), 1e-30)
            p_use = p * (1.0 / l)[None, :]  # lane-broadcast normalize
            if dropout_rate > 0.0:
                keep = _dropout_keep(
                    (tk_pad, t_pad), dropout_rate, seed_ref[0, 0],
                    ((b_idx * g_blk + g) * num_heads + h, 0, 0))
                p_use = jnp.where(keep, p_use / (1.0 - dropout_rate), 0.0)
            o_h = jax.lax.dot_general(
                p_use.astype(vh.dtype), vh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[g, :, sl] = o_h.astype(o_ref.dtype)
            lse_ref[g, h, :] = (m_safe + jnp.log(l)).astype(jnp.float32)


def _dense_bwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref,
                      out_ref, lse_ref, dq_ref, dk_ref, dv_ref, db_ref, *,
                      num_heads, causal, scale, q_len, kv_len, dropout_rate):
    g_blk, t_pad, hd = q_ref.shape
    tk_pad = k_ref.shape[1]
    d = hd // num_heads
    from jax.experimental import pallas as pl

    b_idx = pl.program_id(0)
    # TRANSPOSED scores [tk, t] (matches _dense_fwd_kernel, so dropout
    # masks regenerate in the same layout and lse/delta broadcast along
    # LANES); additive mask+bias tile hoisted; lse is always finite here
    # by the fwd's m_safe clamp
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (tk_pad, t_pad), 0)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (tk_pad, t_pad), 1)
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos + (kv_len - q_len))
    mask = jnp.where(mask, 0.0, -1e30)

    for g in range(g_blk):
        mb = mask
        if bias_ref is not None:
            mb = mb + bias_ref[g, 0, :].astype(jnp.float32)[:, None]
        db_acc = (jnp.zeros((1, tk_pad), jnp.float32)
                  if db_ref is not None else None)
        for h in range(num_heads):
            sl = pl.dslice(h * d, d)
            qh = q_ref[g, :, sl]
            kh = k_ref[g, :, sl]
            vh = v_ref[g, :, sl]
            do = do_ref[g, :, sl]
            o = out_ref[g, :, sl]
            lse = lse_ref[g, h, :]
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=1)  # [t]
            st = jax.lax.dot_general(
                kh, qh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + mb  # [tk, t]
            p = jnp.exp(st - lse[None, :])
            dp = jax.lax.dot_general(
                vh, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [tk, t]
            p_drop = p
            if dropout_rate > 0.0:
                keep = _dropout_keep(
                    (tk_pad, t_pad), dropout_rate, seed_ref[0, 0],
                    ((b_idx * g_blk + g) * num_heads + h, 0, 0))
                inv = 1.0 / (1.0 - dropout_rate)
                p_drop = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dp * inv, 0.0)
            ds_f32 = p * (dp - delta[None, :])  # [tk, t]
            ds = ds_f32.astype(qh.dtype)
            dq_ref[g, :, sl] = (jax.lax.dot_general(
                ds, kh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                * scale).astype(dq_ref.dtype)
            # bf16 operands on the transposed contractions too: the MXU
            # runs f32 dots at a fraction of its bf16 rate, and the
            # f32->bf16 cast is the same rounding the fwd products see
            dk_ref[g, :, sl] = (jax.lax.dot_general(
                ds, qh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                * scale).astype(dk_ref.dtype)
            dv_ref[g, :, sl] = jax.lax.dot_general(
                p_drop.astype(vh.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dv_ref.dtype)
            if db_acc is not None:
                # sum over queries is a LANE reduction in this layout;
                # run it as ones[1,t] x ds^T on the MXU instead
                db_acc = db_acc + jax.lax.dot_general(
                    jnp.ones((1, t_pad), jnp.float32), ds_f32,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [1, tk]
        if db_ref is not None:
            db_ref[g, 0, :] = db_acc[0]


def _pad_last(x, m):
    r = (-x.shape[1]) % m
    return jnp.pad(x, ((0, 0), (0, r), (0, 0))) if r else x


def _pick_g(b, per_elem_bytes, budget=4 * 1024 * 1024):
    """Batch elements per grid step: enough to amortize the ~5.5us fixed
    per-step cost, bounded by the VMEM block budget (blocks are double-
    buffered across grid steps, so they cost twice their size)."""
    for g in (8, 4, 2, 1):
        if b % g == 0 and g * per_elem_bytes <= budget:
            return g
    return 1


@traced_once("dense_vmem.fwd",
             ("num_heads", "causal", "scale", "dropout_rate", "interpret"))
def _dense_fwd_impl(q, k, v, bias, seed, num_heads, causal, scale,
                    dropout_rate, interpret):
    """q,k,v: packed [B, T, H*D]; bias [B, Tk] or None.
    Returns (out [B, T, H*D], lse [B, H, T_pad])."""
    from jax.experimental import pallas as pl

    b, t, hd = q.shape
    t_k = k.shape[1]
    m = 8 if interpret else 128
    qp = _pad_last(q, m)
    kp, vp = _pad_last(k, m), _pad_last(v, m)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]
    g = _pick_g(b, 2 * (t_pad + tk_pad) * hd * q.dtype.itemsize)

    kernel = functools.partial(
        _dense_fwd_kernel, num_heads=num_heads, causal=causal, scale=scale,
        q_len=t, kv_len=t_k, dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        bp = _pad_vec(bias, m)
        in_specs.append(pl.BlockSpec((g, 8, tk_pad), lambda bi: (bi, 0, 0)))
        args.append(jnp.broadcast_to(bp[:, None, :], (b, 8, tk_pad)))

    def entry(*refs):
        if bias is not None:
            q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref = refs
        else:
            q_ref, k_ref, v_ref, s_ref, o_ref, l_ref = refs
            b_ref = None
        kernel(q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, l_ref)

    in_specs.append(pl.BlockSpec((1, 1), lambda bi: (0, 0)))
    args.append(jnp.asarray([[seed]], jnp.uint32))
    nh_pad = max(num_heads, 8)  # sublane-tiled stats block
    out, lse = named_pallas_call(
        "dense_vmem.fwd",
        entry,
        grid=(b // g,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((g, nh_pad, t_pad), lambda bi: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((b, nh_pad, t_pad), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out[:, :t], lse


@traced_once("dense_vmem.bwd",
             ("num_heads", "causal", "scale", "dropout_rate", "interpret"))
def _dense_bwd_impl(q, k, v, bias, seed, out, lse, do, num_heads, causal,
                    scale, dropout_rate, interpret):
    from jax.experimental import pallas as pl

    b, t, hd = q.shape
    t_k = k.shape[1]
    m = 8 if interpret else 128
    qp, kp, vp = _pad_last(q, m), _pad_last(k, m), _pad_last(v, m)
    dop, outp = _pad_last(do, m), _pad_last(out, m)
    t_pad, tk_pad = qp.shape[1], kp.shape[1]
    nh_pad = lse.shape[1]
    g = _pick_g(b, 4 * (t_pad + tk_pad) * hd * q.dtype.itemsize)

    kernel = functools.partial(
        _dense_bwd_kernel, num_heads=num_heads, causal=causal, scale=scale,
        q_len=t, kv_len=t_k, dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        bp = _pad_vec(bias, m)
        in_specs.append(pl.BlockSpec((g, 8, tk_pad), lambda bi: (bi, 0, 0)))
        args.append(jnp.broadcast_to(bp[:, None, :], (b, 8, tk_pad)))
    in_specs.append(pl.BlockSpec((1, 1), lambda bi: (0, 0)))
    args.append(jnp.asarray([[seed]], jnp.uint32))
    in_specs += [
        pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, nh_pad, t_pad), lambda bi: (bi, 0, 0)),
    ]
    args += [dop, outp, lse]

    def entry(*refs):
        if bias is not None:
            (q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, o_ref, l_ref,
             dq_ref, dk_ref, dv_ref, db_ref) = refs
        else:
            (q_ref, k_ref, v_ref, s_ref, do_ref, o_ref, l_ref,
             dq_ref, dk_ref, dv_ref) = refs
            b_ref = db_ref = None
        kernel(q_ref, k_ref, v_ref, b_ref, s_ref, do_ref, o_ref, l_ref,
               dq_ref, dk_ref, dv_ref, db_ref)

    out_specs = [
        pl.BlockSpec((g, t_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, tk_pad, hd), lambda bi: (bi, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, t_pad, hd), q.dtype),
        jax.ShapeDtypeStruct((b, tk_pad, hd), k.dtype),
        jax.ShapeDtypeStruct((b, tk_pad, hd), v.dtype),
    ]
    if bias is not None:
        out_specs.append(pl.BlockSpec((g, 8, tk_pad), lambda bi: (bi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 8, tk_pad), jnp.float32))
    res = named_pallas_call(
        "dense_vmem.bwd",
        entry,
        grid=(b // g,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if bias is not None:
        dq, dk, dv, db = res
        db = db[:, 0, :t_k]
    else:
        dq, dk, dv = res
        db = None
    return dq[:, :t], dk[:, :t_k], dv[:, :t_k], db


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _dense_attention(q, k, v, bias, seed, num_heads, causal, scale,
                     dropout_rate):
    return _dense_fwd(q, k, v, bias, seed, num_heads, causal, scale,
                      dropout_rate)[0]


def _dense_fwd(q, k, v, bias, seed, num_heads, causal, scale, dropout_rate):
    out, lse = _dense_fwd_impl(q, k, v, bias, seed, num_heads, causal,
                               scale, dropout_rate, _INTERPRET)
    return out, (q, k, v, bias, seed, out, lse)


def _dense_bwd(num_heads, causal, scale, dropout_rate, res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv, db = _dense_bwd_impl(q, k, v, bias, seed, out, lse, g,
                                     num_heads, causal, scale, dropout_rate,
                                     _INTERPRET)
    dbias = db.astype(bias.dtype) if bias is not None else None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, None)


_dense_attention.defvjp(_dense_fwd, _dense_bwd)

# dense path ceiling: whole [T,HD] q/k/v/do/out blocks + per-head [T,Tk]
# f32 transients must fit the ~16 MB VMEM comfortably
_DENSE_MAX_Q = 512
_DENSE_MAX_KV = 1024
_DENSE_VMEM_BUDGET = 10 * 1024 * 1024


def _dense_fits(t, t_k, hd, esize):
    """Conservative VMEM estimate for the dense bwd step (the larger of the
    two): 4 q-length + 4 kv-length packed blocks plus ~4 per-head [t, tk]
    f32 transients."""
    t_pad = ((t + 127) // 128) * 128
    tk_pad = ((t_k + 127) // 128) * 128
    blocks = (4 * t_pad + 4 * tk_pad) * hd * esize
    transients = 4 * t_pad * tk_pad * 4
    return blocks + transients <= _DENSE_VMEM_BUDGET


# ---------------------------------------------------------------------------
# differentiable wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_attention(q, k, v, bias, seed, causal, scale, dropout_rate):
    return _flash_fwd(q, k, v, bias, seed, causal, scale, dropout_rate)[0]


def _flash_fwd(q, k, v, bias, seed, causal, scale, dropout_rate):
    out, lse = _flash_fwd_impl(
        q, k, v, bias, seed, causal, scale, dropout_rate,
        _block_sizes(q.shape[1], k.shape[1]), _INTERPRET)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(causal, scale, dropout_rate, res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv, db = _flash_bwd_impl(
        q, k, v, bias, seed, out, lse, g, causal, scale, dropout_rate,
        _block_sizes(q.shape[1], k.shape[1]), _INTERPRET)
    dbias = db.astype(bias.dtype) if bias is not None else None
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), \
        dbias, None


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# segmented streaming: a causal square too long for the head-split kernels'
# VMEM (they hold the whole K/V forward and the whole q/do/dq backward of a
# head) is cut into ``segment``-long pieces along T. Query segment i meets
# key segment j <= i through the SAME head-split kernels: causal on the
# diagonal, unmasked below it, never above. Forward, the partial outputs are
# merged by their row logsumexp; backward, every pair is given the merged
# output and logsumexp, with which the kernel's probabilities and its
# ``delta`` are the whole row's, so the pairs' dq add up over j and their
# dk/dv over i.
# ---------------------------------------------------------------------------

def _head_split_fits(t, t_k, d, esize, blocks=None):
    """VMEM the head-split streaming kernels allocate for one head against
    the budget (cf. :func:`_packed_stream_fits`): forward the whole K/V,
    two buffers each; backward the whole q/do, two buffers each, and the
    float32 dq, also counted twice; and in both the [block, block] float32
    score tiles the body keeps (the chip's compiler counted 16.14M for the
    backward at T=2048, D=256, blocks of 512: this model says 17.25M)."""
    block_q, block_k = blocks or _block_sizes(t, t_k)

    def pad(x, m):
        return ((x + m - 1) // m) * m

    tk_pad, t_pad = pad(t_k, block_k), pad(t, block_q)
    tile = block_q * block_k * 4
    fwd = (4 * tk_pad * d * esize + 4 * block_q * d * esize
           + 2 * 8 * t_pad * 4 + 4 * tile + block_q * d * 4)
    bwd = (4 * t_pad * d * esize + 2 * t_pad * d * 4
           + 8 * block_k * d * esize + 4 * 8 * t_pad * 4
           + 6 * tile + 2 * block_k * d * 4)
    return max(fwd, bwd) <= _STREAM_VMEM_BUDGET


def _segment_plan(t, d, esize):
    """(segment, block): the longest segment that divides T, and the
    largest block for it, whose head-split kernels fit; None if there is
    none. A wide head takes smaller blocks than the default."""
    least = 8 if _INTERPRET else 128
    for segment in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16):
        if segment >= t or t % segment:
            continue
        block = _block_sizes(segment, segment)[0]
        while block >= least and segment % block == 0:
            if _head_split_fits(segment, segment, d, esize, (block, block)):
                return segment, block
            if block % (2 * least):
                break
            block //= 2
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _segmented_attention(q, k, v, scale, plan):
    """``plan``: (segment, block) of :func:`_segment_plan`."""
    return _segmented_fwd(q, k, v, scale, plan)[0]


def _segments(x, segment):
    return [x[:, i:i + segment] for i in range(0, x.shape[1], segment)]


def _segmented_fwd(q, k, v, scale, plan):
    segment, block = plan
    blocks = (block, block)
    qs, ks, vs = (_segments(x, segment) for x in (q, k, v))
    outs, lses = [], []
    for i, q_i in enumerate(qs):
        parts = [_flash_fwd_impl(q_i, ks[j], vs[j], None, jnp.uint32(0),
                                 i == j, scale, 0.0, blocks, _INTERPRET)
                 for j in range(i + 1)]
        lse = jnp.stack([p[1] for p in parts])            # [j, BH, S]
        total = jax.scipy.special.logsumexp(lse, axis=0)
        weight = jnp.exp(lse - total[None])
        out = sum(p[0].astype(jnp.float32) * weight[j][..., None]
                  for j, p in enumerate(parts))
        outs.append(out.astype(q.dtype))
        lses.append(total)
    out = jnp.concatenate(outs, axis=1)
    return out, (q, k, v, out, jnp.concatenate(lses, axis=1))


def _segmented_bwd(scale, plan, res, g):
    q, k, v, out, lse = res
    segment, block = plan
    blocks = (block, block)
    qs, ks, vs, os_, ls, gs = (_segments(x, segment)
                               for x in (q, k, v, out, lse, g))
    n = len(qs)
    dq = [None] * n
    dk = [None] * n
    dv = [None] * n

    def add(acc, x):
        x = x.astype(jnp.float32)
        return x if acc is None else acc + x

    for i in range(n):
        for j in range(i + 1):
            dq_ij, dk_ij, dv_ij, _ = _flash_bwd_impl(
                qs[i], ks[j], vs[j], None, jnp.uint32(0), os_[i], ls[i],
                gs[i], i == j, scale, 0.0, blocks, _INTERPRET)
            dq[i] = add(dq[i], dq_ij)
            dk[j] = add(dk[j], dk_ij)
            dv[j] = add(dv[j], dv_ij)
    return (jnp.concatenate(dq, axis=1).astype(q.dtype),
            jnp.concatenate(dk, axis=1).astype(k.dtype),
            jnp.concatenate(dv, axis=1).astype(v.dtype))


_segmented_attention.defvjp(_segmented_fwd, _segmented_bwd)


# ---------------------------------------------------------------------------
# public entry: packed [B, T, H*D] layout used by the layers API
# ---------------------------------------------------------------------------

def kernel_plan(q_shape, k_shape, num_heads, esize, causal=False,
                dropout_rate=0.0, bias_kind=None, rng_available=True,
                platform=None):
    """The attention dispatch decision as a structured
    ``ops.gates.GateDecision`` (ISSUE 15): ``kernel`` is which path runs
    — ``dense_vmem`` (whole-sequence VMEM-resident, packed layout),
    ``packed_stream`` (copy-free streaming, a lane window of the packed
    heads a program), ``head_split_stream`` (streaming one head a
    program, with the [B,T,H,D] relayout copies round every site),
    ``segmented_stream`` (the head-split kernels on segments of a causal
    square whose head does not fit them whole), or ``reference`` — and
    ``reasons`` records every check that demoted the choice. This IS the
    dispatch logic :func:`flash_attention` runs (single source).

    Who takes which length (self-attention, bf16, the chip's blocks of
    512; float32 halves the streaming lengths): ``dense_vmem`` to T=512
    (keys to 1024); ``packed_stream`` from there to T=3072 at D=64 and
    D=128 and to T=1024 at D=256, as far as one lane window's full-T
    q/do/dq fit the VMEM budget; beyond that ``head_split_stream`` (its
    own count holds to T=5632 at D=64), and for a causal square without
    bias or dropout that it cannot hold either, ``segmented_stream``
    (D=128 from T=4096, D=256 from T=2048).

    ``platform``: what ``gates.platform_reason`` says of where the step
    runs; the static resource pass evaluates the gate shape-only and
    leaves it ``None``.

    ``bias_kind``: None | 'key' (padding-mask form) | 'rich' (anything
    else — reference path only)."""
    b, t, hd = q_shape
    t_k = k_shape[1]
    d = hd // max(num_heads, 1)
    reasons = []
    if platform is not None:
        reasons.append(platform)
    if bias_kind == "rich":
        reasons.append(GateReason(
            "bias", "non-key-mask bias shape: only the additive "
            "[B,1,1,Tk]/[B,Tk] padding-mask form streams"))
    if d % 8 != 0:
        reasons.append(GateReason(
            "geometry", "head dim %d is not a multiple of 8 "
            "(Mosaic-unfriendly; would be a lowering error)" % d))
    if dropout_rate > 0.0 and (_INTERPRET or not rng_available):
        reasons.append(GateReason(
            "dropout", "attention dropout needs the TPU PRNG primitives "
            "(interpret mode / no rng threaded)"))
    if reasons:
        return GateDecision(False, "reference", fallback="packed_stream",
                            reasons=reasons)
    if (t <= _DENSE_MAX_Q and t_k <= _DENSE_MAX_KV
            and (not causal or t <= t_k)
            and _dense_fits(t, t_k, hd, esize)):
        return GateDecision(True, "dense_vmem")
    if causal and t != t_k:
        # the streaming kernels anchor the causal diagonal at position 0
        # while mha_reference anchors it at the sequence end; they
        # disagree for t != t_k, so only the square case streams
        reasons.append(GateReason(
            "geometry", "causal with t_q=%d != t_k=%d: streaming kernels "
            "anchor the diagonal differently from the reference" % (t, t_k)))
        return GateDecision(False, "reference", fallback="packed_stream",
                            reasons=reasons)
    if _packed_stream_fits(t, t_k, hd, esize, num_heads):
        return GateDecision(True, "packed_stream")
    reasons.append(GateReason(
        "vmem", "packed streaming working set for T=%d Tk=%d, one %d-lane "
        "window of H*D=%d (%.1f MB), exceeds the %.0f MB VMEM budget — "
        "falls back to the head-split path (+[B,T,H,D] relayout copies "
        "around every attention site)"
        % (t, t_k, _lane_window(hd, num_heads), hd,
           max(_packed_stream_vmem(t, t_k, hd, esize, num_heads)) / 2**20,
           _STREAM_VMEM_BUDGET / 2**20)))
    if not _head_split_fits(t, t_k, d, esize) and causal \
            and bias_kind is None and dropout_rate == 0.0:
        segmented = _segment_plan(t, d, esize)
        if segmented is not None:
            reasons.append(GateReason(
                "vmem", "one head's K/V (forward) or q/do/dq (backward) at "
                "T=%d D=%d exceeds the %.0f MB VMEM budget: the head-split "
                "kernels run on %d x %d segments of T in blocks of %d, "
                "merged by logsumexp"
                % (t, d, _STREAM_VMEM_BUDGET / 2**20, segmented[0],
                   segmented[0], segmented[1])))
            return GateDecision(True, "segmented_stream",
                                fallback="head_split_stream",
                                reasons=reasons)
    return GateDecision(True, "head_split_stream",
                        fallback="packed_stream", reasons=reasons)


def plan_for(q, k, bias, num_heads, causal, dropout_rate, rng):
    """:func:`kernel_plan` for concrete arrays: classifies the bias form
    and asks where the step is placed. Used by the op impl (which
    records the decision in the op's attrs) and by
    :func:`flash_attention` itself."""
    b, _, _ = q.shape
    t_k = k.shape[1]
    bias_kind = None
    if bias is not None:
        if (bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1
                and bias.shape[0] in (1, b)) or \
                (bias.ndim == 2 and bias.shape[0] in (1, b)):
            bias_kind = "key"
        else:
            bias_kind = "rich"
    return kernel_plan(q.shape, k.shape, num_heads, q.dtype.itemsize,
                       causal=causal, dropout_rate=float(dropout_rate),
                       bias_kind=bias_kind,
                       rng_available=rng is not None,
                       platform=gates.platform_reason(_INTERPRET))


def flash_attention(q, k, v, num_heads, bias=None, causal=False,
                    dropout_rate=0.0, rng=None, plan=None):
    """q,k,v: [B, T, H*D] (packed heads). ``bias``: None or additive
    [B, 1, 1, Tk] / [B, Tk] key mask (the padding-mask form; richer bias
    shapes fall back to the reference path). Returns [B, T, H*D].
    ``plan``: a precomputed :func:`plan_for` decision (the op impl
    records it); None recomputes it here."""
    b, t, hd = q.shape
    d = hd // num_heads
    t_k = k.shape[1]

    key_bias = None
    ref_bias = bias
    if bias is not None:
        ba = bias
        if (ba.ndim == 4 and ba.shape[1] == 1 and ba.shape[2] == 1
                and ba.shape[0] in (1, b)):
            key_bias = jnp.broadcast_to(
                ba.reshape(ba.shape[0], t_k), (b, t_k))
        elif ba.ndim == 2 and ba.shape[0] in (1, b):
            key_bias = jnp.broadcast_to(ba, (b, t_k))
            # the reference path adds bias to [B, H, Tq, Tk] logits:
            # lift the 2-D key form so broadcasting stays right-aligned
            ref_bias = key_bias[:, None, None, :]

    scale = 1.0 / math.sqrt(d)

    if plan is None:
        plan = plan_for(q, k, bias, num_heads, causal, dropout_rate, rng)

    if dropout_rate > 0.0 and plan.kernel != "reference":
        seed = jax.random.randint(rng, (), 0, np.iinfo(np.int32).max,
                                  dtype=jnp.int32).astype(jnp.uint32)
    else:
        seed = jnp.uint32(0)

    # short sequences: whole-sequence VMEM-resident kernel on the packed
    # layout (no head-split transposes, heads looped in-kernel). Causal
    # with t > t_k would create fully-masked rows, whose additive-mask
    # softmax (uniform over tk_pad incl. padding) diverges from the
    # reference's uniform-over-real-keys — those stay on the fallback
    # (kernel_plan encodes the rule).
    if plan.kernel == "dense_vmem":
        return _dense_attention(q, k, v, key_bias, seed, num_heads, causal,
                                scale, float(dropout_rate))

    if plan.kernel == "packed_stream":
        # copy-free streaming path: the kernels' BlockSpecs take lane
        # windows of the packed layout as it is — no [B,T,H,D] head-split
        # relayouts around the custom calls
        return _packed_stream_attention(q, k, v, key_bias, seed, num_heads,
                                        causal, scale, float(dropout_rate))

    def split(x, t_):
        return x.reshape(b, t_, num_heads, d).transpose(0, 2, 1, 3)

    qh, kh, vh = split(q, t), split(k, t_k), split(v, t_k)

    if plan.kernel == "reference":
        # dropout applies to the attention weights, matching the kernels
        out = mha_reference(qh, kh, vh, ref_bias, causal, scale,
                            dropout_rate=dropout_rate, rng=rng)
        return out.transpose(0, 2, 1, 3).reshape(b, t, hd)

    # head-split streaming: flatten heads into the grid's leading axis
    qf = qh.reshape(b * num_heads, t, d)
    kf = kh.reshape(b * num_heads, t_k, d)
    vf = vh.reshape(b * num_heads, t_k, d)
    bf = (jnp.repeat(key_bias, num_heads, axis=0)
          if key_bias is not None else None)
    if plan.kernel == "segmented_stream":
        out = _segmented_attention(qf, kf, vf, scale,
                                   _segment_plan(t, d, q.dtype.itemsize))
    else:
        out = _flash_attention(qf, kf, vf, bf, seed, causal, scale,
                               float(dropout_rate))
    out = out.reshape(b, num_heads, t, d)
    return out.transpose(0, 2, 1, 3).reshape(b, t, hd)
