"""Flash-attention kernel numerics vs the jax reference, run on CPU via
Pallas interpret mode (the dropout path needs the TPU PRNG and is covered
by the bench on hardware)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.ops.flash_attention as fa
from paddle_tpu.ops.gates import GateDecision


@pytest.fixture(autouse=True)
def interpret_mode():
    fa._INTERPRET = True
    yield
    fa._INTERPRET = False


def _mk(rng, b, h, t, tk, d):
    q = rng.normal(0, 1, (b, t, h * d)).astype("f4")
    k = rng.normal(0, 1, (b, tk, h * d)).astype("f4")
    v = rng.normal(0, 1, (b, tk, h * d)).astype("f4")
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _ref(q, k, v, h, bias=None, causal=False):
    b, t, hd = q.shape
    d = hd // h

    def split(x):
        return x.reshape(b, -1, h, d).transpose(0, 2, 1, 3)

    out = fa.mha_reference(split(q), split(k), split(v), bias, causal)
    return out.transpose(0, 2, 1, 3).reshape(b, t, hd)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(rng, causal):
    q, k, v = _mk(rng, 2, 2, 24, 24, 8)
    got = fa.flash_attention(q, k, v, num_heads=2, causal=causal)
    want = _ref(q, k, v, 2, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_forward_key_bias(rng):
    """[B, 1, 1, Tk] additive padding-mask bias takes the kernel path."""
    b, h, t, tk, d = 2, 2, 16, 24, 8
    q, k, v = _mk(rng, b, h, t, tk, d)
    lengths = np.array([20, 9])
    bias4 = np.where(np.arange(tk)[None] < lengths[:, None], 0.0, -1e9)
    bias4 = jnp.asarray(bias4[:, None, None, :].astype("f4"))
    got = fa.flash_attention(q, k, v, num_heads=h, bias=bias4)
    want = _ref(q, k, v, h, bias=bias4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(rng, causal):
    b, h, t, d = 1, 2, 16, 8
    q, k, v = _mk(rng, b, h, t, t, d)
    lengths = np.array([13])
    bias4 = np.where(np.arange(t)[None] < lengths[:, None], 0.0, -1e9)
    bias4 = jnp.asarray(bias4[:, None, None, :].astype("f4"))

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, num_heads=h, bias=bias4,
                               causal=causal)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _ref(q, k, v, h, bias=bias4, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-4,
            err_msg="d%s mismatch" % name)


def test_flash_backward_bias_gradient(rng):
    """A learned additive key bias gets its exact cotangent (column sums
    of dS), not silent zeros."""
    b, h, t, d = 1, 1, 12, 8
    q, k, v = _mk(rng, b, h, t, t, d)
    bias = jnp.asarray(rng.normal(0, 0.5, (b, t)).astype("f4"))

    def loss_flash(bias2):
        o = fa.flash_attention(q, k, v, num_heads=h, bias=bias2)
        return jnp.sum(o ** 2)

    def loss_ref(bias2):
        o = _ref(q, k, v, h, bias=bias2[:, None, None, :])
        return jnp.sum(o ** 2)

    gf = jax.grad(loss_flash)(bias)
    gr = jax.grad(loss_ref)(bias)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=5e-3,
                               atol=5e-4)


def test_flash_unpadded_and_padded_blocks(rng):
    """Sequence lengths not divisible by the block size round-trip."""
    q, k, v = _mk(rng, 1, 2, 19, 27, 8)
    got = fa.flash_attention(q, k, v, num_heads=2)
    want = _ref(q, k, v, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_zero_length_row_no_nan(rng):
    """A batch entry whose key mask is -inf everywhere (zero-length
    sequence) must produce finite gradients, not NaN."""
    b, h, t, d = 2, 1, 16, 8
    q, k, v = _mk(rng, b, h, t, t, d)
    lengths = np.array([12, 0])  # second sequence fully masked
    bias4 = np.where(np.arange(t)[None] < lengths[:, None], 0.0, -1e30)
    bias4 = jnp.asarray(bias4[:, None, None, :].astype("f4"))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, num_heads=h,
                                          bias=bias4) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for arr in g:
        assert np.isfinite(np.asarray(arr)).all()


def test_flash_2d_and_broadcast_bias_fallback(rng):
    """2-D [B, Tk] bias and [1, 1, 1, Tk] broadcast bias work on BOTH the
    kernel path and (with _INTERPRET off on CPU) the reference fallback."""
    b, h, t, d = 2, 2, 12, 8
    q, k, v = _mk(rng, b, h, t, t, d)
    bias2 = jnp.asarray(rng.normal(0, 0.3, (b, t)).astype("f4"))
    got = fa.flash_attention(q, k, v, num_heads=h, bias=bias2)
    want = _ref(q, k, v, h, bias=bias2[:, None, None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    bias1 = jnp.asarray(rng.normal(0, 0.3, (1, 1, 1, t)).astype("f4"))
    got1 = fa.flash_attention(q, k, v, num_heads=h, bias=bias1)
    want1 = _ref(q, k, v, h, bias=bias1)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want1),
                               rtol=2e-4, atol=2e-4)
    # reference fallback path (kernel disabled) agrees for the 2-D form
    fa._INTERPRET = False
    got_fb = fa.flash_attention(q, k, v, num_heads=h, bias=bias2)
    fa._INTERPRET = True
    np.testing.assert_allclose(np.asarray(got_fb), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("family", ["head_split_stream", "packed_stream"],
                         ids=["head-split", "packed"])
@pytest.mark.parametrize("causal,t,tk", [
    (False, 136, 104),   # unaligned kv tail, multi-block both axes
    (True, 136, 136),    # causal diagonal + unaligned tails
    (False, 72, 136),    # q shorter than kv, kv tail masked
])
def test_flash_multiblock_unaligned_tails(rng, causal, t, tk, family):
    """Sequences spanning several blocks with t % block != 0 exercise the
    mask-specialized loop splits (unmasked interior / masked diagonal +
    padded tails) in BOTH streaming paths — the packed [B,T,H*D]
    heads-in-kernel one and the legacy head-split one — fwd and bwd, with
    a key bias. The family is named (``plan=``): at these
    (interpret-tractable) lengths the gate would pick the dense path."""
    plan = GateDecision(True, family)
    b, h, d = 1, 2, 8
    q, k, v = _mk(rng, b, h, t, tk, d)
    lengths = np.array([tk - 5])
    bias4 = np.where(np.arange(tk)[None] < lengths[:, None], 0.0, -1e9)
    bias4 = jnp.asarray(bias4[:, None, None, :].astype("f4"))

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, num_heads=h, bias=bias4,
                               causal=causal, plan=plan)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _ref(q, k, v, h, bias=bias4, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, num_heads=h, bias=bias4,
                                      causal=causal, plan=plan)),
        np.asarray(_ref(q, k, v, h, bias=bias4, causal=causal)),
        rtol=5e-4, atol=5e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-2, atol=1e-3,
                                   err_msg="d%s" % name)


def test_packed_stream_matches_head_split(rng):
    """The packed streaming kernels agree with the head-split streaming
    kernels (not just the reference) fwd+bwd at a multi-head,
    multi-block, biased shape — the copy-free path is a pure layout
    change."""
    b, h, t, d = 2, 2, 72, 8
    q, k, v = _mk(rng, b, h, t, t, d)
    lengths = np.array([t - 7, t])
    bias4 = np.where(np.arange(t)[None] < lengths[:, None], 0.0, -1e9)
    bias4 = jnp.asarray(bias4[:, None, None, :].astype("f4"))

    def loss(q, k, v, family):
        o = fa.flash_attention(q, k, v, num_heads=h, bias=bias4,
                               causal=True, plan=GateDecision(True, family))
        return jnp.sum(o * jnp.sin(o)), o

    outs = {}
    for packed in (False, True):
        (l, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v, "packed_stream" if packed else "head_split_stream")
        outs[packed] = (np.asarray(o), [np.asarray(x) for x in g])
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(outs[True][1], outs[False][1], "qkv"):
        np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


def test_packed_stream_vmem_gate(monkeypatch):
    """The packed-stream gate declines shapes whose full-T packed refs
    exceed the VMEM budget (those keep the head-split path). At the
    chip's 512-blocks the seq-2048 transformer-base bench geometry is one
    of them: the chip's compiler refused it (tests/test_tpu_compile.py)."""
    monkeypatch.setattr(fa, "_INTERPRET", False)  # the chip's block sizes
    assert fa._packed_stream_fits(1024, 1024, 512, 2, 8)
    assert not fa._packed_stream_fits(2048, 2048, 512, 2, 8)  # bench config
    assert not fa._packed_stream_fits(16384, 16384, 512, 2, 8)
    assert not fa._packed_stream_fits(2048, 2048, 4096, 2, 32)


def test_flash_causal_multiblock_grads(rng):
    """Sequences spanning multiple 256-blocks exercise the causal
    block-skipping bounds in fwd, dQ and dK/dV kernels."""
    b, h, t, d = 1, 1, 300, 8
    q, k, v = _mk(rng, b, h, t, t, d)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, num_heads=h,
                                          causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, h, causal=True) ** 2)

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, num_heads=h, causal=True)),
        np.asarray(_ref(q, k, v, h, causal=True)), rtol=5e-4, atol=5e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-2, atol=1e-3,
                                   err_msg="d%s" % name)
