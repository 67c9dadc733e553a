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


# (heads, head dim) of the packed streaming kernels' lane windows
# (``fa._lane_window``): a program holds lcm(D, 128) lanes where that
# divides H*D, else all of H*D
_WINDOW_GEOMETRIES = [
    pytest.param(2, 8, id="full-width-h2d8"),        # 128 does not divide 16
    pytest.param(4, 64, id="two-windows-of-two-heads"),
    pytest.param(2, 128, id="one-head-a-window"),
]


@pytest.mark.parametrize("family", ["head_split_stream", "packed_stream"],
                         ids=["head-split", "packed"])
@pytest.mark.parametrize("h,d", _WINDOW_GEOMETRIES)
@pytest.mark.parametrize("causal,t,tk", [
    (False, 136, 104),   # unaligned kv tail, multi-block both axes
    (True, 136, 136),    # causal diagonal + unaligned tails
    (False, 72, 136),    # q shorter than kv, kv tail masked
])
def test_flash_multiblock_unaligned_tails(rng, causal, t, tk, h, d, family):
    """Sequences spanning several blocks with t % block != 0 exercise the
    mask-specialized loop splits (unmasked interior / masked diagonal +
    padded tails) in BOTH streaming paths — the packed [B,T,H*D]
    heads-in-kernel one, at each geometry of its lane windows, and the
    head-split one — fwd and bwd, with a key bias. The family is named
    (``plan=``): at these (interpret-tractable) lengths the gate would
    pick the dense path."""
    plan = GateDecision(True, family)
    b = 1
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


@pytest.mark.parametrize("with_bias", [True, False],
                         ids=["key-bias", "no-bias"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("h,d,window", [
    (2, 8, 16),      # 128 does not divide H*D = 16: the full width
    (8, 64, 128),    # transformer-base's heads: four windows of two heads
    (2, 128, 128),   # one head a window
    (3, 40, 120),    # lcm(40, 128) = 640 does not divide 120: the full width
], ids=["h2d8-full-width", "h8d64-four-windows", "h2d128-head-a-window",
        "h3d40-full-width"])
def test_packed_stream_matches_head_split(rng, h, d, window, causal,
                                          with_bias):
    """The packed streaming kernels agree with the head-split streaming
    kernels (not just the reference) fwd+bwd at a multi-head,
    multi-block shape whose T is no multiple of the block — the copy-free
    path is a pure layout change, whatever its lane window. The key bias
    is differentiated too: every head adds to its gradient, which the
    packed backward puts out one partial a window and sums outside."""
    assert fa._lane_window(h * d, h) == window
    b, t = 2, 72
    q, k, v = _mk(rng, b, h, t, t, d)
    bias = None
    if with_bias:
        lengths = np.array([t - 7, t])
        bias = np.where(np.arange(t)[None] < lengths[:, None], 0.0, -1e9)
        bias = jnp.asarray(
            bias.astype("f4") + rng.normal(0, 0.5, (b, t)).astype("f4"))

    def loss(q, k, v, bias, family):
        o = fa.flash_attention(q, k, v, num_heads=h, bias=bias,
                               causal=causal,
                               plan=GateDecision(True, family))
        return jnp.sum(o * jnp.sin(o)), o

    wrt = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    outs = {}
    for family in ("packed_stream", "head_split_stream"):
        (_, o), g = jax.value_and_grad(loss, argnums=wrt, has_aux=True)(
            q, k, v, bias, family)
        outs[family] = (np.asarray(o), [np.asarray(x) for x in g])
    np.testing.assert_allclose(outs["packed_stream"][0],
                               outs["head_split_stream"][0],
                               rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(outs["packed_stream"][1],
                           outs["head_split_stream"][1],
                           ("q", "k", "v", "bias")):
        np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)
    if with_bias:
        # and against the plain reference: column sums of dS over all heads
        def loss_ref(bias):
            o = _ref(q, k, v, h, bias=bias[:, None, None, :], causal=causal)
            return jnp.sum(o * jnp.sin(o))

        np.testing.assert_allclose(
            outs["packed_stream"][1][3], np.asarray(jax.grad(loss_ref)(bias)),
            rtol=5e-3, atol=5e-4)


def test_packed_stream_vmem_gate(monkeypatch):
    """The packed-stream gate counts ONE lane window of the packed head
    dimension (ISSUE 29), so the seq-2048 transformer-base bench geometry
    fits at the chip's 512-blocks (the chip's compiler agrees:
    tests/test_tpu_compile.py). It declines what one window's full-T refs
    do not fit: a wide head at 8192 (``qwen3next.train.s8192``'s, which
    stays segmented) and 16k tokens at any head."""
    monkeypatch.setattr(fa, "_INTERPRET", False)  # the chip's block sizes
    assert fa._packed_stream_fits(1024, 1024, 512, 2, 8)
    assert fa._packed_stream_fits(2048, 2048, 512, 2, 8)  # bench config
    assert fa._packed_stream_fits(2048, 2048, 4096, 2, 32)  # 16 windows
    assert not fa._packed_stream_fits(8192, 8192, 4096, 2, 16)
    assert not fa._packed_stream_fits(16384, 16384, 512, 2, 8)
    # the full-width case (128 does not divide 8 heads of 40) is as wide as
    # before, and stops where it did
    assert fa._lane_window(320, 8) == 320
    assert fa._packed_stream_fits(1024, 1024, 320, 2, 8)
    assert not fa._packed_stream_fits(4096, 4096, 320, 2, 8)


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
