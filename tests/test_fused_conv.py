"""Fused conv+BN+ReLU(+residual) epilogue numerics (ops/fused_conv.py)
and the epilogue-fusion rewrite (core/epilogue_fusion.py), run on CPU via
Pallas interpret mode.

Shapes are the ResNet-50 bottleneck channel geometries (the shapes the
kernels exist for) at interpret-tractable spatial/batch sizes: the lane
math (tap shifts, row-wrap masks, per-channel moments) is identical at
56x56 and 8x8."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.ops.fused_conv as fc


@pytest.fixture(autouse=True)
def interpret_mode():
    fc._INTERPRET = True
    yield
    fc._INTERPRET = False


def _unfused_chain(x, w, gamma, beta, mean, var, eps, act, residual,
                   stride, pad, is_test=False, momentum=0.9):
    """EXACTLY the unfused op composition the executor traces:
    _conv2d -> _batch_norm -> elementwise_add -> relu
    (core/opimpl/nn_ops.py / math_ops.py), including the bf16 storage
    rounding between the conv and the BN statistics."""
    co = jax.lax.conv_general_dilated(
        x, w, stride, [(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    in_dtype = co.dtype
    cof = co.astype(jnp.float32) if co.dtype == jnp.bfloat16 else co
    if is_test:
        bm, bv = mean.astype(jnp.float32), var.astype(jnp.float32)
        mean_out, var_out = mean, var
    else:
        n = co.shape[0] * co.shape[2] * co.shape[3]
        s1 = jnp.sum(cof, axis=(0, 2, 3))
        s2 = jnp.sum(cof * cof, axis=(0, 2, 3))
        bm = s1 / n
        bv = jnp.maximum(s2 / n - bm * bm, 0.0)
        mean_out = momentum * mean + (1 - momentum) * jax.lax.stop_gradient(bm)
        var_out = momentum * var + (1 - momentum) * jax.lax.stop_gradient(bv)
    inv = jax.lax.rsqrt(bv.reshape(1, -1, 1, 1) + eps)
    y = (cof - bm.reshape(1, -1, 1, 1)) * inv \
        * gamma.astype(jnp.float32).reshape(1, -1, 1, 1) \
        + beta.astype(jnp.float32).reshape(1, -1, 1, 1)
    y = y.astype(in_dtype)
    if residual is not None:
        y = y + residual.astype(y.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    return y, mean_out, var_out, bm, bv


# (C_in, C_out, k, stride, act, with_residual) — the four bottleneck
# geometries: reduce-1x1, body-3x3, expand-1x1+residual+relu, and the
# stride-2 1x1 shortcut
GEOMS = [
    (16, 8, 1, 1, "relu", False),
    (8, 8, 3, 1, "relu", False),
    (8, 16, 1, 1, "relu", True),
    (16, 8, 1, 2, None, False),
]


def _mk(rng, cin, cout, k, stride, with_res, n=2, hw=8, dtype="f4"):
    x = jnp.asarray(rng.randn(n, cin, hw, hw).astype(dtype))
    w = jnp.asarray((rng.randn(cout, cin, k, k) * 0.2).astype(dtype))
    gamma = jnp.asarray((rng.rand(cout) + 0.5).astype("f4"))
    beta = jnp.asarray((rng.randn(cout) * 0.1).astype("f4"))
    mean = jnp.asarray((rng.randn(cout) * 0.1).astype("f4"))
    var = jnp.asarray((rng.rand(cout) + 0.5).astype("f4"))
    res = None
    if with_res:
        res = jnp.asarray(
            rng.randn(n, cout, hw // stride, hw // stride).astype(dtype))
    return x, w, gamma, beta, mean, var, res


@pytest.mark.parametrize("cin,cout,k,stride,act,with_res", GEOMS)
def test_forward_matches_unfused(rng, cin, cout, k, stride, act, with_res):
    x, w, gamma, beta, mean, var, res = _mk(rng, cin, cout, k, stride,
                                            with_res)
    pad = ((k - 1) // 2,) * 2
    got = fc.fused_conv_bn_act(
        x, w, gamma, beta, mean, var, strides=(stride,) * 2, paddings=pad,
        eps=1e-5, momentum=0.9, act=act, residual=res)
    xs = x[:, :, ::2, ::2] if stride == 2 else x
    want = _unfused_chain(xs, w, gamma, beta, mean, var, 1e-5, act, res,
                          (1, 1), pad)
    for g, r, name in zip(got, want,
                          ("y", "mean_out", "var_out", "saved_mean",
                           "saved_var")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=3e-5, atol=3e-5, err_msg=name)


@pytest.mark.parametrize("cin,cout,k,stride,act,with_res", GEOMS)
def test_backward_matches_unfused(rng, cin, cout, k, stride, act,
                                  with_res):
    x, w, gamma, beta, mean, var, res = _mk(rng, cin, cout, k, stride,
                                            with_res)
    pad = ((k - 1) // 2,) * 2

    def loss_fused(x, w, gamma, beta, *r):
        y = fc.fused_conv_bn_act(
            x, w, gamma, beta, mean, var, strides=(stride,) * 2,
            paddings=pad, eps=1e-5, momentum=0.9, act=act,
            residual=r[0] if r else None)[0]
        return jnp.sum(y * jnp.cos(y))

    def loss_ref(x, w, gamma, beta, *r):
        xs = x[:, :, ::2, ::2] if stride == 2 else x
        y = _unfused_chain(xs, w, gamma, beta, mean, var, 1e-5, act,
                           r[0] if r else None, (1, 1), pad)[0]
        return jnp.sum(y * jnp.cos(y))

    args = (x, w, gamma, beta) + ((res,) if with_res else ())
    an = tuple(range(len(args)))
    gf = jax.grad(loss_fused, argnums=an)(*args)
    gr = jax.grad(loss_ref, argnums=an)(*args)
    for a, b, name in zip(gf, gr, ("dx", "dw", "dgamma", "dbeta", "dres")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_bf16_amp_tolerance(rng):
    """bf16 activations/weights (the AMP bench configuration): fwd+bwd
    track the unfused bf16 composition within AMP tolerance — including
    the storage rounding of the conv output before the f32 statistics."""
    cin, cout, k = 8, 16, 3
    x, w, gamma, beta, mean, var, res = _mk(rng, cin, cout, k, 1, True)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    resb = res.astype(jnp.bfloat16)

    def loss_fused(x, w, gamma, beta, res):
        y, mo, vo, sm, sv = fc.fused_conv_bn_act(
            x, w, gamma, beta, mean, var, strides=(1, 1), paddings=(1, 1),
            eps=1e-5, momentum=0.9, act="relu", residual=res)
        return jnp.sum((y * jnp.cos(y)).astype(jnp.float32)), (y, sm, sv)

    def loss_ref(x, w, gamma, beta, res):
        y, mo, vo, sm, sv = _unfused_chain(
            x, w, gamma, beta, mean, var, 1e-5, "relu", res, (1, 1),
            (1, 1))
        return jnp.sum((y * jnp.cos(y)).astype(jnp.float32)), (y, sm, sv)

    (lf, (yf, smf, svf)), gf = jax.value_and_grad(
        loss_fused, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        xb, wb, gamma, beta, resb)
    (lr, (yr, smr, svr)), gr = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        xb, wb, gamma, beta, resb)
    np.testing.assert_allclose(np.asarray(yf, dtype=np.float32),
                               np.asarray(yr, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(smf), np.asarray(smr),
                               rtol=2e-2, atol=2e-2)
    for a, b, name in zip(gf, gr, ("dx", "dw", "dgamma", "dbeta", "dres")):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            rtol=5e-2, atol=5e-2, err_msg=name)


def test_inference_path(rng):
    """is_test=True folds the BN affine entirely into the conv epilogue
    (single kernel, no stats) and passes the moving stats through."""
    x, w, gamma, beta, mean, var, _ = _mk(rng, 8, 16, 3, 1, False)
    y, mo, vo, sm, sv = fc.fused_conv_bn_act(
        x, w, gamma, beta, mean, var, strides=(1, 1), paddings=(1, 1),
        eps=1e-5, momentum=0.9, act="relu", residual=None, is_test=True)
    want = _unfused_chain(x, w, gamma, beta, mean, var, 1e-5, "relu", None,
                          (1, 1), (1, 1), is_test=True)[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert sm is None and sv is None
    np.testing.assert_array_equal(np.asarray(mo), np.asarray(mean))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(var))


def test_geometry_gate():
    """The Pallas gate accepts exactly the bottleneck geometries and
    declines everything else (which replays the unfused ops)."""
    ok = fc.supported_geometry
    assert ok((2, 64, 56, 56), (64, 64, 1, 1), (1, 1), (0, 0), (1, 1), 1)
    assert ok((2, 64, 56, 56), (64, 64, 3, 3), (1, 1), (1, 1), (1, 1), 1)
    assert ok((2, 256, 56, 56), (512, 256, 1, 1), (2, 2), (0, 0), (1, 1), 1)
    # 7x7 stem, stride-2 3x3, groups, dilation: unfused replay
    assert not ok((2, 3, 224, 224), (64, 3, 7, 7), (2, 2), (3, 3), (1, 1), 1)
    assert not ok((2, 64, 56, 56), (64, 64, 3, 3), (2, 2), (1, 1), (1, 1), 1)
    assert not ok((2, 64, 56, 56), (64, 32, 3, 3), (1, 1), (1, 1), (1, 1), 2)
    assert not ok((2, 64, 56, 56), (64, 64, 3, 3), (1, 1), (1, 1), (2, 2), 1)
    # dynamic batch: replay
    assert not ok((-1, 64, 56, 56), (64, 64, 1, 1), (1, 1), (0, 0),
                  (1, 1), 1)


def test_executor_fused_pallas_matches_unfused(rng, monkeypatch):
    """End to end through the Executor: a bottleneck-shaped model trained
    3 steps with the fusion rewrite + Pallas kernels (interpret) matches
    the unfused program — loss trajectory AND moving BN stats."""
    import paddle_tpu as fluid

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            fluid.unique_name.switch()
            img = fluid.layers.data("img", shape=[8, 8, 8],
                                    dtype="float32")
            label = fluid.layers.data("label", shape=[1], dtype="int32")
            xx = fluid.layers.conv2d(img, 16, 1, bias_attr=False)
            xx = fluid.layers.batch_norm(xx, act="relu")
            short = xx
            y = fluid.layers.conv2d(xx, 16, 3, padding=1, bias_attr=False)
            y = fluid.layers.batch_norm(y)
            out = fluid.layers.elementwise_add(short, y, act="relu")
            out = fluid.layers.pool2d(out, pool_type="avg",
                                      global_pooling=True)
            logits = fluid.layers.fc(out, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, startup, loss

    feed_rng = np.random.RandomState(0)
    feed = {"img": feed_rng.randn(4, 8, 8, 8).astype("f4"),
            "label": feed_rng.randint(0, 4, (4, 1)).astype("i4")}

    def run(fuse):
        from paddle_tpu.core import epilogue_fusion

        main, startup, loss = build()
        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        with fluid.scope_guard(scope), monkeypatch.context() as patch:
            if not fuse:  # the step as the program wrote it: no rewrite
                patch.setattr(epilogue_fusion, "fuse_ops",
                              lambda ops, protected=(): (list(ops), None))
            exe.run(startup)
            vals = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                    for _ in range(3)]
            stats = {n: scope.numpy(n) for n in scope.var_names()
                     if "batch_norm" in n}
        return vals, stats

    fc._INTERPRET = False
    base, stats_base = run(False)      # unfused lowering
    fc._INTERPRET = True
    fused, stats_fused = run(True)     # rewrite + Pallas kernels
    np.testing.assert_allclose(base, fused, rtol=2e-4, atol=2e-5)
    for n in sorted(set(stats_base) & set(stats_fused)):
        np.testing.assert_allclose(stats_base[n], stats_fused[n],
                                   rtol=2e-4, atol=2e-5, err_msg=n)
