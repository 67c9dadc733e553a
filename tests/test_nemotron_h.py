"""The hybrid state-space / attention / latent-experts layers
(``models/nemotron_h``) at small sizes on the CPU: the chunked state-space
scan against the token-by-token recurrence under slow decays; routed
experts with sigmoid scores, a selection bias, a scale and ReLU-squared
experts against a one-hot plain form; the new attributes of ``rms_norm`` and
``causal_conv1d``; the shares of each layer kind adding up to the uncut
reference's layer; the whole tiny model through the Executor against the
benchmark's plain reference.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import nemotron_h
from paddle_tpu.ops import mamba2
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness  # noqa: E402
from test_qwen3_next import _layer_against_plain, _randn  # noqa: E402

REFERENCE = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "nemotron-3-super-120b-a12b.py"))
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

T = 3 * 8 + 5       # three chunks of 8 and a tail that is no whole chunk
W = fluid.ParamAttr(name="w")


# -- the ops' new attributes ------------------------------------------------

def test_rms_norm_gates_before_the_norm_in_groups_with_a_weight_each():
    rng = np.random.default_rng(1)

    def plain(p, x, z):
        y = (x * jax.nn.silu(z)).reshape(2, T, 2, 16)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
        return y.reshape(2, T, 32) * p["w"]

    _layer_against_plain(
        lambda x, z: layers.rms_norm(x, 1e-5, norm_dim=16, gate=z,
                                     gate_first=True, shared_weight=False,
                                     param_attr=W),
        plain, {"x": _randn(rng, 2, T, 32), "z": _randn(rng, 2, T, 32)})


def test_causal_conv_with_a_bias_against_plain():
    rng = np.random.default_rng(2)

    def plain(p, x):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, j:j + T] * p["w"][:, j]
                               for j in range(4)) + p["b"])

    _layer_against_plain(
        lambda x: layers.causal_conv1d(
            x, 4, "silu", param_attr=W,
            bias_attr=fluid.ParamAttr(name="b")),
        plain, {"x": _randn(rng, 2, T, 12)})


# -- the state-space scan ---------------------------------------------------

def _ssd_inputs(rng, b, t, h, p, g, n, slow):
    x, bm, cm = (_randn(rng, b, t, h, p), _randn(rng, b, t, g, n),
                 _randn(rng, b, t, g, n))
    # slow: dt * A of about -0.003 to -0.03 a token, so that what a chunk
    # hands on is most of what the next one reads
    dt = np.abs(_randn(rng, b, t, h)) * (0.02 if slow else 1.0) + 0.01
    a = -np.exp(_randn(rng, h) * (0.3 if slow else 1.0))
    return tuple(jnp.asarray(v) for v in (x, dt, a, bm, cm, _randn(rng, h)))


@pytest.mark.parametrize("t,chunk,group,mxu", [
    (T, 8, 16, None),                   # a tail that is no whole chunk
    (64, 8, 3, None),                   # groups of 3 chunks: 24 | 24 | 16+pad
    (37, 128, 16, None),                # one chunk, mostly padding
    (64, 16, 2, jnp.bfloat16),          # operands in bfloat16
], ids=["tail", "groups", "one_chunk", "bf16"])
def test_chunked_state_space_scan_equals_the_recurrence_under_slow_decays(
        t, chunk, group, mxu):
    rng = np.random.default_rng(3)
    args = _ssd_inputs(rng, 2, t, 4, 8, 2, 16, slow=True)
    cot = _randn(rng, 2, t, 4, 8)

    def total(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    def chunked(*a):
        return mamba2.chunk_mamba2(*a, chunk, mxu, group)

    want = mamba2.recurrent_mamba2(*args)
    got = chunked(*args)
    # the state matters: without what crosses a chunk's border the output
    # is far off (else this test would see no error in the carry)
    alone = mamba2.chunk_mamba2(*(v[:, -5:] if v.ndim > 1 else v
                                  for v in args), chunk, mxu, group)
    assert np.abs(np.asarray(alone) - np.asarray(want[:, -5:])).max() \
        > 0.3 * np.abs(np.asarray(want[:, -5:])).max()
    tol = dict(rtol=2e-4, atol=2e-4) if mxu is None \
        else dict(rtol=5e-2, atol=0.3)
    np.testing.assert_allclose(got, want, **tol)
    wanted = jax.grad(total(mamba2.recurrent_mamba2),
                      argnums=range(6))(*args)
    gotten = jax.grad(total(chunked), argnums=range(6))(*args)
    for i, (a, w) in enumerate(zip(gotten, wanted)):
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(a - w))) <= (
            2e-4 if mxu is None else 4e-2) * scale, i


def test_fast_decays_and_the_packed_entry():
    rng = np.random.default_rng(4)
    b, t, h, p, g, n = 2, T, 4, 8, 2, 16
    x, _, _, bm, cm, d = _ssd_inputs(rng, b, t, h, p, g, n, slow=False)
    raw, a_log, dt_bias = (jnp.asarray(_randn(rng, b, t, h)),
                           jnp.asarray(_randn(rng, h)),
                           jnp.asarray(_randn(rng, h)))
    got = mamba2.mamba2_ssd(
        x.reshape(b, t, h * p), bm.reshape(b, t, g * n),
        cm.reshape(b, t, g * n), raw, a_log, dt_bias, d, h, g, chunk=8)
    want = mamba2.recurrent_mamba2(
        x, jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log), bm, cm, d)
    np.testing.assert_allclose(got, want.reshape(b, t, h * p), rtol=2e-4,
                               atol=2e-4)


def test_mamba2_mixer_against_the_plain_reference():
    rng = np.random.default_rng(5)
    args = {"mamba_head_dim": 8, "ssm_state_size": 16,
            "layer_norm_epsilon": 1e-5}
    _layer_against_plain(
        lambda x: layers.mamba2_mixer(x, 4, 8, 2, 16, 4, 1e-5, 8, name="m"),
        lambda p, x: REFERENCE._mamba(
            EXACT, {"m." + k.split(".", 1)[1]: v for k, v in p.items()},
            "m", x, args),
        {"x": _randn(rng, 2, T, 24)})


def test_attention_without_positions_against_the_plain_reference():
    rng = np.random.default_rng(6)
    _layer_against_plain(
        lambda x: layers.causal_self_attention(x, 4, 2, 8, name="a"),
        lambda p, x: REFERENCE._attention(EXACT, p, "a", x,
                                          {"head_dim": 8}),
        {"x": _randn(rng, 2, T, 24)})


# -- routed experts: sigmoid scores, bias, scale, ReLU squared --------------

D, L, E, K, F = 24, 16, 8, 3, 20


def _relu2_weights(rng, held, width=L):
    return (jnp.asarray(0.4 * _randn(rng, held, F, width)),
            jnp.asarray(0.4 * _randn(rng, held, width, F)))


def _one_hot_routed(x, router, bias, up, down, lo, scale, router_x=None):
    """The plain form: sigmoid scores, the K largest of score + bias, the
    scores of the picks over their sum times ``scale``; every held expert
    on every token under a one-hot weight."""
    scores = jax.nn.sigmoid((x if router_x is None else router_x) @ router)
    _, picks = jax.lax.top_k(scores + bias, K)
    weights = jnp.take_along_axis(scores, picks, -1)
    weights = weights / jnp.sum(weights, -1, keepdims=True) * scale
    out = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        one_hot = jnp.sum(jnp.where(picks == lo + e, weights, 0.0), -1)
        y = jnp.square(jax.nn.relu(x @ up[e].T)) @ down[e].T
        out = out + one_hot[:, None] * y
    return out, picks


@pytest.mark.parametrize("way", ["blocks_xla", "grouped_rows"])
@pytest.mark.parametrize("lo,held", [(0, 8), (2, 4)])
def test_sigmoid_routed_relu2_experts_against_a_one_hot_form(lo, held, way,
                                                             monkeypatch):
    """Both ways to multiply the table: the ``jnp`` block loop, and the
    ``grouped_experts`` kernels in interpret mode."""
    from paddle_tpu.ops import grouped_experts
    from paddle_tpu.ops.gates import GateDecision

    monkeypatch.setattr(grouped_experts, "_INTERPRET", True)
    plan = GateDecision(way == "grouped_rows", way)
    rng = np.random.default_rng(7)
    x = jnp.asarray(_randn(rng, 37, L))
    router_x = jnp.asarray(_randn(rng, 37, D))
    router = jnp.asarray(_randn(rng, D, E))
    # a bias large enough to change the choice, which must not change the
    # weights of what is chosen
    bias = jnp.asarray(0.5 * _randn(rng, E))
    up, down = _relu2_weights(rng, held)
    want, picks = _one_hot_routed(x, router, bias, up, down, lo, 2.5,
                                  router_x)
    _, unbiased = _one_hot_routed(x, router, 0.0 * bias, up, down, lo, 2.5,
                                  router_x)
    assert (np.sort(picks, -1) != np.sort(unbiased, -1)).any()

    def ours(x, router_x, router, up, down):
        return moe.routed_experts(
            x, router, None, up, down, K, lo, block_rows=4, form="relu2",
            score="sigmoid", bias=bias, scale=2.5, router_x=router_x,
            plan=plan)

    got, counts = ours(x, router_x, router, up, down)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        counts, [(np.asarray(picks) == lo + e).sum() for e in range(held)])
    cot = _randn(rng, *want.shape)
    wanted = jax.grad(
        lambda x, rx, r, u, d: jnp.sum(_one_hot_routed(
            x, r, bias, u, d, lo, 2.5, rx)[0] * cot),
        argnums=(0, 1, 2, 3, 4))(x, router_x, router, up, down)
    gotten = jax.grad(lambda *a: jnp.sum(ours(*a)[0] * cot),
                      argnums=(0, 1, 2, 3, 4))(x, router_x, router, up, down)
    for a, w in zip(gotten, wanted):
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-5)


def test_the_selection_bias_gets_no_gradient_and_the_old_attributes_stay():
    rng = np.random.default_rng(8)
    x = jnp.asarray(_randn(rng, 19, D))
    router = jnp.asarray(_randn(rng, D, E))
    up, down = _relu2_weights(rng, E, D)
    grad = jax.grad(lambda b: jnp.sum(moe.routed_experts(
        x, router, None, up, down, K, form="relu2", score="sigmoid",
        bias=b)[0]))(jnp.zeros(E))
    assert not np.asarray(grad).any()
    # softmax over all, no bias, no scale: route_topk is what it was
    weights, picks = moe.route_topk(x, router, K)
    probs = jax.nn.softmax(x @ router, -1)
    top, at = jax.lax.top_k(probs, K)
    np.testing.assert_array_equal(picks, at)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    with pytest.raises(ValueError):
        moe.route_topk(x, router, K, score="tanh")


def test_latent_experts_layer_against_the_plain_reference():
    rng = np.random.default_rng(9)
    args = {"n_routed_experts": E, "num_experts_per_tok": K,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5,
            "experts_held": [2, 4]}
    _layer_against_plain(
        lambda x: layers.routed_experts(
            x, E, K, F, 12, (2, 4), score="sigmoid", selection_bias=True,
            scale=2.5, form="relu2", shared_gate=False, latent_size=L,
            name="m")[0],
        lambda p, x: REFERENCE._moe(EXACT, p, "m", x, args),
        {"x": _randn(rng, 2, T, D)}, perturb=0.1)


# -- the share ties to the model --------------------------------------------

def _columns(first, count):
    return slice(first, first + count)


def test_head_shares_of_a_mamba_layer_add_up_to_the_uncut_reference():
    """Four shares of 2 heads and 1 group each: the columns of in_proj
    [z | x B C | dt], the channels of the convolution, the per-head
    parameters, the gated norm's weight and the rows of out_proj that
    belong to them; the shares' outputs add up to the uncut layer's."""
    rng = np.random.default_rng(10)
    heads, hp, groups, n, d = 8, 4, 4, 8, 24
    inner, bc = heads * hp, groups * n
    args = {"mamba_head_dim": hp, "ssm_state_size": n,
            "layer_norm_epsilon": 1e-5}
    p = {"m.in_proj": 0.3 * _randn(rng, d, 2 * inner + 2 * bc + heads),
         "m.conv": 0.5 * _randn(rng, inner + 2 * bc, 4),
         "m.conv_bias": 0.1 * _randn(rng, inner + 2 * bc),
         "m.A_log": 0.3 * _randn(rng, heads) - 2.0,
         "m.dt_bias": _randn(rng, heads) - 2.0,
         "m.D": 1.0 + 0.1 * _randn(rng, heads),
         "m.norm.w": 1.0 + 0.1 * _randn(rng, inner),
         "m.out_proj": 0.3 * _randn(rng, inner, d)}
    p = {k: jnp.asarray(v) for k, v in p.items()}
    x = jnp.asarray(_randn(rng, 2, T, d))
    whole = REFERENCE._mamba(EXACT, p, "m", x, args)
    ways = 4
    h, g = heads // ways, groups // ways
    total = 0.0
    for share in range(ways):
        assert nemotron_h.held_sizes([share, ways], heads, groups, 8,
                                     2)[:2] == (h, g)
        zs = _columns(share * h * hp, h * hp)
        xs = _columns(inner + share * h * hp, h * hp)
        bs = _columns(2 * inner + share * g * n, g * n)
        cs = _columns(2 * inner + bc + share * g * n, g * n)
        dts = _columns(2 * inner + 2 * bc + share * h, h)
        conv = [_columns(share * h * hp, h * hp),
                _columns(inner + share * g * n, g * n),
                _columns(inner + bc + share * g * n, g * n)]
        held = {
            "m.in_proj": jnp.concatenate(
                [p["m.in_proj"][:, s] for s in (zs, xs, bs, cs, dts)], -1),
            "m.conv": jnp.concatenate([p["m.conv"][s] for s in conv]),
            "m.conv_bias": jnp.concatenate(
                [p["m.conv_bias"][s] for s in conv]),
            "m.norm.w": p["m.norm.w"][zs], "m.out_proj": p["m.out_proj"][zs]}
        for name in ("m.A_log", "m.dt_bias", "m.D"):
            held[name] = p[name][_columns(share * h, h)]
        total = total + REFERENCE._mamba(EXACT, held, "m", x, args)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_head_shares_of_an_attention_layer_add_up_to_the_uncut_reference():
    """Four shares of 2 query heads on the key/value head they read (each
    of the 2 key/value heads is held by two shares)."""
    rng = np.random.default_rng(11)
    h, hkv, hd, d, ways = 8, 2, 8, 24, 4
    p = {"a.q_proj": _randn(rng, d, h * hd), "a.k_proj": _randn(rng, d,
                                                                hkv * hd),
         "a.v_proj": _randn(rng, d, hkv * hd),
         "a.o_proj": 0.3 * _randn(rng, h * hd, d)}
    p = {k: jnp.asarray(0.3 * v) for k, v in p.items()}
    x = jnp.asarray(_randn(rng, 2, T, d))
    whole = REFERENCE._attention(EXACT, p, "a", x, {"head_dim": hd})
    q = h // ways
    total = 0.0
    for share in range(ways):
        assert nemotron_h.held_sizes([share, ways], ways, ways, h,
                                     hkv)[2:] == (q, 1)
        qs = _columns(share * q * hd, q * hd)
        kvs = _columns(share * q // (h // hkv) * hd, hd)
        held = {"a.q_proj": p["a.q_proj"][:, qs],
                "a.k_proj": p["a.k_proj"][:, kvs],
                "a.v_proj": p["a.v_proj"][:, kvs],
                "a.o_proj": p["a.o_proj"][qs]}
        total = total + REFERENCE._attention(EXACT, held, "a", x,
                                             {"head_dim": hd})
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_expert_and_unit_shares_of_an_expert_layer_add_up_to_the_uncut_one():
    """Four shares of 2 routed experts and, apart from them, three shares
    of 4 of the shared expert's 12 units; the router and the latent
    projections, which every chip computes alike, are whole in each. The
    program's own layer, given one share of each, is that share."""
    rng = np.random.default_rng(12)
    units = 12
    p = {"m.router": _randn(rng, D, E),
         "m.latent_down": 0.3 * _randn(rng, D, L),
         "m.latent_up": 0.3 * _randn(rng, L, D),
         "m.shared.up_proj": 0.3 * _randn(rng, D, units),
         "m.shared.down_proj": 0.3 * _randn(rng, units, D)}
    p = {k: jnp.asarray(v) for k, v in p.items()}
    p["m.experts.up"], p["m.experts.down"] = _relu2_weights(rng, E)
    args = {"n_routed_experts": E, "num_experts_per_tok": K,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    x = jnp.asarray(_randn(rng, 2, 21, D))
    whole = REFERENCE._moe(EXACT, p, "m", x, args)
    no_shared = dict(p, **{"m.shared.up_proj": p["m.shared.up_proj"][:, :0],
                           "m.shared.down_proj": p["m.shared.down_proj"][:0]})
    total, load = 0.0, []
    for lo in range(0, E, 2):
        part, counts = moe.routed_experts(
            x @ p["m.latent_down"], p["m.router"], None,
            p["m.experts.up"][lo:lo + 2], p["m.experts.down"][lo:lo + 2], K,
            lo, form="relu2", score="sigmoid", scale=2.5, router_x=x)
        total = total + part @ p["m.latent_up"]
        load.extend(int(c) for c in counts)
        held = dict(no_shared, **{n: p[n][lo:lo + 2] for n in (
            "m.experts.up", "m.experts.down")})
        np.testing.assert_allclose(
            part @ p["m.latent_up"],
            REFERENCE._moe(EXACT, held, "m", x,
                           dict(args, experts_held=[lo, 2])),
            rtol=1e-4, atol=1e-5)
    assert sum(load) == 2 * 21 * K          # every pick is some share's
    for first in range(0, units, 4):
        total = total + jnp.square(jax.nn.relu(
            x @ p["m.shared.up_proj"][:, first:first + 4])) \
            @ p["m.shared.down_proj"][first:first + 4]
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


# -- the whole tiny model ---------------------------------------------------

TINY = dict(seq_len=T, vocab_size=97, hidden_size=32,
            hybrid_override_pattern="ME*EM", layers_held=[1, 4],
            mamba_num_heads=8, mamba_head_dim=4, n_groups=4,
            ssm_state_size=8, conv_kernel=4, chunk_size=8,
            num_attention_heads=8, num_key_value_heads=2, head_dim=8,
            n_routed_experts=8, num_experts_per_tok=3,
            moe_intermediate_size=20, moe_latent_size=16,
            moe_shared_expert_intermediate_size=24, norm_topk_prob=True,
            routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
            heads_held=[1, 2], experts_held=[2, 4],
            shared_units_held=[12, 12], vocab_held=50)


def _tiny_step(amp, args=TINY):
    """One Adam step of the tiny model through the Executor. Returns (loss,
    {leaf: first gradient}, the reference's loss and gradients, scope,
    spec)."""
    rng = np.random.default_rng(13)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        spec = nemotron_h.nemotron_h(**args)
        opt = fluid.optimizer.Adam(1e-3)
        if amp:
            opt = fluid.amp.decorate(opt)
        opt.minimize(spec.loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()
             if p.trainable]
    params = {}
    for n in names:
        value = np.array(scope.get(n))
        if value.ndim == 1:     # norm weights, D, the conv's bias: off 1, 0
            value = value + 0.1 * _randn(rng, *value.shape)
            scope.set(n, jnp.asarray(value))
        params[n] = value
    batch = spec.sample_batch(2, np.random.RandomState(1))
    loss, = exe.run(main, feed=batch, fetch_list=[spec.loss], scope=scope)
    grads = {n: np.asarray(scope.get(n + "_moment1_0")) * 10.0
             for n in names}        # moment1 = (1 - beta1) * g
    ids = {n: jnp.asarray(v.astype(np.int32)) for n, v in batch.items()}
    want = jax.value_and_grad(
        lambda p: REFERENCE.loss(p, ids, args, EXACT))(params)
    return float(loss), grads, want, scope, spec


def test_tiny_model_equals_the_reference_in_float32():
    """Layers 1-4 of ``ME*EM`` (all three kinds), the second half of the
    heads, experts 2-5 and half of the shared expert's units."""
    loss, grads, (want_loss, want_grads), scope, spec = _tiny_step(False)
    assert abs(loss - float(want_loss)) < 2e-5 * abs(float(want_loss))
    # E: norm, router, latent down/up, experts up/down, shared up/down;
    # *: norm, q/k/v/o; M: norm, in_proj, conv + bias, A_log, dt_bias, D,
    # the gated norm, out_proj; embeddings, final norm, head. The
    # selection bias is no leaf: nothing trains it
    assert len(grads) == 2 * 8 + 5 + 9 + 3
    assert not [n for n in grads if "router_bias" in n]
    assert np.asarray(scope.get("l1.moe.router_bias")).shape == (8,)
    for name, got in grads.items():
        want = np.asarray(want_grads[name])
        assert got.shape == want.shape, name
        assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max() + 1e-7, \
            name
    assert np.asarray(scope.get("l4.mamba.in_proj")).shape == (
        32, 2 * 16 + 2 * 16 + 4)
    assert np.asarray(scope.get("l2.attn.k_proj")).shape == (32, 8)
    # each expert layer's counter holds what its held experts took
    assert spec.extras["expert_loads"] == ["l1.moe.load", "l3.moe.load"]
    for name in spec.extras["expert_loads"]:
        load = np.asarray(scope.get(name))
        assert load.shape == (4,) and load.dtype == np.int32
        assert 0 < load.sum() <= 2 * T * 3


def test_tiny_model_stays_near_the_reference_under_amp():
    """bfloat16 compute at a width of 32 is noisy and flips a pick here and
    there; the numbers the benchmark compares stay near the float32
    reference's, and every leaf has a finite, non-zero gradient. All
    experts picked, so that no pick flips."""
    args = dict(TINY, num_experts_per_tok=8)
    loss, grads, (want_loss, want_grads), _, _ = _tiny_step(True, args)
    assert abs(loss - float(want_loss)) < 0.02 * abs(float(want_loss))
    gaps = []
    for name, got in grads.items():
        assert np.isfinite(got).all() and np.abs(got).max() > 0, name
        want = np.linalg.norm(np.asarray(want_grads[name]))
        gaps.append(abs(np.linalg.norm(got) - want) / want)
    assert np.median(gaps) < 0.05 and max(gaps) < 0.5, sorted(gaps)[-5:]


def test_compile_record_shapes_and_costs_of_the_new_sites():
    from paddle_tpu.analysis import cost, passes

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        spec = nemotron_h.nemotron_h(**TINY)
        fluid.optimizer.SGD(0.1).minimize(spec.loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=spec.sample_batch(1, np.random.RandomState(0)),
            fetch_list=[spec.loss], scope=scope)
    gates = [r["gates"] for r in exe.compile_records if r.get("gates")][-1]
    assert any("chunked_jnp" in line for line in gates["mamba2_ssd"])
    assert any("blocks_xla" in line
               for line in gates["routed_experts"])
    assert "flash_attention" in gates

    plain, plain_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(plain, plain_startup), \
            fluid.unique_name.guard():
        nemotron_h.nemotron_h(**TINY)
    estimate = cost.estimate_program(plain, batch=2)
    assert not [u for u in estimate.uncosted
                if u in ("rms_norm", "causal_conv1d", "mamba2_ssd",
                         "routed_experts")]
    by_type = {}
    for row in estimate.records:
        by_type[row.op.type] = by_type.get(row.op.type, 0) + row.flops
    assert by_type["mamba2_ssd"] > 0 and by_type["routed_experts"] > 0
    assert not [r for r in estimate.records if r.unresolved]

    bad, bad_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(bad, bad_startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[T, 24])
        helper = fluid.core.layer_helper.LayerHelper("bad")
        out = helper.create_variable_for_type_inference("float32", x.shape)
        helper.append_op(
            "mamba2_ssd", {"X": x, "Bm": x, "Cm": x, "Dt": x}, {"Out": out},
            {"num_heads": 5, "num_groups": 1})   # 24 is no multiple of 5
    errors = passes.analyze_program(bad, checks={"shape"}).errors
    assert errors and "mamba2_ssd" in str(errors[0])
    with pytest.raises(ValueError):
        nemotron_h.held_sizes([0, 3], 8, 4, 8, 2)   # 8 heads, 3 ways
    with pytest.raises(ValueError):
        nemotron_h.nemotron_h(**dict(TINY, layers_held=[3, 4]))
