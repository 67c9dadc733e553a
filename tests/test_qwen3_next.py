"""The hybrid linear-attention / routed-experts block (``models/qwen3_next``)
at small sizes on the CPU: each new op against a plain ``jnp`` function,
forward and gradients; the chunked delta rule against the token-by-token
recurrence under slow decays; routed experts against a dense loop, under a
routing that sends everything to one expert, and the shares of a layer
adding up to the whole; grouped-query and segmented attention; the whole
tiny model through the Executor against the benchmark's plain reference.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.backward import calc_gradient
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import gated_delta
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

REFERENCE = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "qwen3-next-80b-a3b.py"))
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

T = 3 * 8 + 5       # three chunks of 8 and a tail that is no whole chunk


def _randn(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def _layer_against_plain(build, plain, feeds, seed=0, perturb=0.3):
    """Run ``build(**feed vars)`` through the Executor with its gradients
    (w.r.t. every feed and every parameter, under a random cotangent) and
    compare with ``plain(params, **feeds)`` and its ``jax.grad``."""
    rng = np.random.default_rng(seed)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feed_vars = {n: layers.data(n, shape=list(v.shape),
                                    append_batch_size=False,
                                    stop_gradient=False)
                     for n, v in feeds.items()}
        out = build(**feed_vars)
        cot = layers.data("cot", shape=list(out.shape),
                          append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(out, cot))
        params = [p for p in main.global_block().all_parameters()
                  if p.trainable]
        wrt = list(feed_vars.values()) + params
        grads = calc_gradient(loss, wrt)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    values = {}
    for p in params:
        value = np.array(scope.get(p.name))
        value = value + perturb * _randn(rng, *value.shape)
        values[p.name] = value
        scope.set(p.name, jnp.asarray(value))
    cot_value = _randn(rng, *[int(d) for d in out.shape])
    got = exe.run(main, feed=dict(feeds, cot=cot_value),
                  fetch_list=[out] + grads, scope=scope)

    def total(values, feeds):
        return jnp.sum(plain(values, **feeds) * cot_value)

    want_out = plain(values, **feeds)
    want = jax.grad(total, argnums=(0, 1))(
        values, {n: jnp.asarray(v) for n, v in feeds.items()})
    np.testing.assert_allclose(got[0], want_out, rtol=2e-4, atol=2e-5)
    wanted = [want[1][n] for n in feeds] + [want[0][p.name] for p in params]
    for var, g, w in zip(wrt, got[1:], wanted):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=var.name)


W = fluid.ParamAttr(name="w")


def _rms(x, w, eps=1e-6, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


@pytest.mark.parametrize("kind", ["zero_centered", "per_head", "gated"])
def test_rms_norm_against_plain(kind):
    rng = np.random.default_rng(1)
    x = _randn(rng, 2, T, 32)
    if kind == "zero_centered":
        _layer_against_plain(
            lambda x: layers.rms_norm(x, zero_centered=True, param_attr=W),
            lambda p, x: _rms(x, p["w"]), {"x": x})
    elif kind == "per_head":
        _layer_against_plain(
            lambda x: layers.rms_norm(x, zero_centered=True, norm_dim=8,
                                      param_attr=W),
            lambda p, x: _rms(x.reshape(2, T, 4, 8),
                              p["w"]).reshape(2, T, 32),
            {"x": x})
    else:
        _layer_against_plain(
            lambda x, z: layers.rms_norm(x, norm_dim=8, gate=z, param_attr=W),
            lambda p, x, z: _rms(x.reshape(2, T, 4, 8), p["w"],
                                 zero_centered=False).reshape(2, T, 32)
            * jax.nn.silu(z), {"x": x, "z": _randn(rng, 2, T, 32)})


def test_partial_rotary_against_plain():
    rng = np.random.default_rng(2)

    def plain(p, x):
        xh = x.reshape(2, T, 4, 16)
        return REFERENCE._rotary(xh, 4, 1e4).reshape(2, T, 64)

    _layer_against_plain(lambda x: layers.rotary(x, 4, 4, 1e4), plain,
                         {"x": _randn(rng, 2, T, 64)})
    # the pairing is (j, j + rot/2) and position 0 is the identity
    x = _randn(rng, 1, 2, 1, 8)
    out = np.asarray(REFERENCE._rotary(jnp.asarray(x), 4, 1e4))
    np.testing.assert_allclose(out[0, 0], x[0, 0], rtol=1e-6)
    c, s = np.cos(1.0), np.sin(1.0)
    np.testing.assert_allclose(out[0, 1, 0, 0],
                               x[0, 1, 0, 0] * c - x[0, 1, 0, 2] * s,
                               rtol=1e-5)
    np.testing.assert_allclose(out[0, 1, 0, 4:], x[0, 1, 0, 4:])


def test_causal_conv_against_plain():
    rng = np.random.default_rng(3)

    def plain(p, x):
        w = p["w"]
        out = jnp.zeros_like(x)
        for t in range(x.shape[1]):
            acc = 0.0
            for j in range(4):
                src = t - 3 + j
                if src >= 0:
                    acc = acc + x[:, src] * w[:, j]
            out = out.at[:, t].set(acc)
        return jax.nn.silu(out)

    _layer_against_plain(
        lambda x: layers.causal_conv1d(x, 4, "silu", param_attr=W), plain,
                         {"x": _randn(rng, 2, 9, 6)})


ATTN = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            partial_rotary_factor=0.25, rope_theta=1e4, rms_norm_eps=1e-6)


def test_gated_gqa_attention_against_plain():
    rng = np.random.default_rng(4)
    _layer_against_plain(
        lambda x: layers.causal_self_attention(
            x, 4, 2, 16, 4, 1e4, qk_norm=True, output_gate=True, name="a"),
        lambda p, x: REFERENCE._attention(EXACT, p, "a", x, ATTN),
        {"x": _randn(rng, 2, T, 64)}, perturb=0.1)


GDN = dict(linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=8,
           rms_norm_eps=1e-6)


def test_gated_delta_net_against_plain_recurrence():
    rng = np.random.default_rng(5)
    _layer_against_plain(
        lambda x: layers.gated_delta_net(x, 2, 4, 8, 8, chunk=8, name="g"),
        lambda p, x: REFERENCE._delta_net(EXACT, p, "g", x, GDN),
        {"x": _randn(rng, 2, T, 64)}, perturb=0.1)


def _delta_rule(form, chunk, group, mxu, rep):
    """q, k [B, T, Hk, Dk], v [B, T, Hv, Dv], g, beta [B, T, Hv] -> out, by
    the token-by-token recurrence, the chunked ``jnp`` form (both on key
    heads repeated to the value heads) or the ``gated_delta`` kernels."""
    def repeated(fn):
        return lambda q, k, *rest: fn(jnp.repeat(q, rep, 2),
                                      jnp.repeat(k, rep, 2), *rest)

    if form == "recurrence":
        return repeated(gated_delta.recurrent_gated_delta_rule)
    if form == "jnp":
        return repeated(lambda *a: gated_delta.chunk_gated_delta_rule(
            *a, chunk=chunk, group=group, mxu_dtype=mxu))
    return lambda *a: gated_delta.kernel_gated_delta_rule(
        *a, chunk=chunk, mxu_dtype=mxu)


@pytest.mark.parametrize("form,t,chunk,group,heads,mxu,repeat_keys", [
    ("jnp", 24, 8, 16, (3, 3), None, False),
    ("jnp", T, 8, 2, (3, 3), None, False),
    ("jnp", T, 8, 1, (3, 3), None, False),
    ("jnp", 7, 8, 16, (3, 3), None, False),
    # the Pallas kernels, interpret mode: a block of four chunks and a tail
    # that is no whole chunk; less than one chunk; two groups of key heads;
    # chunks of 64 (the inverse's merges) over three blocks; bfloat16
    # operands; and keys that repeat inside a chunk, where a power of A
    # would grow and the substitution does not
    ("kernel", T, 8, 16, (3, 3), None, False),
    ("kernel", 7, 8, 16, (3, 3), None, False),
    ("kernel", 2 * T, 8, 16, (2, 4), None, False),
    ("kernel", 150, 32, 16, (1, 2), None, False),
    ("kernel", 300, 64, 16, (1, 1), None, False),
    ("kernel", 2 * T, 8, 16, (2, 4), jnp.bfloat16, False),
    ("kernel", 150, 64, 16, (1, 2), None, True),
    ("jnp", 150, 64, 16, (1, 2), None, True),
])
def test_chunked_delta_rule_equals_the_recurrence_under_slow_decays(
        form, t, chunk, group, heads, mxu, repeat_keys, monkeypatch):
    """Decays near 0 (a state that lives for hundreds of tokens), so that
    the last chunk's outputs still depend on the first chunk's writes: an
    error in the state carried from chunk to chunk, from one recomputed
    group of chunks to the next, or from one grid step of the kernels to
    the next, cannot hide. Outputs and all five input gradients, against
    the recurrence and, for the kernels, against the chunked ``jnp`` form
    as well (with bfloat16 operands against that alone: the two round at
    the same places)."""
    monkeypatch.setattr(gated_delta, "_INTERPRET", True)
    rng = np.random.default_rng(6)
    hk, hv = heads
    b, dk, dv = 2, 16, 8
    q, k = _randn(rng, b, t, hk, dk), _randn(rng, b, t, hk, dk)
    if repeat_keys:     # every key three times in a row
        k = np.repeat(k[:, ::3], 3, axis=1)[:, :t]
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = _randn(rng, b, t, hv, dv)
    g = -0.01 * np.abs(_randn(rng, b, t, hv))
    beta = rng.uniform(0.2, 1.0, (b, t, hv)).astype(np.float32)
    args = (q, k, v, g, beta)
    ours = _delta_rule(form, chunk, group, mxu, hv // hk)
    recurrence = _delta_rule("recurrence", chunk, group, None, hv // hk)
    want = recurrence(*args)
    cot = _randn(rng, *want.shape)
    # the first chunk's values reach the last token
    reach = jax.grad(lambda v: jnp.sum(recurrence(q, k, v, g, beta)[:, -1]))(
        v)
    assert float(jnp.abs(reach[:, 0]).max()) > (1e-3 if t < 300 else 1e-6)

    def with_grads(fn):
        return (fn(*args),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2, 3, 4))(*args)

    gotten = with_grads(ours)
    against = [(with_grads(recurrence), 1e-4, 1e-5, 2e-3, 2e-4)]
    if form == "kernel":
        chunked = with_grads(_delta_rule("jnp", chunk, group, mxu, hv // hk))
        against.append((chunked, 1e-4, 1e-5, 2e-3, 2e-4))
        if mxu is not None:     # bfloat16: only the form that rounds alike
            against = [(chunked, 2e-2, 2e-2, 2e-2, 5e-2)]
    for wanted, rtol, atol, grad_rtol, grad_atol in against:
        np.testing.assert_allclose(gotten[0], wanted[0], rtol=rtol,
                                   atol=atol)
        for name, a, w in zip("q k v g beta".split(), gotten[1:],
                              wanted[1:]):
            np.testing.assert_allclose(a, w, rtol=grad_rtol, atol=grad_atol,
                                       err_msg=name)


@pytest.mark.parametrize("mxu", [None, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gated_delta_kernels_equal_the_chunked_form_on_packed_heads(
        mxu, monkeypatch):
    """The op's entry (packed heads, raw q and k that the kernels
    L2-normalise themselves, gates made round them) through the
    ``gated_delta`` kernels against the chunked ``jnp`` form: the output and
    the gradients of all seven inputs, two value heads a key head, a block
    and a tail."""
    from paddle_tpu.ops.gates import GateDecision

    monkeypatch.setattr(gated_delta, "_INTERPRET", True)
    rng = np.random.default_rng(3)
    b, t = 2, 45
    args = tuple(jnp.asarray(x) for x in (
        _randn(rng, b, t, 32), _randn(rng, b, t, 32), _randn(rng, b, t, 32),
        _randn(rng, b, t, 4), _randn(rng, b, t, 4),
        0.3 * _randn(rng, 4) - 2.0, 0.3 * _randn(rng, 4) - 3.0))
    cot = _randn(rng, b, t, 32)

    def with_grads(admitted):
        def fn(*a):
            return gated_delta.gated_delta_attention(
                *a, 2, 4, 8, mxu, plan=GateDecision(admitted, "forced"))
        return (fn(*args),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=tuple(range(7)))(*args)

    tol = dict(rtol=2e-4, atol=2e-6) if mxu is None else \
        dict(rtol=2e-2, atol=1e-2)
    for name, got, want in zip("out q k v a b a_log dt_bias".split(),
                               with_grads(True), with_grads(False)):
        np.testing.assert_allclose(got, want, err_msg=name, **tol)


# -- routed experts ---------------------------------------------------------

E, K, F, D = 8, 3, 12, 16


def _expert_weights(rng, held):
    return tuple(jnp.asarray(0.3 * _randn(rng, *s))
                 for s in ((held, F, D), (held, F, D), (held, D, F)))


def _dense_routed(x, router, wg, wu, wd, lo):
    probs = jax.nn.softmax(x @ router, -1)
    weights, picks = jax.lax.top_k(probs, K)
    weights = weights / weights.sum(-1, keepdims=True)
    out = 0
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(picks == e + lo, weights, 0), -1)
        y = (jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T)) @ wd[e].T
        out = out + w[:, None] * y
    return out


def _grads_match(ours, dense, args, cot, rtol=2e-3, atol=2e-5):
    wanted = jax.grad(lambda *a: jnp.sum(dense(*a) * cot),
                      argnums=tuple(range(len(args))))(*args)
    gotten = jax.grad(lambda *a: jnp.sum(ours(*a)[0] * cot),
                      argnums=tuple(range(len(args))))(*args)
    for a, w in zip(gotten, wanted):
        np.testing.assert_allclose(a, w, rtol=rtol, atol=atol)


@pytest.mark.parametrize("way", ["blocks_xla", "grouped_rows"])
@pytest.mark.parametrize("rows", [4, 8], ids=["rows4", "rows8"])
@pytest.mark.parametrize("lo,held", [(0, 8), (2, 4), (6, 2)])
def test_routed_experts_against_a_dense_loop(lo, held, rows, way,
                                             monkeypatch):
    """Against the dense loop, forward and gradients, both ways to multiply
    the one table (the ``jnp`` block loop; the ``grouped_experts`` kernels
    in interpret mode), an expert's 14 or so rows in blocks of 4 and of
    8."""
    from paddle_tpu.ops import grouped_experts
    from paddle_tpu.ops.gates import GateDecision

    monkeypatch.setattr(grouped_experts, "_INTERPRET", True)
    plan = GateDecision(way == "grouped_rows", way)
    rng = np.random.default_rng(7)
    x = jnp.asarray(_randn(rng, 37, D))
    router = jnp.asarray(_randn(rng, D, E))
    wg, wu, wd = _expert_weights(rng, held)
    want = _dense_routed(x, router, wg, wu, wd, lo)

    def ours(*a):
        return moe.routed_experts(*a, K, lo, block_rows=rows, plan=plan)

    got, counts = ours(x, router, wg, wu, wd)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    _, picks = moe.route_topk(x, router, K)
    np.testing.assert_array_equal(
        counts, [(np.asarray(picks) == lo + e).sum() for e in range(held)])
    _grads_match(ours, lambda *a: _dense_routed(*a, lo),
                 (x, router, wg, wu, wd), _randn(rng, *want.shape))


def _sent_to(tokens, held_picks, experts, rng):
    """x > 0 [tokens, D] and a router over ``experts`` that sends every
    token's first picks to ``held_picks`` (in that order of preference)."""
    x = jnp.asarray(np.abs(_randn(rng, tokens, D)) + 0.1)
    router = np.asarray(0.01 * _randn(rng, D, experts))
    for rank, e in enumerate(held_picks):
        router[:, e] += 1.0 - 0.1 * rank
    return x, jnp.asarray(router)


# load patterns at the edges of the layout; (experts, first held, held, the experts
# every token picks first, tokens, rows of a block)
_LOADS = {
    # held expert 0 of this share takes nothing at all
    "an_expert_with_no_token": (8, 2, 2, (3, 5, 6), 50, 4),
    # one held expert takes every token, the other its leftovers
    "every_token_on_one_expert": (8, 2, 2, (3,), 50, 4),
    # 48 tokens in blocks of 8: six whole blocks, no padding ...
    "an_exact_multiple_of_the_block": (8, 2, 2, (3,), 48, 8),
    # ... and 49: one row in a seventh
    "one_more_than_a_multiple": (8, 2, 2, (3,), 49, 8),
    # 3 x 50 rows on three of four held experts of 32: four times the mean
    # load is 75 rows, so the kernels' calls (whole experts, at most 96
    # rows) are three
    "past_one_calls_capacity": (32, 4, 4, (4, 6, 7), 50, 4),
}


@pytest.mark.parametrize("way", ["blocks_xla", "grouped_rows"])
@pytest.mark.parametrize("load", sorted(_LOADS))
def test_load_patterns_drop_nothing_either_way(load, way, monkeypatch):
    from paddle_tpu.ops import grouped_experts
    from paddle_tpu.ops.gates import GateDecision

    monkeypatch.setattr(grouped_experts, "_INTERPRET", True)
    experts, lo, held, picked, tokens, rows = _LOADS[load]
    rng = np.random.default_rng(11)
    x, router = _sent_to(tokens, picked, experts, rng)
    wg, wu, wd = _expert_weights(rng, held)
    plan = GateDecision(way == "grouped_rows", way)

    def ours(*a):
        return moe.routed_experts(*a, K, lo, block_rows=rows, plan=plan)

    got, counts = jax.jit(ours)(x, router, wg, wu, wd)
    want = _dense_routed(x, router, wg, wu, wd, lo)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for e in picked:
        if lo <= e < lo + held:
            assert int(counts[e - lo]) == tokens
    _grads_match(ours, lambda *a: _dense_routed(*a, lo),
                 (x, router, wg, wu, wd), _randn(rng, *want.shape))
    table = moe.bin_assignments(
        moe.route_topk(x, router, K)[1], lo, held, rows,
        moe.call_rows_for(tokens * K, experts, held, tokens, rows))
    np.testing.assert_array_equal(table.counts, counts)
    if load == "an_expert_with_no_token":
        assert int(counts[0]) == 0
    if load == "past_one_calls_capacity":
        assert int(table.calls) == 3
    else:
        assert int(table.calls) == 1
    if load == "an_exact_multiple_of_the_block":
        assert int(moe.table_rows(counts, rows)[1]) - int(counts[0]) == 48
    if load == "one_more_than_a_multiple":
        assert int(moe.table_rows(counts, rows)[1]) \
            - -(-int(counts[0]) // rows) * rows == 56


def test_kernels_tile_the_experts_width_and_the_columns_under_a_small_budget(
        monkeypatch):
    """Where the matrices do not fit VMEM the kernels walk ``F`` in tiles
    (the backward here in two, the forward whole) and ``combine`` the
    columns of the sum in tiles (two): the same results as the block
    loop, forward and every gradient."""
    from paddle_tpu.ops import grouped_experts
    from paddle_tpu.ops.gates import GateDecision

    monkeypatch.setattr(grouped_experts, "_INTERPRET", True)
    d, f, held, rows = 256, 256, 4, 8
    rng = np.random.default_rng(12)
    x = jnp.asarray(_randn(rng, 37, d))
    router = jnp.asarray(_randn(rng, d, E))
    mats = tuple(jnp.asarray(0.1 * _randn(rng, *s))
                 for s in ((held, f, d), (held, f, d), (held, d, f)))
    cot = _randn(rng, 37, d)

    def run(way):
        def ours(*a):
            return moe.routed_experts(*a, K, 2, block_rows=rows,
                                      plan=GateDecision(way, "forced"))
        return (ours(x, router, *mats)[0],) + jax.grad(
            lambda *a: jnp.sum(ours(*a)[0] * cot),
            argnums=(0, 1, 2, 3, 4))(x, router, *mats)

    want = run(False)
    monkeypatch.setattr(grouped_experts, "_VMEM_BUDGET", 2 * 1024 * 1024)
    assert grouped_experts.f_tile("fwd", 3, d, f, rows, 4) == f
    assert grouped_experts.f_tile("bwd", 3, d, f, rows, 4) == f // 2
    tiled_f = run(True)
    monkeypatch.setattr(grouped_experts, "_VMEM_BUDGET", 64 * 1024)
    assert grouped_experts.column_tile(37, d, 1, rows) == d // 2
    sums = jnp.asarray(_randn(rng, 37, d))
    parts = jnp.asarray(_randn(rng, 1, 4 * rows, d))
    tokens = jnp.asarray(rng.integers(0, 38, 4 * rows), jnp.int32)  # 37: empty
    np.testing.assert_allclose(
        grouped_experts.combine(tokens, jnp.int32(3), parts, sums, rows),
        sums.at[tokens[:3 * rows]].add(parts[0, :3 * rows], mode="drop"),
        rtol=1e-6, atol=1e-6)
    for a, w in zip(tiled_f, want):
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-5)


def test_every_token_to_one_held_expert_drops_nothing():
    """The worst case for a capacity: every token's first pick is the same
    held expert. Its counter reads every token, the result is the dense
    loop's, and the table holds every one of its rows, block after block,
    sized for every pick of every token being held."""
    rng = np.random.default_rng(8)
    tokens = 50
    x, router = _sent_to(tokens, (3,), E, rng)
    wg, wu, wd = _expert_weights(rng, 2)
    got, counts = jax.jit(lambda *a: moe.routed_experts(
        *a, K, 2))(x, router, wg, wu, wd)
    assert int(counts[1]) == tokens
    np.testing.assert_allclose(
        got, _dense_routed(x, router, wg, wu, wd, 2), rtol=1e-4, atol=1e-5)
    rows = moe.block_rows_for(tokens * K)
    call_rows = moe.call_rows_for(tokens * K, E, 2, tokens, rows)
    table = moe.bin_assignments(moe.route_topk(x, router, K)[1], 2, 2, rows,
                                call_rows)
    held_rows = np.asarray(table.row_assign) < tokens * K
    assert int(held_rows.sum()) == int(counts.sum())
    first = -(-int(counts[0]) // rows) * rows      # expert 3's first row
    assert held_rows[first:first + tokens].all()
    assert (np.asarray(table.block_expert)[
        first // rows:(first + tokens) // rows] == 1).all()
    assert int(table.blocks) * rows >= int(counts.sum())
    assert table.row_assign.shape[0] >= tokens * K
    assert int(moe.table_rows(counts, rows)[0]) == int(counts.sum())
    assert call_rows >= tokens          # one expert always fits one call


def test_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Section 4 of the model-configs guide: the routed parts that the four
    shares (2 experts each) compute, plus the shared expert counted once,
    are the uncut layer's reference."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(_randn(rng, 2, 21, D))
    p = {"m.router": jnp.asarray(_randn(rng, D, E)),
         "m.shared.gate_proj": jnp.asarray(0.3 * _randn(rng, D, F)),
         "m.shared.up_proj": jnp.asarray(0.3 * _randn(rng, D, F)),
         "m.shared.down_proj": jnp.asarray(0.3 * _randn(rng, F, D)),
         "m.shared_gate": jnp.asarray(_randn(rng, D, 1))}
    (p["m.experts.gate"], p["m.experts.up"],
     p["m.experts.down"]) = _expert_weights(rng, E)
    args = {"num_experts": E, "num_experts_per_tok": K,
            "norm_topk_prob": True}
    whole = REFERENCE._moe(EXACT, p, "m", x, args)
    shared = jax.nn.sigmoid(x @ p["m.shared_gate"]) * REFERENCE._swiglu(
        EXACT, x, p["m.shared.gate_proj"], p["m.shared.up_proj"],
        p["m.shared.down_proj"])
    total, load = shared, []
    for lo in range(0, E, 2):
        part, counts = moe.routed_experts(
            x, p["m.router"], p["m.experts.gate"][lo:lo + 2],
            p["m.experts.up"][lo:lo + 2], p["m.experts.down"][lo:lo + 2],
            K, lo)
        total = total + part
        load.extend(int(c) for c in counts)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert sum(load) == 2 * 21 * K          # every pick is some share's
    # and the reference given one share is that share plus the shared part
    held = dict(p, **{n: p[n][2:4] for n in (
        "m.experts.gate", "m.experts.up", "m.experts.down")})
    one = REFERENCE._moe(EXACT, held, "m", x, dict(args,
                                                   experts_held=[2, 2]))
    part, _ = moe.routed_experts(
        x, p["m.router"], p["m.experts.gate"][2:4], p["m.experts.up"][2:4],
        p["m.experts.down"][2:4], K, 2)
    np.testing.assert_allclose(part + shared, one, rtol=1e-4, atol=1e-5)


def test_routed_experts_layer_counts_and_refuses_bad_shares():
    rng = np.random.default_rng(10)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[T, D])
        out, load = layers.routed_experts(x, E, K, F, F, range(2, 6),
                                          name="m")
        with pytest.raises(ValueError):
            layers.routed_experts(x, E, K, F, experts_held=(6, 4), name="n")
    assert load.persistable and tuple(load.shape) == (4,)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    xv = _randn(rng, 2, T, D)
    exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope)
    _, picks = moe.route_topk(jnp.asarray(xv.reshape(-1, D)),
                              scope.get("m.router"), K)
    np.testing.assert_array_equal(
        np.asarray(scope.get("m.load")),
        [(np.asarray(picks) == e).sum() for e in range(2, 6)])


# -- attention: grouped-query heads, the segmented path ---------------------

def test_grouped_query_heads_read_their_group():
    rng = np.random.default_rng(11)
    b, t, h, hkv, d = 2, 12, 4, 2, 8
    q, k, v = (_randn(rng, b, t, h * d), _randn(rng, b, t, hkv * d),
               _randn(rng, b, t, hkv * d))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        qv, kv, vv = (layers.data(n, shape=list(a.shape),
                                  append_batch_size=False)
                      for n, a in (("q", q), ("k", k), ("v", v)))
        helper = fluid.core.layer_helper.LayerHelper("gqa")
        out = helper.create_variable_for_type_inference("float32", q.shape)
        helper.append_op("flash_attention", {"Q": qv, "K": kv, "V": vv},
                         {"Out": out}, {"num_heads": h, "num_kv_heads": hkv,
                                        "causal": True})
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": q, "k": k, "v": v}, fetch_list=[out])

    def heads(x, n):
        return x.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    want = fa.mha_reference(
        heads(q, h), np.repeat(heads(k, hkv), h // hkv, axis=1),
        np.repeat(heads(v, hkv), h // hkv, axis=1), causal=True)
    np.testing.assert_allclose(
        got, np.asarray(want).transpose(0, 2, 1, 3).reshape(b, t, h * d),
        rtol=1e-4, atol=1e-5)


def test_kernel_plan_segments_a_wide_head_at_8192_and_leaves_the_rest():
    plan = fa.kernel_plan((1, 8192, 4096), (1, 8192, 4096), 16, 2,
                          causal=True)
    assert plan.kernel == "segmented_stream" and plan.admitted
    assert plan.blocked_only_by("vmem")
    assert fa._segment_plan(8192, 256, 2) == (2048, 256)
    # what the benchmark's transformer cells take: s256 is what it was;
    # PR 29 moved s2048 from head_split_stream to packed_stream (a lane
    # window of the packed head dimension fits where its whole width did
    # not), and that window at D=256, T=8192 is far from fitting
    assert not fa._packed_stream_fits(8192, 8192, 4096, 2, 16)
    assert fa.kernel_plan((128, 256, 512), (128, 256, 512), 8, 2,
                          causal=True).kernel == "dense_vmem"
    assert fa.kernel_plan((16, 2048, 512), (16, 2048, 512), 8, 2,
                          causal=True).kernel == "packed_stream"
    assert fa.kernel_plan((16, 2048, 512), (16, 2048, 512), 8, 2,
                          causal=False).kernel == "packed_stream"


def test_gated_delta_gate_admits_the_published_shape_and_says_why_not():
    """The cell's shape (T 8192, 16 key and 32 value heads of 128, chunk
    64) is admitted shape-only; a head of 64, another chunk and a placement
    that is no single TPU are refused, each with its reason."""
    plan = gated_delta.kernel_plan(8192, 16, 32, 128, 128, 64)
    assert plan.admitted and plan.kernel == "gated_delta"
    assert plan.describe() == "kernel gated_delta"
    narrow = gated_delta.kernel_plan(8192, 16, 32, 64, 128, 64)
    assert not narrow and narrow.kernel == "chunked_scan_xla"
    assert narrow.blocked_only_by("geometry") and "128" in narrow.describe()
    assert gated_delta.kernel_plan(8192, 16, 32, 128, 128,
                                   32).blocked_only_by("geometry")
    from paddle_tpu.ops.gates import placed, platform_reason

    for where in (dict(platform="tpu", meshed=True), dict(platform="cpu")):
        with placed(**where):
            off = gated_delta.kernel_plan(
                8192, 16, 32, 128, 128, 64, platform=platform_reason())
        assert off.kernel == "chunked_scan_xla"
        assert off.blocked_only_by("platform")
        assert ("mesh" in off.describe()) == bool(where.get("meshed"))
    with placed("tpu"):
        assert gated_delta.kernel_plan(
            8192, 16, 32, 128, 128, 64, platform=platform_reason()).admitted
    # the shape-only pass reads the same gate
    from paddle_tpu.analysis import resources

    for head, refused in ((128, False), (64, True)):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            layers.gated_delta_net(layers.data("x", shape=[8192, 256]), 16,
                                   32, head, head, name="g")
        finds = [d for d in resources.check_resources(main, batch=1).warnings
                 if d.check == "vmem-gate"]
        assert bool(finds) == refused
        assert all("gated_delta_rule" in d.message
                   and "chunked_scan_xla" in d.message for d in finds)


def test_segmented_stream_equals_the_reference(monkeypatch):
    """The head-split kernels (interpret mode) on segments of T, merged by
    logsumexp, forward and backward, under a VMEM budget made small enough
    that T = 96 does not fit whole."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_STREAM_VMEM_BUDGET", 150 * 1024)
    monkeypatch.setattr(fa, "_DENSE_MAX_Q", 0)
    b, t, h, d = 1, 96, 2, 16
    plan = fa.kernel_plan((b, t, h * d), (b, t, h * d), h, 4, causal=True)
    assert plan.kernel == "segmented_stream", plan
    segment, block = fa._segment_plan(t, d, 4)
    assert segment < t and t % segment == 0
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(_randn(rng, b, t, h * d)) for _ in range(3))
    cot = _randn(rng, b, t, h * d)

    def heads(x):
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3)

    def plain(q, k, v):
        out = fa.mha_reference(heads(q), heads(k), heads(v), causal=True)
        return out.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def ours(q, k, v):
        return fa.flash_attention(q, k, v, h, causal=True, plan=plan)

    np.testing.assert_allclose(ours(q, k, v), plain(q, k, v), rtol=2e-4,
                               atol=2e-5)
    wanted = jax.grad(lambda *a: jnp.sum(plain(*a) * cot),
                      argnums=(0, 1, 2))(q, k, v)
    gotten = jax.grad(lambda *a: jnp.sum(ours(*a) * cot),
                      argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(gotten, wanted):
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)


# -- the whole tiny model ---------------------------------------------------

TINY = dict(seq_len=T, vocab_size=97, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            partial_rotary_factor=0.25, rope_theta=1e4,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            linear_conv_kernel_dim=4, full_attention_interval=4,
            num_experts=8, num_experts_per_tok=3, norm_topk_prob=True,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            rms_norm_eps=1e-6, experts_held=[2, 4], vocab_held=50, chunk=8)


def _tiny_step(amp, args=TINY):
    """One Adam step of the tiny model through the Executor. Returns (loss,
    {leaf: first gradient}, the reference's loss and gradients, scope,
    spec)."""
    from paddle_tpu.models.qwen3_next import qwen3_next

    rng = np.random.default_rng(13)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        spec = qwen3_next(**args)
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
        if n.endswith(".w"):    # zero-centred weights start at exactly 0
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
    loss, grads, (want_loss, want_grads), scope, spec = _tiny_step(False)
    assert abs(loss - float(want_loss)) < 2e-5 * abs(float(want_loss))
    assert len(grads) == 4 * 10 + 3 * 7 + 6 + 3
    for name, got in grads.items():
        want = np.asarray(want_grads[name])
        assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max() + 1e-7, \
            name
    # each layer's counter holds what its held experts took
    for name in spec.extras["expert_loads"]:
        load = np.asarray(scope.get(name))
        assert load.shape == (4,) and load.dtype == np.int32
        assert 0 < load.sum() <= 2 * T * 3


def test_tiny_model_stays_near_the_reference_under_amp():
    """bfloat16 compute at a width of 64 is noisy and flips a pick here and
    there; the numbers the benchmark compares (loss, norms of the leaves'
    gradients) stay near the float32 reference's, and every leaf has a
    finite, non-zero gradient. All experts picked, so that no pick flips."""
    args = dict(TINY, num_experts_per_tok=8)
    loss, grads, (want_loss, want_grads), _, _ = _tiny_step(True, args)
    assert abs(loss - float(want_loss)) < 0.02 * abs(float(want_loss))
    gaps = []
    for name, got in grads.items():
        assert np.isfinite(got).all() and np.abs(got).max() > 0, name
        want = np.linalg.norm(np.asarray(want_grads[name]))
        gaps.append(abs(np.linalg.norm(got) - want) / want)
    assert np.median(gaps) < 0.05 and max(gaps) < 0.5, sorted(gaps)[-5:]


def test_compile_record_names_the_new_sites_decisions():
    from paddle_tpu.models.qwen3_next import qwen3_next

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        spec = qwen3_next(**TINY)
        fluid.optimizer.SGD(0.1).minimize(spec.loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=spec.sample_batch(1, np.random.RandomState(0)),
            fetch_list=[spec.loss], scope=scope)
    gates = [r["gates"] for r in exe.compile_records if r.get("gates")][-1]
    assert any("chunked_scan_xla" in line
               for line in gates["gated_delta_rule"])
    assert any("blocks_xla" in line
               for line in gates["routed_experts"])
    assert "flash_attention" in gates


def test_shape_rules_refuse_what_cannot_be_and_every_op_is_costed():
    from paddle_tpu.analysis import cost, passes
    from paddle_tpu.models.qwen3_next import qwen3_next

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        qwen3_next(**TINY)
    estimate = cost.estimate_program(main, batch=2)
    assert not [u for u in estimate.uncosted
                if u in ("rms_norm", "rotary", "causal_conv1d",
                         "gated_delta_rule", "routed_experts")]
    by_type = {}
    for row in estimate.records:
        by_type[row.op.type] = by_type.get(row.op.type, 0) + row.flops
    assert by_type["gated_delta_rule"] > 0 and by_type["routed_experts"] > 0
    assert not [r for r in estimate.records if r.unresolved]

    bad, bad_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(bad, bad_startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[T, 30])
        layers.rotary(x, 4, 4)               # 30 is no multiple of 4 heads
    errors = passes.analyze_program(bad, checks={"shape"}).errors
    assert errors and "rotary" in str(errors[0])
