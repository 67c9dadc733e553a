"""One trace of every Pallas kernel signature (``ops.kernel_names.traced_once``).

A step calls the same kernel at many sites. The function that builds and
binds it runs its Python once per (avals, statics) in a process; every
further site takes the cached equations. Counted here on the CPU (kernels
in interpret mode, or traced and never run) through ``collect_traces``,
for each family the benchmark's cells run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.flash_attention as fa
import paddle_tpu.ops.fused_conv as fc
from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

F32 = jnp.float32


@pytest.fixture(autouse=True)
def fresh_process_state():
    """Interpret mode, and JAX's trace caches emptied: "once per process"
    would otherwise count what an earlier test had traced."""
    fa._INTERPRET = fc._INTERPRET = True
    jax.clear_caches()
    yield
    fa._INTERPRET = fc._INTERPRET = False


# ---------------------------------------------------------------------------
# one site of each family: ``site(sig)`` is a scalar function of ``arrays(sig)``
# ---------------------------------------------------------------------------

_HEADS = 2


def _attention_arrays(sig, rng):
    b, t, hd = 2, sig.get("length", 16), 16
    if sig["family"] == "head_split_stream":  # [B*H, T, D]
        b, hd = b * _HEADS, hd // _HEADS
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, t, hd)), F32)
               for _ in range(3))
    bias = (jnp.asarray(rng.normal(0, 1, (b, t)), F32)
            if sig.get("bias") else None)
    return q, k, v, bias


def _attention_site(sig):
    causal = sig.get("causal", False)
    rate = sig.get("dropout_rate", 0.0)
    seed = jnp.uint32(7)

    def site(q, k, v, bias):
        if sig["family"] == "dense_vmem":
            out = fa._dense_attention(q, k, v, bias, seed, _HEADS, causal,
                                      0.25, rate)
        elif sig["family"] == "packed_stream":
            out = fa._packed_stream_attention(q, k, v, bias, seed, _HEADS,
                                              causal, 0.25, rate)
        else:
            out = fa._flash_attention(q, k, v, bias, seed, causal, 0.25,
                                      rate)
        return jnp.sum(out * out)

    return site


def _conv_arrays(sig, rng):
    n, c, o, hw, ksize = 2, 8, 16, sig.get("length", 8), sig.get("ksize", 1)
    x = jnp.asarray(rng.normal(0, 1, (n, c, hw, hw)), F32)
    w = jnp.asarray(rng.normal(0, 0.3, (o, c, ksize, ksize)), F32)
    gamma, beta = (jnp.asarray(rng.normal(1, 0.1, (o,)), F32)
                   for _ in range(2))
    res = (jnp.asarray(rng.normal(0, 1, (n, o, hw, hw)), F32)
           if sig.get("residual") else None)
    return x, w, gamma, beta, res


def _conv_site(sig):
    pad = (sig.get("ksize", 1) - 1) // 2

    def site(x, w, gamma, beta, res):
        zeros = jnp.zeros_like(gamma)
        y = fc.fused_conv_bn_act(
            x, w, gamma, beta, zeros, zeros + 1, strides=(1, 1),
            paddings=(pad, pad), eps=1e-5, momentum=0.9,
            act=sig.get("act", "relu"), residual=res)[0]
        return jnp.sum(y * y)

    return site


def _site(sig):
    return (_conv_site if sig["family"] == "fused_conv"
            else _attention_site)(sig)


def _arrays(sig, rng):
    return (_conv_arrays if sig["family"] == "fused_conv"
            else _attention_arrays)(sig, rng)


def _attention_family(family):
    both = (family + ".fwd", family + ".bwd")
    return both, [({"causal": True}, both), ({"bias": True}, both),
                  ({"length": 24}, both), ({"dropout_rate": 0.1}, both)]


# family -> (its kernels, [(what else of a signature a site can differ in,
# the kernels that then need another body)])
_FAMILIES = {
    "dense_vmem": _attention_family("dense_vmem"),
    "packed_stream": _attention_family("packed_stream"),
    "head_split_stream": _attention_family("head_split_stream"),
    # the fused conv's backward is XLA's; its kernels are the conv with its
    # moments and the apply pass, which does not see the filter
    "fused_conv": (("fused_conv.fwd", "fused_conv.apply"), [
        ({"act": None}, ("fused_conv.apply",)),
        ({"residual": True}, ("fused_conv.apply",)),
        ({"ksize": 3}, ("fused_conv.fwd",)),
        ({"length": 12}, ("fused_conv.fwd", "fused_conv.apply"))]),
}


def _traced(fn, args):
    """Trace ``fn`` as a step would be (nothing lowered, nothing run);
    returns the tally of kernel bodies."""
    with collect_traces() as bodies:
        jax.jit(fn).trace(*args)
    return tally_traces(bodies)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_sites_of_one_signature_trace_one_body_and_each_new_one_adds_one(
        family, rng):
    kernels, others = _FAMILIES[family]
    base = {"family": family}
    n = 3

    def tally_of(sigs):
        args = [_arrays(sig, rng) for sig in sigs]

        def step(*args):
            return sum(_site(sig)(*a) for sig, a in zip(sigs, args))

        return _traced(jax.grad(step, argnums=tuple(range(len(sigs)))), args)

    first = tally_of([base] * n)
    assert first == {k: {"traced": 1, "reused": n - 1} for k in kernels}

    # the same step again, in another jit: nothing is traced anew
    again = tally_of([base] * n)
    assert again == {k: {"traced": 0, "reused": n} for k in kernels}

    # two sites of every other signature beside the known one
    more = tally_of([base] + [dict(base, **other) for other, _ in others
                              for _ in range(2)])
    for k in kernels:
        bodies = sum(k in gain for _, gain in others)
        assert more[k] == {"traced": bodies,
                           "reused": 1 + 2 * len(others) - bodies}, (k, more)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_reused_body_gives_the_bits_of_a_freshly_traced_one(family, rng):
    fwd = _FAMILIES[family][0][0]
    sig = {"family": family, "bias": True, "residual": True}
    args = _arrays(sig, rng)
    diff = tuple(i for i, a in enumerate(args) if a is not None)

    def run():
        with collect_traces() as bodies:
            value, grads = jax.jit(jax.value_and_grad(
                _site(sig), argnums=diff))(*args)
        return tally_traces(bodies)[fwd], [np.asarray(value)] + [
            np.asarray(g) for g in grads]

    fresh_count, fresh = run()
    reused_count, reused = run()
    assert fresh_count == {"traced": 1, "reused": 0}
    assert reused_count == {"traced": 0, "reused": 1}
    jax.clear_caches()
    again_count, again = run()
    assert again_count == {"traced": 1, "reused": 0}
    for a, b, c in zip(fresh, reused, again):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_eqns(sub)
    return found


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_interpret_flag_is_part_of_the_signature(family, rng):
    """``_INTERPRET`` is flipped inside one process by this and four other
    test files: a body traced in one mode is not the other mode's."""
    fwd = _FAMILIES[family][0][0]
    sig = {"family": family}
    args = _arrays(sig, rng)
    seen = []
    for mode in (True, False, True):
        fa._INTERPRET = fc._INTERPRET = mode
        with collect_traces() as bodies:
            jaxpr = jax.make_jaxpr(_site(sig))(*args)
        seen.append(tally_traces(bodies)[fwd])
        calls = _pallas_eqns(jaxpr.jaxpr)
        assert calls and all(bool(e.params["interpret"]) is mode
                             for e in calls), (mode, calls)
    assert seen == [{"traced": 1, "reused": 0}, {"traced": 1, "reused": 0},
                    {"traced": 0, "reused": 1}]
