"""The step kernel behind ``eva_attention`` (``ops/eva_attention.py``:
``eva_step.fwd``, interpret mode here) against the ``jnp`` form that reads
both caches whole under the mask and against a float32 softmax, and the gate
that decides which of the two a site takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.executor import build_step_fn
from paddle_tpu.ops import cache_attention as ca
from paddle_tpu.ops import eva_attention as ea
from paddle_tpu.ops import gates
from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

F32, BF16 = jnp.float32, jnp.bfloat16
CHUNK = 16

# (heads, D, window, summary entries): EvaByte's ratios (a window of 128
# chunks, as many summary entries as window slots) cut down, and caches of
# different lengths either way (rungs of 32 and of 8 windows)
SHAPES = {
    "16x16_w256_l256": (16, 16, 256, 256),
    "16x32_w256_l512": (16, 32, 256, 512),
    "16x16_w512_l256": (16, 16, 512, 256),
}
BLOCK = 128     # what ``step_block`` cuts every one of them into


@pytest.fixture(autouse=True)
def interpreted():
    """Interpret mode, and JAX's trace caches emptied of what another
    mode traced."""
    ea._INTERPRET = True
    jax.clear_caches()
    yield
    ea._INTERPRET = False
    jax.clear_caches()


def _arrays(shape, rows, dtype=BF16, seed=0):
    heads, d, w, entries = SHAPES[shape]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (rows, heads * d)), dtype)
    caches = [jnp.asarray(rng.normal(0, 1, (rows, n, heads * d)), dtype)
              for n in (w, w, entries, entries)]
    return q, caches


def _sizes(shape):
    heads, _, w, _ = SHAPES[shape]
    return heads, w, CHUNK


def _kernel(shape, q, caches, pos):
    assert ea.plan_for(q, *caches, SHAPES[shape][0]).kernel == "eva_step"
    out, count = ea.step_blocks(q, *caches, jnp.asarray(pos, jnp.int32),
                                *_sizes(shape))
    return np.asarray(out.astype(F32)), [int(n) for n in count]


def _rung(shape, q, caches, pos):
    out, count = ea.attend_step(q, *caches, jnp.asarray(pos, jnp.int32),
                                *_sizes(shape))
    return np.asarray(out.astype(F32)), [int(n) for n in count]


def _dense(shape, q, caches, pos):
    """A float32 softmax a head over the window slots ``<= p % W`` and the
    summary entries ``< (p // W) * (W / C)`` together."""
    heads, d, w, entries = SHAPES[shape]
    rows = q.shape[0]
    qh = np.asarray(q.astype(F32)).reshape(rows, heads, d)
    wk, wv, sk, sv = (np.asarray(x.astype(F32)).reshape(rows, -1, heads, d)
                      for x in caches)
    pos = np.asarray(pos)
    member = np.concatenate(
        [np.arange(w)[None] <= (pos % w)[:, None],
         np.arange(entries)[None] < ((pos // w) * (w // CHUNK))[:, None]],
        axis=1)[:, None]
    x = np.einsum("bhd,bchd->bhc", qh, np.concatenate([wk, sk], 1)) \
        * d ** -0.5
    x = np.where(member, x, -np.inf)
    e = np.exp(x - x.max(-1, keepdims=True))
    return np.einsum("bhc,bchd->bhd", e / e.sum(-1, keepdims=True),
                     np.concatenate([wv, sv], 1)).reshape(rows, -1)


def _positions(where, shape):
    """Rows' positions; a rung holds ``entries * CHUNK`` of them."""
    _, _, w, entries = SHAPES[shape]
    last = entries * CHUNK - 1
    deep = (last // w) * w           # the rung's last window's first
    return {
        "first_window": [0, 5, BLOCK - 1, BLOCK, w - 1],
        "window_starts": [w, deep],                     # p % W == 0
        "window_ends": [w - 1, 2 * w - 1, last],        # p % W == W - 1
        "one_window_deep": [w + 3, w + BLOCK - 1, w + BLOCK, 2 * w - 2],
        "several_deep": [deep + 7, deep + BLOCK, last - 1],
        "a_row_fed_0_beside_long_rows": [last, 0, deep + w // 2, 0, w + 1],
    }[where]


@pytest.mark.parametrize("where", [
    "first_window", "window_starts", "window_ends", "one_window_deep",
    "several_deep", "a_row_fed_0_beside_long_rows"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_equals_the_whole_cache_form_and_a_float32_softmax(shape,
                                                                  where):
    pos = _positions(where, shape)
    q, caches = _arrays(shape, len(pos))
    out, count = _kernel(shape, q, caches, pos)
    rung, rung_count = _rung(shape, q, caches, pos)
    # bfloat16 outputs of sums in two orders: a few units in the last place
    np.testing.assert_allclose(out, rung, atol=0.03)
    np.testing.assert_allclose(out, _dense(shape, q, caches, pos), atol=0.03)
    _, _, w, _ = SHAPES[shape]
    assert count == rung_count == [
        sum(p % w + 1 for p in pos),
        sum((p // w) * (w // CHUNK) for p in pos), sum(p + 1 for p in pos)]


@pytest.mark.parametrize("shape", list(SHAPES))
def test_float32_caches_agree_with_the_softmax_closely(shape):
    pos = _positions("a_row_fed_0_beside_long_rows", shape) \
        + _positions("one_window_deep", shape)
    q, caches = _arrays(shape, len(pos), F32)
    out, count = _kernel(shape, q, caches, pos)
    np.testing.assert_allclose(out, _dense(shape, q, caches, pos),
                               atol=2e-5, rtol=2e-5)
    assert count == _rung(shape, q, caches, pos)[1]


@pytest.mark.parametrize("shape", list(SHAPES))
def test_garbage_a_row_does_not_hold_changes_no_output_bit(shape):
    """Large finite values in the window slots above ``p % W`` (the window
    before) and in the summary entries at and past ``(p // W) * (W / C)``,
    in the blocks the kernel fetches and in those it does not."""
    _, _, w, entries = SHAPES[shape]
    pos = _positions("first_window", shape) \
        + _positions("one_window_deep", shape) \
        + _positions("several_deep", shape)
    q, (wk, wv, sk, sv) = _arrays(shape, len(pos))
    at = np.asarray(pos)[:, None]
    stale = (np.arange(w)[None] > at % w)[:, :, None]
    unread = (np.arange(entries)[None]
              >= (at // w) * (w // CHUNK))[:, :, None]

    def with_(value_k, value_v):
        return [jnp.where(stale, value_k, wk).astype(BF16),
                jnp.where(stale, value_v, wv).astype(BF16),
                jnp.where(unread, value_k, sk).astype(BF16),
                jnp.where(unread, value_v, sv).astype(BF16)]

    clean = _kernel(shape, q, with_(0, 0), pos)
    dirty = _kernel(shape, q, with_(1e30, -3e37), pos)
    assert np.array_equal(clean[0], dirty[0]) and clean[1] == dirty[1]


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_row_alone_and_among_fifteen_others_agree_bit_for_bit(shape):
    _, _, w, entries = SHAPES[shape]
    pos = [int(p) for p in np.random.default_rng(5).integers(
        0, entries * CHUNK, 16)]
    pos[3], pos[4] = w + BLOCK + 5, 0
    q, caches = _arrays(shape, 16, seed=3)
    batched, _ = _kernel(shape, q, caches, pos)
    alone, count = _kernel(shape, q[3:4], [x[3:4] for x in caches], pos[3:4])
    assert np.array_equal(batched[3], alone[0])
    assert count == [BLOCK + 6, w // CHUNK, w + BLOCK + 6]


def test_a_position_past_the_rung_or_below_it_reads_inside_the_caches():
    """What a retired slot may be fed: the copies stay inside both caches,
    and the results and the counts are the whole-cache form's."""
    shape = "16x16_w256_l256"
    _, _, w, entries = SHAPES[shape]
    q, caches = _arrays(shape, 3)
    pos = [entries * CHUNK + 40, -1, entries * CHUNK + w]
    out, count = _kernel(shape, q, caches, pos)
    rung, rung_count = _rung(shape, q, caches, pos)
    np.testing.assert_allclose(out, rung, atol=0.03)
    assert count == rung_count


# ---------------------------------------------------------------------------
# which sites take it
# ---------------------------------------------------------------------------

# (rows, window, summary entries, heads, D) of the cell's step ops
EVABYTE = (16, 2048, 2048, 32, 128)


def _sites(shape, n=1, dtype="bfloat16", cache_dtype=None):
    """A program of ``n`` ``eva_attention`` ops of one signature, and the
    abstract feeds it is traced with."""
    b, w, entries, heads, d = shape
    cache_dtype = cache_dtype or dtype
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = layers.data("q", shape=[heads * d], dtype=dtype)
        caches = [layers.data(name, shape=[length, heads * d],
                              dtype=cache_dtype)
                  for name, length in (("wk", w), ("wv", w), ("sk", entries),
                                       ("sv", entries))]
        pos = layers.data("pos", shape=[], dtype="int32")
        outs = [layers.eva_attention(q, *caches, pos, heads, w, CHUNK)
                for _ in range(n)]
    feed = {"q": jax.ShapeDtypeStruct((b, heads * d), jnp.dtype(dtype)),
            "pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
    for name, length in (("wk", w), ("wv", w), ("sk", entries),
                         ("sv", entries)):
        feed[name] = jax.ShapeDtypeStruct((b, length, heads * d),
                                          jnp.dtype(cache_dtype))
    return main, [o[0].name for o in outs], feed


def _trace(main, fetch, feed, placement):
    """Trace the program's step as an Executor placed so would (nothing
    lowered, nothing run): the ops' recorded choices, the gate tally, the
    kernel bodies traced and the step's jaxpr."""
    step = build_step_fn(main, fetch, [], infer_only=True)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with gates.placed(*placement), gates.collect() as met, \
            collect_traces() as bodies:
        traced = jax.jit(step).trace({}, feed, rng)
    choices = [op.attrs["_kernel_choice"]
               for op in main.global_block().ops
               if op.type == "eva_attention"]
    return choices, gates.tally(met), tally_traces(bodies), traced.jaxpr


@pytest.fixture
def compiled_mode():
    """The gate as a served step meets it: no interpret mode, so only the
    placement admits a kernel."""
    ea._INTERPRET = False
    yield
    ea._INTERPRET = True


def _primitives(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, counts)
    return counts


def test_a_step_on_one_tpu_takes_the_kernel_and_sites_share_its_body(
        compiled_mode):
    main, fetch, feed = _sites(EVABYTE, n=8)
    choices, tally, bodies, _ = _trace(main, fetch, feed, ("tpu",))
    assert len(choices) == 8
    for choice in choices:
        assert choice["admitted"] and choice["kernel"] == "eva_step"
        assert "blocks of 128 of 2048 window slots and 2048 summary " \
            "entries" in choice["reasons"][0]["detail"]
    assert tally == {"eva_attention": {"kernel eva_step": 8}}
    assert bodies == {"eva_step.fwd": {"traced": 1, "reused": 7}}


def test_the_admitted_form_is_one_kernel_call_that_takes_the_caches_as_stored(
        compiled_mode):
    """One ``pallas_call`` a site; the four caches are the step's own
    inputs and no equation but the kernel's takes one (nothing copied,
    concatenated or turned on the way in), and nothing outside the kernel
    is of a cache's size."""
    main, fetch, feed = _sites(EVABYTE)
    _, _, _, jaxpr = _trace(main, fetch, feed, ("tpu",))
    assert _primitives(jaxpr.jaxpr, {})["pallas_call"] == 1
    b, w, entries, heads, d = EVABYTE
    caches = [v for v in jaxpr.jaxpr.invars
              if len(v.aval.shape) == 3 and v.aval.shape[1] in (w, entries)]
    assert len(caches) == 4
    for eqn in jaxpr.jaxpr.eqns:
        taken = [v for v in eqn.invars if any(v is c for c in caches)]
        if eqn.primitive.name == "pallas_call":
            assert taken == eqn.invars[-4:] and len(taken) == 4
            continue
        assert not taken, eqn
        for v in eqn.outvars:
            assert np.prod(v.aval.shape, dtype=np.int64) \
                < b * min(w, entries) * heads * d, eqn


def _with(shape, **changed):
    names = ("b", "w", "entries", "heads", "d")
    return tuple(changed.get(n, x) for n, x in zip(names, shape))


@pytest.mark.parametrize("shape,dtypes,placement,check,says", [
    (EVABYTE, ("bfloat16",), ("cpu",), "platform",
     "placed on 'cpu', not a TPU"),
    (EVABYTE, ("bfloat16",), ("tpu", True), "platform",
     "partitioned over a mesh"),
    (EVABYTE, ("float32", "bfloat16"), ("tpu",), "dtype",
     "not of one 2- or 4-byte floating type"),
    (_with(EVABYTE, entries=2048 + 64), ("bfloat16",), ("tpu",), "geometry",
     "share no block of a multiple of 128"),
    (_with(EVABYTE, w=128, entries=128), ("bfloat16",), ("tpu",), "geometry",
     "share no block of a multiple of 128"),
    (_with(EVABYTE, heads=12), ("bfloat16",), ("tpu",), "geometry",
     "12 heads no multiple of 16 sublanes"),
    (_with(EVABYTE, b=1024), ("bfloat16",), ("tpu",), "vmem",
     "exceed the 32 MB VMEM budget"),
    (EVABYTE, ("float16",), ("tpu",), None, None),
], ids=["cpu", "mesh", "mixed_types", "no_common_block", "one_block",
        "narrow", "oversized_batch", "float16"])
def test_the_rest_keep_the_whole_cache_form_and_say_why(
        compiled_mode, shape, dtypes, placement, check, says):
    main, fetch, feed = _sites(shape, dtype=dtypes[0],
                               cache_dtype=dtypes[-1])
    (choice,), tally, bodies, jaxpr = _trace(main, fetch, feed, placement)
    if check is None:       # another 2-byte floating type is taken as well
        assert choice["admitted"] and choice["kernel"] == "eva_step"
        return
    assert not choice["admitted"] and choice["kernel"] == "rung_xla"
    assert choice["fallback"] == "eva_step"
    assert [r["check"] for r in choice["reasons"]] == [check]
    (line, times), = tally["eva_attention"].items()
    assert line.startswith("fell back to rung_xla (wanted eva_step): "
                           + check) and says in line and times == 1
    assert not bodies and "pallas_call" not in _primitives(jaxpr.jaxpr, {})


def test_the_gate_counts_the_block_and_what_the_kernel_holds():
    """EvaByte's rows of 16 KB (keys and values) take blocks of 128 of both
    caches, and the kernel's own count of its VMEM stays under half the
    budget there where the two other step kernels' shared count, which
    takes an output a head for granted, reads 31 MB."""
    b, w, entries, heads, d = EVABYTE
    assert ea.step_block(w, entries, 2 * heads * d * 2) == 128
    assert ea.step_block(2048, 4096, 16384) == 128
    assert ea.step_block(2048, 2048 + 64, 16384) is None
    assert ea.step_block(128, 2048, 16384) is None
    held = ea._working_set(b, 128, heads, heads * d, 2)
    assert 14e6 < held < ca._VMEM_BUDGET / 2
    assert ca._working_set(b, 128, heads, heads * d, heads * d, 2) > 31e6
    plan = ea.step_plan(b, w, entries, heads, heads * d, 2)
    assert plan and plan.kernel == "eva_step"
    assert ea.step_plan(b, w, entries, heads, heads * d, 4)
    assert ea.step_plan(b, w, entries, heads, heads * d,
                        1).blocked_only_by("dtype")
    assert ea.step_plan(1024, w, entries, heads, heads * d,
                        2).blocked_only_by("vmem")
