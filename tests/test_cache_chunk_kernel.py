"""The chunk kernel behind ``cached_attention_chunk``
(``ops/cache_attention.py``: ``cache_chunk.fwd``, interpret mode here)
against ``attend_chunk``'s ``jnp`` form, which walks the same blocks as XLA
loops, and against a float32 softmax; and the gate that decides which of
the two a site takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.executor import build_step_fn
from paddle_tpu.ops import cache_attention as ca
from paddle_tpu.ops import gates
from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

F32, BF16 = jnp.float32, jnp.bfloat16
C = 1536            # three blocks of 512, as the ``jnp`` form cuts it too
HEADS, GROUPS, DK, DV = 16, 2, 192, 128     # MiMo-V2-Flash's 64 on 4, cut
TILE = ca.CHUNK_TILE


@pytest.fixture(autouse=True)
def interpreted():
    """Interpret mode, and JAX's trace caches emptied of what another
    mode traced."""
    ca._INTERPRET = True
    jax.clear_caches()
    yield
    ca._INTERPRET = False
    jax.clear_caches()


def _arrays(rows, lanes, dtype=BF16, sink=False, seed=0, c=C):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (rows, lanes, HEADS * DK)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (rows, c, GROUPS * DK)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (rows, c, GROUPS * DV)), dtype)
    s = jnp.asarray(rng.normal(0, 1, (HEADS,)), dtype) if sink else None
    return q, k, v, s


def _run(first, live, lanes, c=C):
    """A row's positions: ``live`` lanes from ``first`` on, pad lanes
    (``c``) after them."""
    p = first + np.arange(lanes)
    p[live:] = c
    return p


def _kernel(q, k, v, s, pos):
    return np.asarray(ca.chunk_blocks(
        q, k, v, jnp.asarray(pos, jnp.int32), HEADS, GROUPS, s).astype(F32))


def _jnp(q, k, v, s, pos):
    return np.asarray(ca.attend_chunk(
        q, k, v, jnp.asarray(pos, jnp.int32), HEADS, GROUPS, 0,
        s).astype(F32))


def _dense(q, k, v, s, pos):
    """A float32 softmax a head and a lane over the positions ``<= pos``,
    the sink in its denominator."""
    rows, lanes, c = q.shape[0], q.shape[1], k.shape[1]
    r = HEADS // GROUPS
    qh = np.asarray(q.astype(F32)).reshape(rows, lanes, GROUPS, r, DK)
    kh = np.asarray(k.astype(F32)).reshape(rows, c, GROUPS, DK)
    vh = np.asarray(v.astype(F32)).reshape(rows, c, GROUPS, DV)
    x = np.einsum("bkgrd,bcgd->bkgrc", qh, kh) / np.sqrt(DK)
    x = np.where(np.arange(c) <= np.asarray(pos)[:, :, None, None, None], x,
                 -np.inf)
    top = x.max(-1, keepdims=True)
    sink = None
    if s is not None:
        sink = np.asarray(s.astype(F32)).reshape(1, 1, GROUPS, r, 1)
        top = np.maximum(top, sink)
    e = np.exp(x - top)
    total = e.sum(-1, keepdims=True)
    if sink is not None:
        total = total + np.exp(sink - top)
    return np.einsum("bkgrc,bcgd->bkgrd", e / total, vh).reshape(
        rows, lanes, -1)


# rows of a chunk of K lanes, by what their positions cross
def _rows(lanes):
    block = ca.chunk_block(C)
    return {
        "from_position_0": _run(0, lanes, lanes),
        "across_a_block_edge": _run(block - 60, lanes, lanes),
        "to_the_last_position": _run(C - lanes, lanes, lanes),
        "pads_from_inside_a_tile": _run(300, TILE + 5, lanes),
        "pads_from_a_tile_edge": _run(block + 3, TILE, lanes),
        "one_live_lane": _run(2 * block, 1, lanes),
        "no_live_lane": _run(0, 0, lanes),
    }


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("where", list(_rows(256)))
def test_a_row_equals_the_jnp_form_on_its_live_lanes(where, sink):
    """One row of 256 lanes: the same blocks in the same order, a block's
    sums in another (bfloat16 outputs: a unit in the last place); a tile of
    pad lanes alone comes out 0, as a row of pad lanes does from the ``jnp``
    form."""
    pos = _rows(256)[where][None]
    q, k, v, s = _arrays(1, 256, sink=sink)
    out, ref = _kernel(q, k, v, s, pos), _jnp(q, k, v, s, pos)
    live = pos < C
    np.testing.assert_allclose(out[live], ref[live], atol=0.01)
    np.testing.assert_allclose(out[live], _dense(q, k, v, s, pos)[live],
                               atol=0.03)
    dead = ~live.reshape(-1, TILE).any(1).repeat(TILE).reshape(live.shape)
    assert not out[dead].any()


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize("lanes", [256, 512, 1024])
def test_chunks_of_every_rung_equal_the_jnp_form(lanes, rows, sink):
    """B rows of K lanes: a row across block and tile edges with pad lanes
    at its end, a row that starts at position 0, a row with no live lane."""
    pos = np.stack([_run(C - lanes - 77, lanes - 77, lanes),
                    _run(0, lanes, lanes), _run(0, 0, lanes)])[:rows]
    q, k, v, s = _arrays(rows, lanes, sink=sink, seed=lanes + rows)
    out, ref = _kernel(q, k, v, s, pos), _jnp(q, k, v, s, pos)
    live = pos < C
    np.testing.assert_allclose(out[live], ref[live], atol=0.01)
    np.testing.assert_allclose(out[live], _dense(q, k, v, s, pos)[live],
                               atol=0.03)
    if rows == 3:
        assert not out[2].any() and not ref[2].any()


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
def test_lanes_in_no_order_read_up_to_their_own_position(sink):
    """Positions that are not consecutive, pad lanes among the live ones:
    the mask is a lane's own, only the walk's end is the tile's."""
    rng = np.random.default_rng(11)
    pos = rng.integers(0, C, (2, 256))
    pos[0, rng.integers(0, 256, 40)] = C
    pos[1, :TILE] = C + 9
    q, k, v, s = _arrays(2, 256, sink=sink, seed=4)
    out, ref = _kernel(q, k, v, s, pos), _jnp(q, k, v, s, pos)
    live = pos < C
    np.testing.assert_allclose(out[live], ref[live], atol=0.01)
    np.testing.assert_allclose(out[live], _dense(q, k, v, s, pos)[live],
                               atol=0.03)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
def test_float32_caches_agree_with_the_softmax_closely(sink):
    pos = np.stack([_run(700, 200, 256), _run(0, 256, 256)])
    q, k, v, s = _arrays(2, 256, F32, sink=sink)
    live = pos < C
    np.testing.assert_allclose(_kernel(q, k, v, s, pos)[live],
                               _dense(q, k, v, s, pos)[live],
                               atol=2e-5, rtol=2e-5)


def test_a_rung_the_jnp_form_cuts_otherwise_agrees_to_rounding():
    """1280 positions: the kernel's blocks are 256 (a multiple of 128), the
    ``jnp`` form's 320. Other blocks, other partial sums."""
    c = 1280
    assert ca.chunk_block(c) == 256
    pos = _run(c - 300, 256, 256, c)[None]
    q, k, v, s = _arrays(1, 256, c=c)
    np.testing.assert_allclose(_kernel(q, k, v, s, pos),
                               _jnp(q, k, v, s, pos), atol=0.03)


def test_garbage_past_a_lanes_position_changes_no_output_bit():
    """Large finite values where a row holds nothing yet, in the blocks
    the kernel fetches and in those it does not."""
    pos = np.stack([_run(200, 256, 256), _run(900, 130, 256)])
    q, k, v, s = _arrays(2, 256)
    past = (np.arange(C) > np.where(pos < C, pos, -1).max(1)[:, None])[
        :, :, None]
    clean = _kernel(q, jnp.where(past, 0, k), jnp.where(past, 0, v), s, pos)
    dirty = _kernel(q, jnp.where(past, 1e30, k).astype(BF16),
                    jnp.where(past, -3e37, v).astype(BF16), s, pos)
    live = pos < C
    assert np.array_equal(clean[live], dirty[live])


def test_a_row_alone_and_among_others_agree_bit_for_bit():
    pos = np.stack([_run(0, 256, 256), _run(640, 190, 256),
                    _run(1200, 256, 256)])
    q, k, v, s = _arrays(3, 256, sink=True, seed=3)
    batched = _kernel(q, k, v, s, pos)
    alone = _kernel(q[1:2], k[1:2], v[1:2], s, pos[1:2])
    assert np.array_equal(batched[1], alone[0])


def test_a_groups_keys_are_found_in_whole_128s():
    """Four groups of 192 lie at 0, 192, 384, 576: windows of 256 from 0,
    128, 384, 512 hold them, inside the row; widths of whole 128s need no
    window wider than themselves."""
    assert ca._key_windows(4, 192) == (256, [0, 128, 384, 512])
    assert ca._key_windows(2, 192) == (256, [0, 128])
    assert ca._key_windows(4, 128) == (128, [0, 128, 256, 384])
    assert ca._key_windows(1, 256) == (256, [0])
    assert ca._key_windows(2, 64) == (128, [0, 0])
    for g, dk in ((4, 192), (2, 192), (2, 64), (6, 64), (2, 320), (8, 48)):
        width, first = ca._key_windows(g, dk)
        for gi, at in enumerate(first):
            assert at % 128 == 0 and width % 128 == 0
            assert 0 <= at <= gi * dk and (gi + 1) * dk <= at + width <= g * dk


# ---------------------------------------------------------------------------
# which sites take it
# ---------------------------------------------------------------------------

# (rows, lanes, capacity, heads, key/value heads, Dk, Dv) of
# ``mimo2flash.serve.mixedlen.sat``'s chunk ops
MIMO_FULL = (1, 1024, 16384, 64, 4, 192, 128)
MIMO_WINDOW = (1, 1024, 128, 64, 8, 192, 128)


def _sites(shape, n=1, dtype="bfloat16", q_dtype=None, **more):
    """A program of ``n`` ``cached_attention_chunk`` ops of one signature,
    and the abstract feeds it is traced with."""
    b, lanes, c, heads, g, dk, dv = shape
    q_dtype = q_dtype or dtype
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = layers.data("q", shape=[lanes, heads * dk], dtype=q_dtype)
        ck = layers.data("ck", shape=[c, g * dk], dtype=dtype)
        cv = layers.data("cv", shape=[c, g * dv], dtype=dtype)
        pos = layers.data("pos", shape=[lanes], dtype="int32")
        if more.get("ring"):
            more["new_k"] = layers.data("nk", shape=[lanes, g * dk],
                                        dtype=dtype)
            more["new_v"] = layers.data("nv", shape=[lanes, g * dv],
                                        dtype=dtype)
        outs = [layers.cached_attention(q, ck, cv, pos, heads, g, **more)
                for _ in range(n)]
    real = jnp.dtype(dtype)
    feed = {"q": jax.ShapeDtypeStruct((b, lanes, heads * dk),
                                      jnp.dtype(q_dtype)),
            "ck": jax.ShapeDtypeStruct((b, c, g * dk), real),
            "cv": jax.ShapeDtypeStruct((b, c, g * dv), real),
            "pos": jax.ShapeDtypeStruct((b, lanes), jnp.int32)}
    if more.get("ring"):
        feed["nk"] = jax.ShapeDtypeStruct((b, lanes, g * dk), real)
        feed["nv"] = jax.ShapeDtypeStruct((b, lanes, g * dv), real)
    return main, [o.name for o in outs], feed


def _trace(main, fetch, feed, placement):
    """Trace the program's chunk run as an Executor placed so would
    (nothing lowered, nothing run): the ops' recorded choices, the gate
    tally, the kernel bodies traced and the traced program."""
    persist = sorted(v.name for v in main.list_vars() if v.persistable)
    state = {n: jax.ShapeDtypeStruct(
        tuple(main.global_block().var(n).shape), BF16) for n in persist}
    step = build_step_fn(main, fetch, persist, infer_only=True)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with gates.placed(*placement), gates.collect() as met, \
            collect_traces() as bodies:
        traced = jax.jit(step).trace(state, feed, rng)
    choices = [op.attrs["_kernel_choice"]
               for op in main.global_block().ops
               if op.type == "cached_attention_chunk"]
    return choices, gates.tally(met), tally_traces(bodies), traced


def _kernel_scopes(jaxpr, found):
    """The name stacks of a traced program's Pallas calls, sub-programs
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.source_info.name_stack))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernel_scopes(inner, found)
    return found


@pytest.fixture
def compiled_mode():
    """The gate as a served chunk run meets it: no interpret mode, so only
    the placement admits a kernel."""
    ca._INTERPRET = False
    yield
    ca._INTERPRET = True


@pytest.mark.parametrize("more", [
    {}, {"sink_attr": fluid.ParamAttr(name="sink")}], ids=["plain", "sink"])
@pytest.mark.parametrize("lanes", [1024, 512])
def test_a_chunk_on_one_tpu_takes_the_kernel_and_sites_share_its_body(
        compiled_mode, lanes, more):
    shape = (1, lanes) + MIMO_FULL[2:]
    main, fetch, feed = _sites(shape, n=2, **more)
    choices, tally, bodies, traced = _trace(main, fetch, feed, ("tpu",))
    assert len(choices) == 2
    for choice in choices:
        assert choice["admitted"] and choice["kernel"] == "cache_chunk"
        assert "blocks of 512 of 16384 positions" in \
            choice["reasons"][0]["detail"]
    assert tally == {"cached_attention_chunk": {"kernel cache_chunk": 2}}
    assert bodies == {"cache_chunk.fwd": {"traced": 1, "reused": 1}}
    # the kernel runs under the full layers' scope
    scopes = _kernel_scopes(traced.jaxpr.jaxpr, [])
    assert len(scopes) == 2
    for scope in scopes:
        assert "cached_attention_chunk/attn.full/cache_chunk.fwd" in scope


_REFUSALS = [
    ("ring", MIMO_WINDOW, {"window": 128, "ring": True}, ("tpu",), "shape",
     "a ring of 128 positions"),
    ("window", MIMO_FULL, {"window": 128}, ("tpu",), "shape",
     "a window of 128 positions"),
    ("cpu", MIMO_FULL, {}, ("cpu",), "platform",
     "placed on 'cpu', not a TPU"),
    ("mesh", MIMO_FULL, {}, ("tpu", True), "platform",
     "partitioned over a mesh"),
    ("mixed_types", MIMO_FULL, {"q_dtype": "float32"}, ("tpu",), "dtype",
     "queries and caches are not of one"),
    ("no_dividing_block", (1, 1024, 16384 + 64, 64, 4, 192, 128), {},
     ("tpu",), "geometry", "has no block of a multiple of 128"),
    ("lanes_off_the_tile", (1, 1000, 16384, 64, 4, 192, 128), {}, ("tpu",),
     "geometry", "1000 lanes no multiple of the tile of 128"),
    ("values_off_128", (1, 1024, 16384, 64, 4, 192, 96), {}, ("tpu",),
     "geometry", "a group's values of 96"),
    ("vmem", (1, 1024, 16384, 256, 4, 192, 128), {}, ("tpu",), "vmem",
     "exceed the 32 MB VMEM budget"),
]


@pytest.mark.parametrize("shape,more,placement,check,says",
                         [c[1:] for c in _REFUSALS],
                         ids=[c[0] for c in _REFUSALS])
def test_the_rest_keep_the_jnp_form_and_say_why(
        compiled_mode, shape, more, placement, check, says):
    main, fetch, feed = _sites(shape, **more)
    (choice,), tally, bodies, _ = _trace(main, fetch, feed, placement)
    assert not choice["admitted"] and choice["kernel"] == "rung_xla"
    assert choice["fallback"] == "cache_chunk"
    assert [r["check"] for r in choice["reasons"]] == [check]
    (line, times), = tally["cached_attention_chunk"].items()
    assert line.startswith("fell back to rung_xla (wanted cache_chunk): "
                           + check) and says in line and times == 1
    assert not bodies


@pytest.mark.parametrize("reason,kw,admitted", [
    ("cpu", {"platform": gates.GateReason("platform", "x")}, False),
    ("one_tpu", {}, True),
    ("window", {"window": 128}, False), ("no_window", {"window": 0}, True),
    ("ring", {"ring": True}, False),
    ("mixed_types", {"itemsize": None}, False),
    ("one_byte", {"itemsize": 1}, False), ("float32", {"itemsize": 4}, True),
    ("no_block", {"c": 16384 + 64}, False), ("one_block", {"c": 128}, True),
    ("lanes_off_the_tile", {"lanes": 192}, False),
    ("lanes_of_a_tile", {"lanes": 128}, True),
    ("vmem", {"heads": 256}, False), ("plain_heads", {"kv_heads": 64,
                                                      "kd": 64 * 192,
                                                      "vd": 64 * 128}, True),
])
def test_each_side_of_every_reason_of_the_plan(reason, kw, admitted):
    args = dict(b=1, c=16384, lanes=1024, heads=64, kv_heads=4, kd=768,
                vd=512, itemsize=2)
    args.update(kw)
    plan = ca.chunk_plan(**args)
    assert bool(plan) == admitted, plan
    assert plan.kernel == ("cache_chunk" if admitted else "rung_xla")


def test_the_op_takes_the_kernel_and_gives_the_jnp_forms_result():
    """``cached_attention_chunk`` through an Executor, interpret mode in
    the kernel's place: the op asks the plan, the kernel's result is the
    ``jnp`` form's on every live lane, and the choice is recorded."""
    shape = (2, 256, C, HEADS, GROUPS, DK, DV)
    pos = np.stack([_run(500, 256, 256), _run(0, 131, 256)]).astype("int32")
    q, k, v, s = _arrays(2, 256, F32, sink=True, seed=8)
    outs = {}
    for mode in (True, False):
        ca._INTERPRET = mode
        jax.clear_caches()
        main, fetch, _ = _sites(shape, dtype="float32",
                                sink_attr=fluid.ParamAttr(name="sink"))
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            scope.set("sink", np.asarray(s))
            outs[mode], = exe.run(main, feed={
                "q": np.asarray(q), "ck": np.asarray(k), "cv": np.asarray(v),
                "pos": pos}, fetch_list=fetch)
        op, = [o for o in main.global_block().ops
               if o.type == "cached_attention_chunk"]
        assert op.attrs["_kernel_choice"]["admitted"] is mode
    live = pos < C
    np.testing.assert_allclose(outs[True][live], outs[False][live],
                               atol=2e-6, rtol=2e-6)
