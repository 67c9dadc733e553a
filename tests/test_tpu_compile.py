"""Ask the chip's compiler, without the chip.

libtpu compiles for a TPU that is described and not attached, so every
default-on Pallas kernel family is lowered here at the shape its BASELINE
config runs and compiled for one chip of a ``v5e:2x2`` host. That catches
what interpret mode cannot: a kernel that asks for more scoped VMEM or SMEM
than the chip has, a slice Mosaic cannot tile, a program that does not fit
HBM. A compile is not a run — numerics and time on the chip are
``chip_smoke.py``'s business.

The gates are steered from here with ``ops.gates.placed("tpu")`` (code
that asks JAX for its devices still sees the CPU). The whole train step of
every BASELINE config is compiled the same way in the ``slow`` cases.
Describing the topology takes libtpu's process lock: one such process at a
time.
"""

import contextlib
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import fused_ce, fused_conv, scatter  # noqa: E402
from paddle_tpu.ops.gates import placed, platform_reason  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topology():
    """A described (not attached) four-chip v5e host. (conftest.py keeps
    the persistent compile cache off: an entry written by a compile for
    it cannot be read back without a chip.)"""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip("libtpu cannot describe a v5e here: %s" % e)


@pytest.fixture(scope="module")
def chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


def _compile(chip, fn, *avals, **jit_kw):
    """Compile ``fn`` for the described chip; returns the compiled object."""
    def on(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    with placed("tpu"):
        lowered = jax.jit(fn, **jit_kw).lower(*jax.tree.map(on, avals))
    return lowered.compile()


def _kernel_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\""
    r".*?op_name=\"([^\"]*)\"", re.M)


def _kernel_names(compiled):
    """[(HLO instruction name, op_name path)] of the compiled module's
    Pallas calls. A device event on the chip is called by the first; the
    benchmark joins it to the second."""
    return _CUSTOM_CALL.findall(compiled.as_text())


def _assert_named(compiled, expected):
    """Every Pallas call of the compiled module carries one of ``expected``
    (``<family>.<part>``): its instruction is called ``<name>.<n>``
    and its op_name path holds ``<name>`` as a component; and each
    expected name is there."""
    found = set()
    for instruction, op_name in _kernel_names(compiled):
        name, = [e for e in expected
                 if instruction == e or instruction.startswith(e + ".")]
        assert name in op_name.split("/"), (name, op_name)
        found.add(name)
    assert found == set(expected)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# flash attention: fwd + bwd with in-kernel dropout
# ---------------------------------------------------------------------------

_FLASH_CASES = [
    # id, (B, T, H*D, heads), causal, key bias, expected plan
    ("bert_b128_t128", (128, 128, 768, 12), False, True, "dense_vmem"),
    ("transformer_b128_t256_enc", (128, 256, 512, 8), False, True,
     "dense_vmem"),
    ("transformer_b128_t256_dec", (128, 256, 512, 8), True, False,
     "dense_vmem"),
    ("stream_b32_t1024", (32, 1024, 512, 8), False, True, "packed_stream"),
    # ISSUE 29: a 128-lane window of the packed heads fits at T=2048 ...
    ("seq2048_b16_enc", (16, 2048, 512, 8), False, True, "packed_stream"),
    ("seq2048_b16_dec", (16, 2048, 512, 8), True, False, "packed_stream"),
    # ... one head of 128 a window as well; past 3072 the head-split
    # kernels and their relayout copies take over
    ("seq3072_b4_d128", (4, 3072, 1024, 8), True, False, "packed_stream"),
    ("seq4096_b8_dec", (8, 4096, 512, 8), True, False, "head_split_stream"),
]


@pytest.mark.parametrize("shape,causal,with_bias,plan",
                         [c[1:] for c in _FLASH_CASES],
                         ids=[c[0] for c in _FLASH_CASES])
def test_flash_attention_compiles(chip, shape, causal, with_bias, plan):
    b, t, hd, heads = shape
    bias = sds((b, t), F32) if with_bias else None
    with placed("tpu"):
        decision = fa.kernel_plan((b, t, hd), (b, t, hd), heads, 2,
                                  causal=causal, dropout_rate=0.1,
                                  bias_kind="key" if with_bias else None)
    assert decision.kernel == plan, decision

    def loss(q, k, v, bias, g, key_data):
        out = fa.flash_attention(q, k, v, heads, bias=bias, causal=causal,
                                 dropout_rate=0.1,
                                 rng=jax.random.wrap_key_data(key_data))
        return jnp.sum(out.astype(F32) * g.astype(F32))

    x = sds((b, t, hd), BF16)
    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        x, x, x, bias, x, sds((2,), jnp.uint32))
    assert _kernel_calls(compiled) == 2  # one forward, one fused backward
    _assert_named(compiled, {plan + ".fwd", plan + ".bwd"})


def test_wide_head_at_8192_compiles_in_segments(chip):
    """``qwen3next.train.s8192``'s attention: 16 heads of 256 at T = 8192,
    causal, no dropout. One head's K/V is past the head-split kernels' VMEM
    (the chip's compiler refused their backward on 2048-token segments in
    blocks of 512 at 16.14M of 16M, inside the step), so the plan cuts T
    into 2048-token segments in blocks of 256: ten forward and ten backward
    calls of the same named kernels."""
    b, t, hd, heads = 1, 8192, 4096, 16
    with placed("tpu"):
        decision = fa.kernel_plan((b, t, hd), (b, t, hd), heads, 2,
                                  causal=True)
    assert decision.kernel == "segmented_stream", decision
    assert fa._segment_plan(t, hd // heads, 2) == (2048, 256)
    assert not fa._head_split_fits(2048, 2048, 256, 2, (512, 512))

    def loss(q, k, v, g):
        out = fa.flash_attention(q, k, v, heads, causal=True)
        return jnp.sum(out.astype(F32) * g.astype(F32))

    x = sds((b, t, hd), BF16)
    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)), x, x, x, x)
    assert _kernel_calls(compiled) == 20
    _assert_named(compiled, {"head_split_stream.fwd",
                             "head_split_stream.bwd"})


def test_gated_delta_core_and_routed_experts_fit_at_published_widths(chip):
    """The delta rule of one layer (32 heads of 128 x 128 state, T = 8192)
    runs as the two ``gated_delta`` kernels, whose in-chunk tensors never
    leave VMEM, and keeps under 0.5 GB of temporaries (2.9 GB as one
    stacked ``jnp`` form, 1 GB with its groups of chunks recomputed); and
    the routed experts (16 of 512, top-10) run as the ``grouped_experts``
    kernels (forward, backward, and ``combine`` after each) over one call's
    gathered rows, under 0.7 GB (the float32 weight gradients 0.2, a call's
    row buffers and partial outputs the rest): what lets four layers fit
    one chip."""
    from paddle_tpu.ops import gated_delta
    from paddle_tpu.parallel import moe

    t = 8192
    with placed("tpu"):
        assert gated_delta.kernel_plan(
            t, 16, 32, 128, 128, 64,
            platform=platform_reason()).kernel == "gated_delta"

    def core(q, k, v, a, b, a_log, dt_bias):
        return jnp.sum(gated_delta.gated_delta_attention(
            q, k, v, a, b, a_log, dt_bias, 16, 32, 64, BF16))

    compiled = _compile(
        chip, jax.grad(core, argnums=(0, 1, 2, 3, 4, 5, 6)),
        sds((1, t, 2048), BF16), sds((1, t, 2048), BF16),
        sds((1, t, 4096), BF16), sds((1, t, 32), BF16),
        sds((1, t, 32), BF16), sds((32,), F32), sds((32,), F32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    assert _kernel_calls(compiled) == 2
    _assert_named(compiled, {"gated_delta.fwd", "gated_delta.bwd"})

    def experts(x, router, wg, wu, wd):
        return jnp.sum(moe.routed_experts(x, router, wg, wu, wd, 10, 0)[0])

    # the value too: the gradient alone does not need the forward kernel
    compiled = _compile(
        chip, jax.value_and_grad(experts, argnums=(0, 1, 2, 3, 4)),
        sds((t, 2048), BF16), sds((2048, 512), F32),
        sds((16, 512, 2048), BF16), sds((16, 512, 2048), BF16),
        sds((16, 2048, 512), BF16))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert _kernel_calls(compiled) == 4
    _assert_named(compiled, _EXPERT_KERNELS)


def test_sparse_selection_and_latent_attention_fit_at_published_widths(chip):
    """``glm52.serve.longdoc.sat``'s sparse ops at its own sizes (8 slot
    rows x 12288 positions; 32 index heads of 128, top-2048; 64 heads over
    one cached row of 512 + 64), bfloat16 but the index path (float32 keys;
    a step's float32 queries scored exactly, a chunk's bfloat16 ones in one
    pass): a step's indexer and attention
    over the gathered set, and a chunk's 512 lanes a row, whose scores exist
    a block of positions at a time (no [lanes, heads, context] scores, no
    [lanes, 2048, 576] gather: 9.7 GB), all of it plain XLA under 1 GB of
    temporaries."""
    from paddle_tpu.ops import sparse_latent

    b, c, k, heads = 8, 12288, 512, 64

    def step(qi, w, keys, q, kv_b, cache, pos):
        index, count = sparse_latent.sparse_index(qi, w, keys, pos, 32, 2048)
        return sparse_latent.latent_attention(
            q, kv_b, cache, index, heads, 192, 256, 256 ** -0.5), count

    compiled = _compile(
        chip, step, sds((b, 4096), F32), sds((b, 32), F32),
        sds((b, c, 128), F32), sds((b, heads * 256), BF16),
        sds((512, heads * 448), BF16), sds((b, c, 576), BF16),
        sds((b,), I32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    assert _kernel_calls(compiled) == 0

    def chunk(qi, w, keys, q, kv_b, cache, pos):
        mask, count = sparse_latent.sparse_index_chunk(qi, w, keys, pos, 32,
                                                       2048)
        return sparse_latent.latent_attention_chunk(
            q, kv_b, cache, mask, pos, heads, 192, 256, 256 ** -0.5), count

    compiled = _compile(
        chip, chunk, sds((b, k, 4096), BF16), sds((b, k, 32), BF16),
        sds((b, c, 128), F32), sds((b, k, heads * 256), BF16),
        sds((512, heads * 448), BF16), sds((b, c, 576), BF16),
        sds((b, k), I32))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    # the blocks run under loops, not unrolled: a dynamic trip count
    assert compiled.as_text().count(" while(") >= 4


def test_grouped_window_and_ring_attention_fit_at_published_widths(chip):
    """``mimo2flash.serve.mixedlen.sat``'s attention at its own sizes (16
    slot rows x 16384 positions; 64 query heads of 192 on 4 key heads and 4
    value heads of 128 a full layer, on 8 and 8 over a ring of 128 a window
    layer, a sink a head), bfloat16: a step reads each cache as it is
    stored (no copy of a cache, no repeat to the query heads), a chunk's 512
    lanes walk a full layer's cache in blocks under a dynamic trip count (no
    [lanes, heads, context] scores: 2.1 GB) and read a ring beside their own
    rows in blocks of the ring's size. These are the ``jnp`` forms, plain
    XLA: what the window layers' ops run on the chip, and what a full
    layer's ops run on the CPU and under a mesh; on one TPU a full layer's
    step is the kernel ``cache_step.fwd`` and its chunk ``cache_chunk.fwd``,
    whose reference these are (both compiled below)."""
    from paddle_tpu.ops import cache_attention as ca

    b, c, heads, ring = 16, 16384, 64, 128

    def full_step(q, k, v, pos):
        return ca.attend_step(q, k, v, pos, heads, 4)

    compiled = _compile(
        chip, full_step, sds((b, heads * 192), BF16), sds((b, c, 768), BF16),
        sds((b, c, 512), BF16), sds((b,), I32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    assert not re.search(r"= bf16\[16,16384,\d+\]\S* (copy|transpose)\(",
                         compiled.as_text())

    def window_step(q, k, v, pos, sink):
        return ca.attend_step(q, k, v, pos, heads, 8, ring, sink, True)

    compiled = _compile(
        chip, window_step, sds((b, heads * 192), BF16),
        sds((b, ring, 1536), BF16), sds((b, ring, 1024), BF16),
        sds((b,), I32), sds((heads,), BF16))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9

    def full_chunk(q, k, v, pos):
        return ca.attend_chunk(q, k, v, pos, heads, 4)

    compiled = _compile(
        chip, full_chunk, sds((1, 512, heads * 192), BF16),
        sds((1, c, 768), BF16), sds((1, c, 512), BF16), sds((1, 512), I32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9
    # the blocks run under a loop, not unrolled: a dynamic trip count
    assert compiled.as_text().count(" while(") >= 1

    def ring_chunk(q, rk, rv, nk, nv, pos, sink):
        return ca.attend_chunk_ring(q, rk, rv, nk, nv, pos, heads, 8, ring,
                                    sink)

    compiled = _compile(
        chip, ring_chunk, sds((1, 512, heads * 192), BF16),
        sds((1, ring, 1536), BF16), sds((1, ring, 1024), BF16),
        sds((1, 512, 1536), BF16), sds((1, 512, 1024), BF16),
        sds((1, 512), I32), sds((heads,), BF16))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9
    assert _kernel_calls(compiled) == 0


# (rows, capacity, heads, key/value heads, Dk, Dv, sink) of the step ops of
# ``opt1p3b.serve.chat*`` and of ``mimo2flash.serve.mixedlen.sat``'s full
# layers
_CACHE_STEP_CASES = {
    "opt1p3b": (16, 1280, 32, 32, 64, 64, False),
    "mimo2flash_full": (16, 16384, 64, 4, 192, 128, False),
    "mimo2flash_full_sink": (16, 16384, 64, 4, 192, 128, True),
}


@pytest.mark.parametrize("cell", sorted(_CACHE_STEP_CASES))
def test_cache_step_compiles_within_the_vmem_the_gate_counts(
        chip, cell, monkeypatch):
    """The step kernel behind ``cached_attention`` at each serving cell's
    own shape, ``vmem_limit_bytes`` set to the working set its gate counts:
    the gate admits the shape, Mosaic needs no more than is counted, the
    kernel is there by name, and no cache is copied or transposed on its
    way in (the kernel reads the caches where and as they are stored)."""
    from paddle_tpu.ops import cache_attention as ca

    b, c, heads, g, dk, dv, with_sink = _CACHE_STEP_CASES[cell]
    q, k, v = (sds((b, heads * dk), BF16), sds((b, c, g * dk), BF16),
               sds((b, c, g * dv), BF16))
    with placed("tpu"):
        plan = ca.plan_for(q, k, v, heads, g)
    assert plan.kernel == "cache_step", plan
    block = ca.step_block(c, 2 * g * (dk + dv))
    counted = ca._working_set(b, block, heads, g * dk, g * dv, 2)
    assert counted <= ca._VMEM_BUDGET
    monkeypatch.setattr(ca, "_VMEM_BUDGET", counted)

    def step(q, k, v, pos, sink):
        return ca.step_blocks(q, k, v, pos, heads, g, sink)

    compiled = _compile(chip, step, q, k, v, sds((b,), I32),
                        sds((heads,), BF16) if with_sink else None)
    _assert_named(compiled, {"cache_step.fwd"})
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9
    assert not re.search(r"= bf16\[%d,%d,\d+\]\S* (copy|transpose)\(" % (b, c),
                         compiled.as_text())


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("lanes", [1024, 512])
def test_cache_chunk_compiles_within_the_vmem_the_gate_counts(
        chip, lanes, with_sink, monkeypatch):
    """The chunk kernel behind ``cached_attention_chunk`` at a full layer's
    shape of ``mimo2flash.serve.mixedlen.sat`` (one row of 1024 or 512 lanes
    of 64 heads of 192 over 16384 positions of 4 key heads and 4 value heads
    of 128), ``vmem_limit_bytes`` set to the working set its gate counts:
    the gate admits the shape, Mosaic needs no more than is counted, the
    kernel is there by name under ``attn.full``, no block's float32 scores
    exist outside it (``[.., lanes, 512]``: 134 MB at 1024 lanes), and no
    cache is copied or transposed on its way in (the kernel takes each
    group's columns from the caches where and as they are stored)."""
    from paddle_tpu.ops import cache_attention as ca

    c, heads, g, dk, dv = 16384, 64, 4, 192, 128
    q, k, v = (sds((1, lanes, heads * dk), BF16), sds((1, c, g * dk), BF16),
               sds((1, c, g * dv), BF16))
    with placed("tpu"):
        plan = ca.chunk_plan_for(q, k, v, heads, g)
    assert plan.kernel == "cache_chunk", plan
    width, first = ca._key_windows(g, dk)
    block = ca.chunk_block(c)
    counted = ca._working_set(1, block, heads // g * ca.CHUNK_TILE, width,
                              dv, 2)
    assert (block, width, first) == (512, 256, [0, 128, 384, 512])
    assert counted <= ca._VMEM_BUDGET
    monkeypatch.setattr(ca, "_VMEM_BUDGET", counted)

    def chunk(q, k, v, pos, sink):
        return ca.chunk_blocks(q, k, v, pos, heads, g, sink)

    compiled = _compile(chip, chunk, q, k, v, sds((1, lanes), I32),
                        sds((heads,), BF16) if with_sink else None)
    _assert_named(compiled, {"cache_chunk.fwd"})
    (_, path), = _kernel_names(compiled)
    assert "attn.full" in path.split("/")
    text = compiled.as_text()
    assert not re.search(r"f32\[[\d,]*%d,%d\]" % (lanes, block), text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    assert not re.search(r"= bf16\[1,%d,\d+\]\S* (copy|transpose)\(" % c,
                         text)


def test_latent_step_compiles_within_the_vmem_the_gate_counts(
        chip, monkeypatch):
    """The step kernel behind ``latent_attention_dense`` at
    ``glm47flash.serve.reason.sat``'s own shape (32 rows x 4096 positions of
    512 + 64, two lanes of 20 heads), ``vmem_limit_bytes`` set to the
    working set its gate counts: the gate admits the shape, Mosaic needs no
    more than is counted, the kernel is there by name, and the cache is
    neither copied nor turned on its way in: the device keeps it with the
    positions innermost, and that is what the kernel is handed."""
    from paddle_tpu.ops import cache_attention as ca
    from paddle_tpu.ops import sparse_latent

    b, c, lanes, heads, r, p, nope, v = 32, 4096, 2, 20, 512, 64, 192, 256
    q, cache = (sds((b, lanes, heads * (nope + p)), BF16),
                sds((b, c, r + p), BF16))
    with placed("tpu"):
        plan = ca.latent_plan_for(q, cache, r, heads)
    assert plan.kernel == "latent_step", plan
    block = ca.step_block(c, 2 * (r + p))
    counted = ca._working_set(b, block, ca._query_rows(lanes, heads, 2),
                              r + p, r, 2, own_values=False)
    assert block == 512 and counted <= ca._VMEM_BUDGET
    monkeypatch.setattr(ca, "_VMEM_BUDGET", counted)

    def step(q, kv_b, cache, pos):
        return sparse_latent.latent_attention_dense(
            q, kv_b, cache, pos, heads, nope, v, (nope + p) ** -0.5,
            plan=plan)

    compiled = _compile(chip, step, q, sds((r, heads * (nope + v)), BF16),
                        cache, sds((b, lanes), I32))
    _assert_named(compiled, {"latent_step.fwd"})
    assert "latent_attention.core" in dict(
        (re.sub(r"\.\d+$", "", n), path)
        for n, path in _kernel_names(compiled))["latent_step.fwd"]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9
    assert not re.search(r"= bf16\[%d,(%d,%d|%d,%d)\]\S* (copy|transpose)\("
                         % (b, c, r + p, r + p, c), compiled.as_text())


def test_eva_step_compiles_within_the_vmem_the_gate_counts(chip, monkeypatch):
    """The step kernel behind ``eva_attention`` at
    ``evabyte.serve.bytes.sat``'s own shape (16 rows, a window of 2048 slots
    and 2048 summary entries of 32 heads of 128), ``vmem_limit_bytes`` set
    to the working set its gate counts: the gate admits the shape, Mosaic
    needs no more than is counted, the kernel is there by name under
    ``attn.eva``, and none of the four caches is copied or transposed on its
    way in (the kernel reads them where and as they are stored)."""
    from paddle_tpu.ops import cache_attention as ca
    from paddle_tpu.ops import eva_attention as ea

    b, w, entries, heads, d, chunk = 16, 2048, 2048, 32, 128, 16
    q = sds((b, heads * d), BF16)
    caches = [sds((b, n, heads * d), BF16) for n in (w, w, entries, entries)]
    with placed("tpu"):
        plan = ea.plan_for(q, *caches, heads)
    assert plan.kernel == "eva_step", plan
    block = ea.step_block(w, entries, 2 * heads * d * 2)
    counted = ea._working_set(b, block, heads, heads * d, 2)
    assert block == 128 and counted <= ca._VMEM_BUDGET
    monkeypatch.setattr(ca, "_VMEM_BUDGET", counted)

    def step(q, wk, wv, sk, sv, pos):
        return ea.step_blocks(q, wk, wv, sk, sv, pos, heads, w, chunk)

    compiled = _compile(chip, step, q, *caches, sds((b,), I32))
    _assert_named(compiled, {"eva_step.fwd"})
    (_, path), = _kernel_names(compiled)
    assert "attn.eva" in path.split("/")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9
    assert not re.search(r"= bf16\[%d,%d,\d+\]\S* (copy|transpose)\("
                         % (b, w), compiled.as_text())


def test_mimo_v2_step_reads_its_weights_where_they_lie_and_fits_the_chip(
        chip):
    """The cell's step program, built from the configuration's own keys
    and compiled for one chip at 16 slot rows x 16384: the caches it is
    handed are written in place (1.4 GB: two layers at the rung, five rings
    of 128), and NO weight is copied. Without the ``optimization_barrier``
    between the query/key projections and the ops that view their product
    a head at a time (192 wide, no multiple of 128), the compiler lays each
    projection's weight out for that view: a copy of 100 MB a layer a
    step."""
    import json

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import mimo_v2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2-flash.json")) as f:
        body = json.load(f)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch, spec = mimo_v2.mimo_v2_step(
            dtype="bfloat16", **{k: body[k] for k in body["builder_keys"]})
    gb = main.global_block()
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: sds(tuple(gb.var(n).shape),
                    BF16 if gb.var(n).dtype == "bfloat16"
                    else np.dtype(gb.var(n).dtype)) for n in persist}
    b, c = 16, 16384
    feed = {spec["token_feed"]: sds((b,), I32),
            spec["pos_feed"]: sds((b,), I32)}
    cache_bytes = 0
    for cf in spec["cache_feeds"]:
        shape = (b, cf.get("capacity") or c) + tuple(cf["tail"])
        feed[cf["feed"]] = sds(shape, BF16)
        cache_bytes += 2 * int(np.prod(shape))
    assert cache_bytes == 1342177280 + 52428800
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    step = build_step_fn(main, [v.name for v in fetch], persist,
                         infer_only=True)
    compiled = _compile(chip, step, state, feed, rng, donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes       # written in place
    assert mem.temp_size_in_bytes < 0.3e9
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert 0.25 * _HBM_BYTES < need < 0.6 * _HBM_BYTES
    assert not re.search(r"= bf16\[\d+,\d+\]\S* (copy|transpose)\(%?state",
                         compiled.as_text())
    # the two full layers read their caches by the step kernel, where they
    # lie; the five rings keep the jnp form
    assert _step_kernel_names(compiled) == {"cache_step.fwd": 2}
    assert not re.search(r"= bf16\[16,16384,\d+\]\S* (copy|transpose)\(",
                         compiled.as_text())
    choices = [op.attrs["_kernel_choice"]["kernel"]
               for op in gb.ops if op.type == "cached_attention"]
    assert sorted(choices) == ["cache_step"] * 2 + ["rung_xla"] * 5


@pytest.mark.parametrize("kind", ["step", "chunk"])
def test_glm_lite_programs_fit_the_chip_beside_every_expert(chip, kind):
    """``glm47flash.serve.reason.sat``'s two programs, built from the
    configuration's own keys and compiled for one chip at 32 slot rows x
    4096 (the chunk at its rung's sub-batch): 10.35 GB of weights, every
    expert and the whole vocabulary among them, 1.21 GB of latent caches
    written in place, and temporaries that leave room: a verifying step's
    two heads of [32, 2, 154880] float32 logits, a chunk's lanes through
    64 experts."""
    import json

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import glm_lite
    from paddle_tpu.serving.decode_batcher import chunk_rows

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        body = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "serve.reason.sat.json")) as f:
        engine = json.load(f)["engine"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch, spec = getattr(glm_lite, "glm_lite_" + kind)(
            dtype="bfloat16", **{k: body[k] for k in body["builder_keys"]})
    gb = main.global_block()
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: sds(tuple(gb.var(n).shape),
                    BF16 if gb.var(n).dtype == "bfloat16"
                    else np.dtype(gb.var(n).dtype)) for n in persist}
    b, c = engine["ladder"][0], engine["seq_ladder"][0]
    assert (b, c) == (32, 4096)
    if kind == "step":
        rows = b
        feed = {spec["token_feed"]: sds((b, 2), I32),
                spec["pos_feed"]: sds((b, 2), I32)}
    else:
        k = engine["prefill_ladder"][0]
        rows = chunk_rows(k, b)
        feed = {spec["token_feed"]: sds((rows, k + 1), I32),
                spec["pos_feed"]: sds((rows, k), I32)}
    cache_bytes = 0
    for cf in spec["cache_feeds"]:
        shape = (rows, c) + tuple(cf["tail"])
        feed[cf["feed"]] = sds(shape, BF16)
        cache_bytes += 2 * int(np.prod(shape))
    assert cache_bytes == 8 * 1152 * rows * c
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    step = build_step_fn(main, [v.name for v in fetch], persist,
                         infer_only=True)
    compiled = _compile(chip, step, state, feed, rng, donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes       # written in place
    weights = 2 * body["parameters"]
    assert mem.argument_size_in_bytes >= weights
    # the slot table stays beside a chunk's sub-batch while it runs
    table = 8 * 1152 * b * c if kind == "chunk" else 0
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes + table
    print("glm_lite %s: temporaries %.2f GB, arguments %.2f GB" % (
        kind, mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9))
    # whole-cache copies: none round the writes (a scatter over two lanes
    # turned each of the eight caches round twice: 2.4 GB of temporaries)
    assert mem.temp_size_in_bytes < 0.6e9
    assert 0.6 * _HBM_BYTES < need < 0.8 * _HBM_BYTES
    # a step's eight latent sites (seven layers and the module's) read
    # their caches by the step kernel, one body traced for all; a chunk's
    # lanes keep the blocks of ``latent_attention_chunk``
    dense = [op.attrs["_kernel_choice"]["kernel"] for op in gb.ops
             if op.type == "latent_attention_dense"]
    assert _step_kernel_names(compiled).get("latent_step.fwd", 0) == \
        len(dense) == (8 if kind == "step" else 0)
    assert set(dense) <= {"latent_step"}
    if kind == "step":
        # and none is turned or copied for the kernel: the four copies of a
        # cache where it lies that the step made before are all there are
        assert len(re.findall(r"= bf16\[%d,(?:%d,576|576,%d)\]\S* (?:copy|"
                              r"transpose)\(" % (rows, c, c),
                              compiled.as_text())) <= 4
        # a step's two-lane writes run under the step write's own scope, as
        # the chip's compiler leaves it: what ``cache_write_ms`` looks up
        from benchmark import trace_reduce

        wanted = trace_reduce.scope_pattern(("kv_cache_write",))
        found = [s for s in trace_reduce.hlo_scopes(
            compiled.as_text()).values() if wanted.search(s)]
        assert found, "no instruction under kv_cache_write"


@pytest.mark.parametrize("kind", ["step", "chunk"])
def test_evabyte_programs_fit_the_chip_at_the_rung_of_32768(chip, kind):
    """``evabyte.serve.bytes.sat``'s two programs, built from the
    configuration's own keys and compiled for one chip at 16 slot rows x
    32768 (the chunk at its rung's sub-batch, one row of 1024 lanes): 3.24
    GB of weights, and 8.59 GB of caches that are no rung long (a window
    cache of 2048 positions and a summary cache of 2048 entries a row and
    layer where a full cache would hold 32768), written in place; a chunk
    run's scores over window, lanes and summaries leave room beside the
    slot table."""
    import json

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import evabyte
    from paddle_tpu.serving.decode_batcher import chunk_rows

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "evabyte-6.5b.json")) as f:
        body = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "serve.bytes.sat.json")) as f:
        engine = json.load(f)["engine"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch, spec = getattr(evabyte, "evabyte_" + kind)(
            dtype="bfloat16", **{k: body[k] for k in body["builder_keys"]})
    gb = main.global_block()
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: sds(tuple(gb.var(n).shape),
                    BF16 if gb.var(n).dtype == "bfloat16"
                    else np.dtype(gb.var(n).dtype)) for n in persist}
    b, c = engine["ladder"][0], engine["seq_ladder"][0]
    assert (b, c) == (16, 32768)
    if kind == "step":
        rows = b
        feed = {spec["token_feed"]: sds((b,), I32),
                spec["pos_feed"]: sds((b,), I32)}
    else:
        k = engine["prefill_ladder"][0]
        rows = chunk_rows(k, b)
        assert (k, rows) == (1024, 1)
        feed = {spec["token_feed"]: sds((rows, k), I32),
                spec["pos_feed"]: sds((rows, k), I32)}
    cache_bytes, order, handed = 0, [], []
    for cf in spec["cache_feeds"]:
        entries = cf.get("capacity") or c // cf["stride"]
        assert entries == 2048
        order.append(cf["feed"])
        handed.append(sds((rows, entries) + tuple(cf["tail"]), BF16))
        cache_bytes += 2 * rows * entries * cf["tail"][0]
    assert cache_bytes == body["cache_bytes_per_row"] * rows
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    step = build_step_fn(main, [v.name for v in fetch], persist,
                         infer_only=True)

    def step_handed(state, feed, rng, handed):
        # the caches handed over in the order of the fetches that carry
        # them on, as ``Executor`` hands a decode loop's: 32 arrays of one
        # shape, which jit pairs with the outputs by their order
        return step(state, {**feed, **dict(zip(order, handed))}, rng)

    compiled = _compile(chip, step_handed, state, feed, rng, tuple(handed),
                        donate_argnums=(3,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes       # written in place
    assert mem.argument_size_in_bytes >= 2 * body["parameters"]
    # the slot table stays beside a chunk's sub-batch while it runs
    table = body["cache_bytes_per_row"] * b if kind == "chunk" else 0
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes + table
    print("evabyte %s: temporaries %.2f GB, arguments %.2f GB" % (
        kind, mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9))
    assert 0.65 * _HBM_BYTES < need < 0.85 * _HBM_BYTES
    text = compiled.as_text()
    # no cache is copied round a write (a step's scatters land in the
    # buffers it was handed; a chunk run turns its ONE row's sixteen window
    # caches round for the scores a head, 16 MB each, and nothing else), and
    # no weight is turned round for a view: without the barrier before
    # rotary the compiler lays the q and k matrices out a head at a time,
    # 32 MB each in every run
    copies = re.findall(r"= bf16\[%d,2048,4096\]\S* copy\(" % rows, text)
    assert len(copies) <= (0 if kind == "step" else 16), len(copies)
    assert not re.search(r"= bf16\[(4096,4096|4096,11008|11008,4096)\]\S* "
                         r"(copy|transpose)\(", text)
    assert mem.temp_size_in_bytes < (0.1e9 if kind == "step" else 1.2e9)
    # a step's attention is the step kernel, a call a layer, under its
    # scope; a chunk run's stays ``jnp``
    calls = _kernel_names(compiled)
    assert len(calls) == (body["num_hidden_layers"] if kind == "step" else 0)
    for instruction, path in calls:
        assert instruction.startswith("eva_step.fwd")
        assert {"attn.eva", "eva_step.fwd"} <= set(path.split("/"))
    from benchmark import trace_reduce

    scopes = trace_reduce.hlo_scopes(text).values()
    for scope in ("attn.eva", "eva.summary", "kv_cache_write" + (
            "" if kind == "step" else "_chunk")):
        wanted = trace_reduce.scope_pattern((scope,))
        assert any(wanted.search(s) for s in scopes), scope


def test_glm52_step_reads_a_selection_and_names_no_latent_step_kernel():
    """``glm52.serve.longdoc.sat``'s step, traced as an Executor on one TPU
    would trace it (nothing lowered): its latent attention reads an index
    and a gather (op ``latent_attention``), so no site asks the
    ``latent_attention_dense`` gate and no ``latent_step.fwd`` body is
    traced."""
    import json

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import glm_dsa
    from paddle_tpu.ops import gates
    from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-5.2.json")) as f:
        body = json.load(f)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch, spec = glm_dsa.glm_dsa_step(
            dtype="bfloat16", **{k: body[k] for k in body["builder_keys"]})
    gb = main.global_block()
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: sds(tuple(gb.var(n).shape),
                    BF16 if gb.var(n).dtype == "bfloat16"
                    else np.dtype(gb.var(n).dtype)) for n in persist}
    b, c = 8, 12288
    feed = {spec["token_feed"]: sds((b,), I32),
            spec["pos_feed"]: sds((b,), I32)}
    for cf in spec["cache_feeds"]:
        feed[cf["feed"]] = sds((b, c) + tuple(cf["tail"]),
                               BF16 if cf["dtype"] == "bfloat16"
                               else np.dtype(cf["dtype"]))
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    step = build_step_fn(main, [v.name for v in fetch], persist,
                         infer_only=True)
    with placed("tpu"), gates.collect() as met, collect_traces() as bodies:
        jax.jit(step).trace(state, feed, rng)
    kinds = {op.type for op in gb.ops}
    assert "latent_attention" in kinds
    assert "latent_attention_dense" not in kinds
    assert "latent_attention_dense" not in gates.tally(met)
    assert "latent_step.fwd" not in tally_traces(bodies)


def test_state_space_core_and_latent_experts_fit_at_published_widths(chip):
    """``nemotron3super.train.s8192``'s share of a layer: the Mamba-2 scan
    (16 heads of 64 x 128 state, one group, T = 8192, chunks of 128) keeps
    under 0.5 GB of temporaries with its groups of chunks recomputed, and
    the ReLU-squared experts in the 1024-wide latent (8 of 512, top-22,
    router at 4096) run as the ``grouped_experts`` kernels under 0.7
    GB."""
    from paddle_tpu.ops import mamba2
    from paddle_tpu.parallel import moe

    t = 8192

    def core(x, bm, cm, dt, a_log, dt_bias, d):
        return jnp.sum(mamba2.mamba2_ssd(x, bm, cm, dt, a_log, dt_bias, d,
                                         16, 1, 128, BF16))

    compiled = _compile(
        chip, jax.grad(core, argnums=(0, 1, 2, 3, 4, 5, 6)),
        sds((1, t, 1024), BF16), sds((1, t, 128), BF16),
        sds((1, t, 128), BF16), sds((1, t, 16), BF16), sds((16,), F32),
        sds((16,), F32), sds((16,), F32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    assert _kernel_calls(compiled) == 0     # no kernel for it yet

    def experts(x, h, router, bias, up, down):
        return jnp.sum(moe.routed_experts(
            x, router, None, up, down, 22, 0, form="relu2", score="sigmoid",
            bias=bias, scale=5.0, router_x=h)[0])

    compiled = _compile(
        chip, jax.value_and_grad(experts, argnums=(0, 1, 2, 4, 5)),
        sds((t, 1024), BF16), sds((t, 4096), BF16), sds((4096, 512), F32),
        sds((512,), F32), sds((8, 2688, 1024), BF16),
        sds((8, 1024, 2688), BF16))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert _kernel_calls(compiled) == 4
    _assert_named(compiled, _EXPERT_KERNELS)


_EXPERT_KERNELS = {"grouped_experts.fwd", "grouped_experts.bwd",
                   "grouped_experts.combine"}

# (form, held, D, F, assignments, tokens) of the two cells' expert layers
_EXPERT_CASES = {
    "nemotron3super": ("relu2", 8, 1024, 2688, 8192 * 22, 8192),
    "qwen3next": ("swiglu", 16, 2048, 512, 8192 * 10, 8192),
}


@pytest.mark.parametrize("part", ["fwd", "bwd", "combine"])
@pytest.mark.parametrize("cell", sorted(_EXPERT_CASES))
def test_grouped_experts_compile_within_the_vmem_the_gate_counts(
        chip, cell, part):
    """Each ``grouped_experts`` kernel at each hybrid cell's published
    widths, with the tile rows, ``F`` tile and call size the program takes
    there and ``vmem_limit_bytes`` set to the working set its gate counts:
    Mosaic needs no more than is counted, the count is under the budget,
    and the gate admits the shape. The grid's bound is a traced scalar."""
    from paddle_tpu.ops import grouped_experts as ge
    from paddle_tpu.parallel import moe

    form, held, d, f, assignments, tokens = _EXPERT_CASES[cell]
    n = 2 if form == "relu2" else 3         # matrices an expert
    rows = moe.block_rows_for(assignments)
    call_rows = moe.call_rows_for(assignments, 512, held, tokens, rows)
    with placed("tpu"):
        plan = ge.kernel_plan(n, d, f, rows, 2, platform=platform_reason())
    assert plan.kernel == "grouped_rows", plan
    if part == "combine":       # of the backward's partial sums of dx
        parts = f // ge.f_tile("bwd", n, d, f, rows, 2)
        dc = ge.column_tile(tokens, d, parts, rows)
        counted = ge._combine_working_set(tokens, dc, parts, rows)
        assert counted <= ge._VMEM_BUDGET
        compiled = _compile(
            chip, lambda tok, tiles, addends, sums: ge._combine_impl(
                tok, tiles, addends, sums, rows=rows, dc=dc, vmem=counted,
                interpret=False),
            sds((call_rows,), I32), sds((), I32),
            sds((parts, call_rows, d), F32), sds((tokens, d), F32))
        _assert_named(compiled, {"grouped_experts.combine"})
        return
    tf = ge.f_tile(part, n, d, f, rows, 2)
    counted = ge._working_set(part, n, d, tf, rows, 2)
    assert counted <= ge._VMEM_BUDGET
    mats = tuple([sds((held, f, d), BF16)] * (n - 1)
                 + [sds((held, d, f), BF16)])
    table = (sds((assignments // rows + held,), I32), sds((1,), I32),
             sds((), I32))
    x, w = sds((call_rows, d), BF16), sds((call_rows, 1), F32)
    statics = dict(form=form, rows=rows, tf=tf, vmem=counted,
                   interpret=False)
    if part == "fwd":
        compiled = _compile(
            chip, lambda be, at, tiles, x, w, mats: ge._fwd_impl(
                be, at, tiles, x, w, mats, **statics), *table, x, w, mats)
    else:
        grads = tuple(sds(m.shape, F32) for m in mats)
        compiled = _compile(
            chip, lambda be, at, tiles, x, dy, w, mats, grads: ge._bwd_impl(
                be, at, tiles, x, dy, w, mats, grads, **statics),
            *table, x, x, w, mats, grads)
    _assert_named(compiled, {"grouped_experts." + part})


@contextlib.contextmanager
def _vmem_limits(limits):
    """Inside the block every Pallas call named in ``limits`` ({kernel
    name: bytes}) is given that ``vmem_limit_bytes`` in place of the
    compiler's 16 MiB of scoped VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not limits:
        yield
        return
    pallas_call = pl.pallas_call

    def limited(kernel, **kwargs):
        limit = limits.get(kwargs.get("name"))
        if limit is not None:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=int(limit))
        return pallas_call(kernel, **kwargs)

    jax.clear_caches()  # a body traced before carries no limit
    pl.pallas_call = limited
    try:
        yield
    finally:
        pl.pallas_call = pallas_call
        jax.clear_caches()


def _attention_grad(b, t, hd, heads, causal=True, with_bias=False,
                    dtype=BF16):
    """(d(loss)/d(q, k, v) of one attention site, its abstract
    arguments)."""
    def loss(q, k, v, g, bias):
        out = fa.flash_attention(q, k, v, heads, bias=bias, causal=causal)
        return jnp.sum(out.astype(F32) * g.astype(F32))

    x = sds((b, t, hd), dtype)
    return jax.grad(loss, argnums=(0, 1, 2)), (
        x, x, x, x, sds((b, t), F32) if with_bias else None)


def _least_vmem(chip, name, shape, causal, with_bias, step=1 << 17):
    """The compiler's own scoped-VMEM count for the kernel ``name`` of one
    attention site: the least ``vmem_limit_bytes``, to ``step`` bytes,
    under which the site compiles."""
    refused, fits = 0, 16 << 20
    while fits - refused > step:
        mid = (refused + fits) // 2 // step * step
        try:
            fn, avals = _attention_grad(*shape, causal, with_bias)
            with _vmem_limits({name: mid}):
                _compile(chip, fn, *avals)
            fits = mid
        except Exception as e:  # noqa: BLE001 — jaxlib's own error type
            assert "vmem" in str(e), e
            refused = mid
    return fits


_GATE_COUNT_CASES = [
    # id, (B, T, H*D, heads), causal, key bias, dtype
    ("seq2048_d64_bias", (2, 2048, 512, 8), False, True, BF16),
    ("seq2048_d64_causal", (2, 2048, 512, 8), True, False, BF16),
    ("seq3072_d128_causal", (2, 3072, 1024, 8), True, False, BF16),
    ("seq1024_d256_causal", (2, 1024, 2048, 8), True, False, BF16),
    ("seq1024_8x40_full_width", (2, 1024, 320, 8), False, True, BF16),
    ("seq2048_d64_f32", (2, 2048, 512, 8), False, True, F32),
]


@pytest.mark.parametrize("shape,causal,with_bias,dtype",
                         [c[1:] for c in _GATE_COUNT_CASES],
                         ids=[c[0] for c in _GATE_COUNT_CASES])
def test_packed_stream_gate_counts_what_mosaic_allocates(
        chip, shape, causal, with_bias, dtype):
    """The packed kernels compile when each is given no more scoped VMEM
    than ``_packed_stream_vmem`` counts for it: the gate's count is at
    least the chip's compiler's own, at every lane-window geometry (two
    heads of 64, one head of 128 or 256, the full width) and close to the
    budget where a shape is admitted close to it. What the gate admits
    and refuses at the benchmark's shapes: the seq-2048 transformer-base
    site fits (ISSUE 29; 9.375M by the compiler, 9.75M by the gate),
    ``qwen3next.train.s8192``'s wide head and 16k tokens do not."""
    b, t, hd, heads = shape
    esize = jnp.dtype(dtype).itemsize
    assert fa._packed_stream_fits(t, t, hd, esize, heads)
    assert fa._packed_stream_fits(2048, 2048, 512, 2, 8)
    assert not fa._packed_stream_fits(8192, 8192, 4096, 2, 16)
    assert not fa._packed_stream_fits(16384, 16384, 512, 2, 8)
    fwd, bwd = fa._packed_stream_vmem(t, t, hd, esize, heads)
    assert max(fwd, bwd) <= fa._STREAM_VMEM_BUDGET
    fn, avals = _attention_grad(*shape, causal, with_bias, dtype)
    with _vmem_limits({"packed_stream.fwd": fwd, "packed_stream.bwd": bwd}):
        compiled = _compile(chip, fn, *avals)
    _assert_named(compiled, {"packed_stream.fwd", "packed_stream.bwd"})


# ---------------------------------------------------------------------------
# fused CE
# ---------------------------------------------------------------------------

def test_fused_ce_compiles(chip):
    """Batch 256 x seq 256 of transformer-base: 1.97e9 logits, past the
    1.5e9 threshold where the fused path engages."""
    t, d, v = 65536, 512, 30000
    assert t * v >= fused_ce._FUSED_MIN_LOGITS

    def loss(x, w, b, y):
        return jnp.sum(fused_ce._fused(x, w, b, y, 0.1))

    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        sds((t, d), BF16), sds((d, v), BF16),
                        sds((v,), BF16), sds((t,), I32))
    assert _kernel_calls(compiled) >= 1  # fwd kernel; bwd is an XLA scan
    _assert_named(compiled, {"fused_ce.fwd"})


# ---------------------------------------------------------------------------
# fused conv + BN + ReLU at ResNet-50 batch-128 bottleneck geometries
# ---------------------------------------------------------------------------

_CONV_CASES = [
    # id, C_in, C_out, kernel, stride, H=W (input), residual
    ("c2_1x1_64to256_res", 64, 256, 1, 1, 56, True),
    ("c2_3x3_64", 64, 64, 3, 1, 56, False),
    ("c3_1x1_s2_256to512", 256, 512, 1, 2, 56, False),
    ("c4_3x3_256", 256, 256, 3, 1, 14, False),
    ("c5_1x1_512to2048_res", 512, 2048, 1, 1, 7, True),
]


@pytest.mark.parametrize("c,o,ksize,stride,hw,with_res",
                         [c[1:] for c in _CONV_CASES],
                         ids=[c[0] for c in _CONV_CASES])
def test_fused_conv_compiles(chip, c, o, ksize, stride, hw, with_res):
    n = 128
    pad = (ksize - 1) // 2
    out_hw = hw // stride
    x_shape, w_shape = (n, c, hw, hw), (o, c, ksize, ksize)
    with placed("tpu"):
        decision = fused_conv.gate(x_shape, w_shape, (stride, stride),
                                   (pad, pad), (1, 1), 1, 2, with_res)
    assert decision.admitted, decision

    def loss(x, w, gamma, beta, mean, var, res):
        y = fused_conv.fused_conv_bn_act(
            x, w, gamma, beta, mean, var, strides=(stride, stride),
            paddings=(pad, pad), eps=1e-5, momentum=0.9, act="relu",
            residual=res)[0]
        return jnp.sum(y.astype(F32))

    ch = sds((o,), F32)
    res = sds((n, o, out_hw, out_hw), BF16) if with_res else None
    compiled = _compile(chip, jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                        sds(x_shape, BF16), sds(w_shape, BF16), ch, ch, ch,
                        ch, res)
    assert _kernel_calls(compiled) == 2  # conv+moments, apply
    _assert_named(compiled, {"fused_conv.fwd", "fused_conv.apply"})


# ---------------------------------------------------------------------------
# scatter: no shape the gate admits is refused by the compiler
# ---------------------------------------------------------------------------

def test_scatter_compiles_at_largest_admitted_shape(chip):
    from chip_smoke import largest_scatter_table

    k = 32
    v = largest_scatter_table(k)  # the shape chip_smoke.py runs
    n = scatter._SMEM_IDS_BYTES // 4  # the id bound, exactly
    with placed("tpu"):
        decision = scatter.gate(v, k, n, "float32")
    assert decision.admitted and decision.kernel == "pallas_rowbin", decision
    compiled = _compile(chip, scatter.scatter_add_rows,
                        sds((v, k), F32), sds((n,), I32), sds((n, k), F32))
    assert _kernel_calls(compiled) == 1
    _assert_named(compiled, {"pallas_rowbin.scatter"})


# ---------------------------------------------------------------------------
# the decode loop's row copies round a sub-batched chunk run (ISSUE 38)
# ---------------------------------------------------------------------------

_SERVED_CACHES = {
    # cell geometry: (bucket, capacity, rung), {tail and type: arrays}
    "opt1p3b_rung256": ((16, 1280, 256), {((2048,), BF16): 48}),
    "glm52_rung256": ((8, 12288, 256), {((576,), BF16): 5,
                                       ((128,), F32): 2}),
}


@pytest.mark.parametrize("geometry,caches", list(_SERVED_CACHES.values()),
                         ids=list(_SERVED_CACHES))
def test_row_copies_compile_and_the_scatter_updates_the_table_in_place(
        chip, geometry, caches):
    """At the serving cells' own cache shapes: the gather's output is the
    sub-batch and nothing more, the scatter aliases the whole table and
    holds no copy of a cache array, and both carry their jit's name (what
    a device trace tells them from the chunk executable by)."""
    from paddle_tpu.serving.decode_batcher import _rows_helpers, chunk_rows

    b, c, k = geometry
    rows = chunk_rows(k, b)
    assert rows < b
    table, sub = {}, {}
    for (tail, dtype), count in caches.items():
        for i in range(count):
            name = "cache_%d_%d" % (len(tail) + tail[0], i)
            table[name] = sds((b, c) + tail, dtype)
            sub[name] = sds((rows, c) + tail, dtype)
    gather, scatter_back = (jitted.__wrapped__
                            for jitted in _rows_helpers(min(k, c)))
    idx, n = sds((rows,), I32), sds((), I32)
    table_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in table.values())
    gathered = _compile(chip, gather, table, idx, n)
    assert "jit_serve_rows_gather" in gathered.as_text()
    memory = gathered.memory_analysis()
    assert memory.alias_size_in_bytes == 0
    assert memory.output_size_in_bytes <= table_bytes * rows // b + (1 << 16)
    scattered = _compile(chip, scatter_back, table, sub, idx, idx, n,
                         donate_argnums=(0,))
    assert "jit_serve_rows_scatter" in scattered.as_text()
    memory = scattered.memory_analysis()
    assert memory.alias_size_in_bytes == table_bytes
    assert memory.temp_size_in_bytes < table_bytes // len(table)
    whole = re.compile(r"= \w+\[%d,%d,\d+\][^ ]* copy\(" % (b, c))
    assert not whole.search(scattered.as_text())


@pytest.mark.parametrize("n", [scatter._SMEM_IDS_BYTES // 4 + 1,
                               32768 * 26],
                         ids=["one_past_bound", "deepfm_bench_ids"])
def test_scatter_gate_bounds_prefetched_ids(n):
    """Past the SMEM bound the plan falls to the XLA scatter with an
    ``smem`` reason. 851,968 ids is what DeepFM's bench batch sends, and
    the compiler refused it: 3.4 MB of ids into 1 MiB of SMEM."""
    with placed("tpu"):
        decision = scatter.gate(50000, 32, n, "float32")
    assert not decision.admitted and decision.kernel == "xla_at_add"
    assert decision.blocked_only_by("smem"), decision


# ---------------------------------------------------------------------------
# names: what a trace on the chip can tell a kernel by (ISSUE 24)
# ---------------------------------------------------------------------------

def _conv_infer():
    def infer(x, w, gamma, beta, mean, var):
        return fused_conv.fused_conv_bn_act(
            x, w, gamma, beta, mean, var, strides=(1, 1), paddings=(1, 1),
            eps=1e-5, momentum=0.9, act="relu", is_test=True)[0]

    ch = sds((64,), F32)
    return infer, (sds((8, 64, 56, 56), BF16), sds((64, 64, 3, 3), BF16),
                   ch, ch, ch, ch)


def _experts_grad():
    from paddle_tpu.parallel import moe

    def experts(x, router, wg, wu, wd):
        return jnp.sum(moe.routed_experts(x, router, wg, wu, wd, 2, 0)[0])

    return jax.value_and_grad(experts, argnums=(0, 2, 3, 4)), (
        sds((1024, 128), BF16), sds((128, 8), F32), sds((4, 256, 128), BF16),
        sds((4, 256, 128), BF16), sds((4, 128, 256), BF16))


def _mimo_chunk():
    """``mimo-v2-flash``'s chunk program of 1024 lanes over one slot row at
    the rung of 16384, of its two full layers alone (layer 0 with the dense
    feed-forward, layer 11 with its experts): the configuration's own
    widths, so the gate admits the kernel as it does in the cell."""
    import json

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import mimo_v2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2-flash.json")) as f:
        body = json.load(f)
    sizes = dict({k: body[k] for k in body["builder_keys"]},
                 layers_held=[0, 11])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch, spec = mimo_v2.mimo_v2_chunk(dtype="bfloat16", **sizes)
    gb = main.global_block()
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: sds(tuple(gb.var(n).shape),
                    BF16 if gb.var(n).dtype == "bfloat16"
                    else np.dtype(gb.var(n).dtype)) for n in persist}
    lanes, c = 1024, 16384
    feed = {spec["token_feed"]: sds((1, lanes), I32),
            spec["pos_feed"]: sds((1, lanes), I32)}
    for cf in spec["cache_feeds"]:
        feed[cf["feed"]] = sds((1, cf.get("capacity") or c)
                               + tuple(cf["tail"]), BF16)
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    return build_step_fn(main, [v.name for v in fetch], persist,
                         infer_only=True), (state, feed, rng)


_NAME_CASES = [
    # family, (function, abstract arguments), the names its calls carry;
    # small shapes: a name does not depend on the size
    ("dense_vmem", lambda: _attention_grad(8, 128, 256, 2),
     {"dense_vmem.fwd", "dense_vmem.bwd"}),
    ("packed_stream", lambda: _attention_grad(2, 1024, 256, 2),
     {"packed_stream.fwd", "packed_stream.bwd"}),
    ("head_split_stream", lambda: _attention_grad(1, 4096, 512, 8),
     {"head_split_stream.fwd", "head_split_stream.bwd"}),
    ("fused_conv_infer", _conv_infer, {"fused_conv.infer"}),
    ("grouped_experts", lambda: _experts_grad(), _EXPERT_KERNELS),
    ("mimo_v2_chunk", _mimo_chunk, {"cache_chunk.fwd"}),
]


@pytest.mark.parametrize("build,names", [c[1:] for c in _NAME_CASES],
                         ids=[c[0] for c in _NAME_CASES])
def test_compiled_hlo_carries_each_kernel_family_by_name(chip, build, names):
    """The v5e-compiled step names every Pallas call ``<family>.<part>``,
    in the instruction's own name and in its op_name path, so that a trace
    tells forward from backward and one family from another. (The fused
    CE, layer norm, train-mode conv and scatter kernels are asserted where
    they are compiled above.)"""
    fn, avals = build()
    _assert_named(_compile(chip, fn, *avals), names)


# ---------------------------------------------------------------------------
# whole train steps (slow): every BASELINE config through build_step_fn
# ---------------------------------------------------------------------------

def _abstract_step(build):
    """A training program as a BASELINE step is built (``build()`` returns
    the model spec and the batch; Adam runs under
    ``fluid.amp.decorate``), as (abstract (state, feed, rng), program,
    loss name, persistable names) — no array is ever made."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import build_step_fn

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        spec, batch = build()
        fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=1e-4)).minimize(spec.loss)
    rng = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    init_names = sorted({v.name for v in startup.list_vars()
                         if v.persistable})
    with placed("tpu"):
        _, state, _ = jax.eval_shape(
            build_step_fn(startup, (), init_names), {}, {}, rng)
    persist = sorted({v.name for v in main.list_vars() if v.persistable})
    state = {n: state[n] for n in persist if n in state}
    gb = main.global_block()
    feed = {}
    for name, value in spec.sample_batch(2, np.random.RandomState(0)).items():
        value = np.asarray(value)
        dtype = gb.var(name).dtype if gb.has_var(name) else value.dtype
        feed[name] = sds((batch,) + value.shape[1:],
                         jax.dtypes.canonicalize_dtype(np.dtype(dtype)))
    return (state, feed, rng), main, spec.loss.name, persist


_HBM_BYTES = 16 * 1024 ** 3

def _step_kernel_names(compiled):
    """{``<family>.<part>``: sites} of a compiled step's Pallas calls, by
    the instruction's own name (``dense_vmem.bwd.20``: what a device event
    on the chip is called)."""
    sites = {}
    for instruction, _ in _kernel_names(compiled):
        name = re.sub(r"\.\d+$", "", instruction)
        sites[name] = sites.get(name, 0) + 1
    return sites


def test_transformer_base_step_keeps_its_kernel_sites_and_traces_each_once(
        chip):
    """The benchmark's ``tbase.train.s256`` step (128 x 256, no dropout),
    compiled for one chip: 18 forward and 18 backward attention sites,
    each instruction still called ``dense_vmem.<part>.<n>``, from at most
    three traced bodies a part (encoder self, decoder causal self,
    decoder cross; ISSUE 25)."""
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

    jax.clear_caches()  # "once per process": not what a test before traced
    avals, program, loss, persist = _abstract_step(
        lambda: (transformer.transformer_base(
            src_vocab=30000, trg_vocab=30000, d_model=512, d_ff=2048,
            n_head=8, n_layer=6, dropout_rate=0.0, label_smooth_eps=0.1,
            seq_len=256), 128))
    step = build_step_fn(program, (loss,), persist)
    with collect_traces() as bodies:
        compiled = _compile(chip, step, *avals, donate_argnums=(0,))
    assert _kernel_calls(compiled) == 36
    assert _step_kernel_names(compiled) == {"dense_vmem.fwd": 18,
                                            "dense_vmem.bwd": 18}
    traces = tally_traces(bodies)
    # each forward site is traced in the forward pass and in the replay
    assert {k: v["traced"] + v["reused"] for k, v in traces.items()} == {
        "dense_vmem.fwd": 36, "dense_vmem.bwd": 18}
    assert all(1 <= v["traced"] <= 3 for v in traces.values()), traces


def test_nemotron3_super_step_compiles_and_fits_the_chip(chip):
    """The benchmark's ``nemotron3super.train.s8192`` step (1 x 8192, the
    share of a 64-chip-a-layer deployment at published widths: layers 36-46,
    an eighth of every mixer's heads, 8 of 512 experts, 16384 rows of the
    vocabulary), built from the configuration's own ``builder_args`` and
    compiled for one chip: 508M parameters, their Adam state aliased in
    place, and the whole under the chip's memory."""
    import json

    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.models import nemotron_h

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        args = json.load(f)["builder_args"]
    avals, program, loss, persist = _abstract_step(
        lambda: (nemotron_h.nemotron_h(seq_len=8192, **args), 1))
    params = sum(int(np.prod(p.shape))
                 for p in program.global_block().all_parameters()
                 if p.trainable)
    assert abs(params - 508e6) < 0.01 * 508e6
    step = build_step_fn(program, (loss,), persist)
    compiled = _compile(chip, step, *avals, donate_argnums=(0,))
    mem = compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    print("nemotron3super step: %d tpu_custom_call, %.2f GB temporaries + "
          "%.2f GB arguments" % (_kernel_calls(compiled),
                                 mem.temp_size_in_bytes / 1e9,
                                 mem.argument_size_in_bytes / 1e9))
    assert 12 * params <= mem.argument_size_in_bytes < 12.1 * params
    assert mem.alias_size_in_bytes >= 12 * params       # updated in place
    assert 0.25 * _HBM_BYTES < need < 0.8 * _HBM_BYTES
    # the attention layer's four heads of 128 run a Pallas kernel, forward
    # and backward; the head's fused cross-entropy too
    names = _step_kernel_names(compiled)
    assert any(n.endswith(".fwd") for n in names) and any(
        n.endswith(".bwd") for n in names), names


_STEP_CASES = [
    # id, model, seq override, its Pallas calls by name, attention plan
    ("transformer_b128_s256", "transformer", None,
     {"dense_vmem.fwd": 18, "dense_vmem.bwd": 18}, "dense_vmem"),
    ("bert_b128_s128", "bert", None,
     {"dense_vmem.fwd": 12, "dense_vmem.bwd": 12}, "dense_vmem"),
    ("resnet50_b128", "resnet50", None,
     {"fused_conv.fwd": 49, "fused_conv.apply": 49}, None),
    # the [100000, 32] fused table is over the scatter kernel's VMEM
    # budget and its 851,968 ids over the SMEM bound: XLA scatter
    ("deepfm_b32768", "deepfm", None, {}, None),
    # ISSUE 29: every site copy-free, a 128-lane window a program
    ("transformer_b16_s2048", "transformer", 2048,
     {"packed_stream.fwd": 18, "packed_stream.bwd": 18}, "packed_stream"),
]


@pytest.mark.slow
@pytest.mark.parametrize("model,seq,kernels,attn_plan",
                         [c[1:] for c in _STEP_CASES],
                         ids=[c[0] for c in _STEP_CASES])
def test_whole_train_step_compiles(chip, model, seq, kernels, attn_plan):
    from paddle_tpu import models
    from paddle_tpu.core.executor import build_step_fn

    avals, program, loss, persist = _abstract_step(
        lambda: models.baseline(model, seq_len=seq))
    step = build_step_fn(program, (loss,), persist)
    limits = {}
    if attn_plan == "packed_stream":
        # a kernel's need depends on the program round it: inside this step
        # the packed kernels get by on 1 MiB over what the gate counts for
        # them (found: backward 10.25M against 9.375M alone, forward 5.0M
        # against 4.25M), well inside the 3 MiB the budget leaves free
        fwd, bwd = fa._packed_stream_vmem(seq, seq, 512, 2, 8)
        limits = {"packed_stream.fwd": fwd + (1 << 20),
                  "packed_stream.bwd": bwd + (1 << 20)}
        assert max(limits.values()) <= fa._STREAM_VMEM_BUDGET
    with _vmem_limits(limits):
        compiled = _compile(chip, step, *avals, donate_argnums=(0,))
    mem = compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    print("%s seq=%s: %d tpu_custom_call, %.2f GB temporaries + %.2f GB "
          "arguments" % (model, seq, _kernel_calls(compiled),
                         mem.temp_size_in_bytes / 1e9,
                         mem.argument_size_in_bytes / 1e9))
    assert need < _HBM_BYTES
    assert _step_kernel_names(compiled) == kernels
    assert _kernel_calls(compiled) == sum(kernels.values())
    if attn_plan is not None:
        from chip_smoke import kernel_plans

        plans = kernel_plans(program)["flash_attention"]
        assert set(plans) == {attn_plan}, plans
    if limits:
        # the record: the compiler's count for the backward of one site
        # compiled alone, beside the gate's own and the budget
        alone = max(_least_vmem(chip, "packed_stream.bwd",
                                (2, seq, 512, 8), causal, not causal)
                    for causal in (False, True))
        print("packed_stream.bwd at T=%d: %.3f MiB of scoped VMEM by the "
              "compiler (a site alone), %.3f MiB by the gate, budget %.0f "
              "MiB; the step compiles with 1 MiB over the gate's count as "
              "the limit" % (seq, alone / 2**20, bwd / 2**20,
                             fa._STREAM_VMEM_BUDGET / 2**20))
        assert alone <= bwd


@pytest.mark.slow
def test_bert_dygraph_train_step_compiles(chip):
    """BASELINE config 4: BERT-base through the dygraph build, the jitted
    functional train step."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert_dygraph

    batch, seq_len = 128, 128
    model, _, _, _ = bert_dygraph.bert_base_dygraph(seq_len=seq_len,
                                                    amp=True)
    feeds = bert_dygraph.sample_batch(2, seq_len, 30522,
                                      np.random.RandomState(0))
    with fluid.dygraph.guard():
        model(*feeds)  # materialises the lazily built parameters
    step, params, opt_state = bert_dygraph.make_train_step(model)
    feed_avals = tuple(
        sds((batch,) + np.asarray(f).shape[1:],
            jax.dtypes.canonicalize_dtype(np.asarray(f).dtype))
        for f in feeds)
    compiled = _compile(
        chip, step, jax.eval_shape(lambda: params),
        jax.eval_shape(lambda: opt_state),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)), *feed_avals,
        donate_argnums=(0, 1))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < _HBM_BYTES
    assert _kernel_calls(compiled) > 0


@pytest.mark.slow
def test_meshed_train_step_compiles_for_four_chips(topology):
    """What ``chip_smoke.py --multichip`` runs on the mesh: transformer-base
    (no dropout, global batch 128) under ``with_data_parallel`` over a
    ('dp', 'mp') = 2x2 mesh of the four described chips, through the
    Executor's own sharding rules. The partitioner that matters is the
    chip's: the mp-annotated FFN weight stays sharded, the gradient
    all-reduce is there, and no Pallas call is (the gates refuse a meshed
    step)."""
    import paddle_tpu as fluid
    from jax.sharding import Mesh
    from paddle_tpu import models
    from paddle_tpu.core.executor import build_step_fn
    from paddle_tpu.parallel import sharding_check

    mesh = Mesh(np.array(topology.devices).reshape(2, 2), ("dp", "mp"))
    (state, feed, rng), program, loss, persist = _abstract_step(
        lambda: (models.transformer.transformer_base(
            seq_len=256, dropout_rate=0.0), 128))
    in_sh, out_sh = fluid.Executor(fluid.CPUPlace())._mesh_shardings(
        program, tuple(sorted(feed)), (loss,), tuple(sorted(state)),
        persist, mesh, "dp", None)

    def on(aval, sharding):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=sharding)

    step = build_step_fn(program, (loss,), persist)
    with placed("tpu", meshed=True):
        lowered = jax.jit(step, donate_argnums=(0,), in_shardings=in_sh,
                          out_shardings=out_sh).lower(
            *jax.tree.map(on, (state, feed, rng), in_sh))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    print("2x2 mesh: %d all-reduce, %.2f GB temporaries + %.2f GB arguments "
          "per chip" % (hlo.count(" all-reduce("),
                        mem.temp_size_in_bytes / 1e9,
                        mem.argument_size_in_bytes / 1e9))
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < _HBM_BYTES
    assert "all-reduce" in hlo
    assert "tpu_custom_call" not in hlo
    sharding_check.assert_param_sharded(hlo, "enc0_ffn_fc1.w", (512, 2048))
    from chip_smoke import kernel_plans

    assert set(kernel_plans(program)["flash_attention"]) == {
        "reference[platform]"}
