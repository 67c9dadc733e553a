"""chip_smoke.py — the quickest proof that paddle_tpu still runs on the chip.

    python chip_smoke.py               one TPU chip: train, kernels, configs, serve
    python chip_smoke.py --multichip   four chips: one chip vs a ('dp','mp')=2x2 mesh
    python chip_smoke.py --rehearse [--multichip]
                                       CPU, tiny shapes, kernels interpreted:
                                       same phases and control flow, never a pass

Everything goes through the entry points a user calls (``import paddle_tpu
as fluid``, ``fluid.Executor(fluid.TPUPlace(0))``, ``fluid.amp.decorate``,
``paddle_tpu.serving``), in ONE process: a chip belongs to one process, so
this script starts no child. Weights and data are random, from ``--seed``.

Output: one JSON object per phase on its own line, then as the LAST line
exactly ``{"ok": ..., "device": {"platform", "kind", "count"}}``. The run
exits 0 only if every phase passed. Without ``--rehearse`` it refuses to run
where JAX finds no TPU: exit 2, nothing on stdout. No number printed here is
a measurement: this is a smoke, and the timings it prints (first-step
seconds, wall seconds) only say where a run spends its 20 minutes — about 11
of them cold and 4.5 with a warm compile cache, nearly all of it compiling on
the chip's shared host CPU.

Tolerances (bf16 MXU operands, f32 accumulation), against each kernel's
in-repo reference:
  attention   |out - ref| < 3e-2, |grad - ref| < 6e-2 (inputs ~N(0, 0.3))
  fused conv  max|y - ref| / max|ref| < 3e-2; grads |a - ref|_2 / |ref|_2
              < 1e-1 (elements a rounding error from the ReLU's zero flip)
  fused CE    |loss - ref| < 5e-2 (losses ~10), grads relative < 5e-2
  scatter     |out - ref| < 1e-4 (f32 adds in another order)
  state-space scan, routed ReLU^2 experts
              |a - ref|_2 / |ref|_2 < 3e-2, output and every gradient
  dropout     keep rate within 5 sigma; backward's dV equals the one
              predicted from the forward's OBSERVED mask (rate 0.5 and a
              power-of-two T make every term exact) to 1e-4
  2x2 mesh    |loss_mesh - loss_chip| <= 2e-3 * loss_chip at each of 3 steps
              (Pallas bf16 attention on the chip, f32-softmax reference
              attention under the mesh)
"""

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import traceback

T_START = time.time()


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(record):
    print(json.dumps(record), flush=True)


def log(msg):
    print("[chip_smoke %6.1fs] %s" % (time.time() - T_START, msg),
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# sizes: the real ones, and the tiny ones of --rehearse
# ---------------------------------------------------------------------------

def sizes(rehearse):
    if not rehearse:
        return {
            # transformer_base() at its defaults: d_model 512, d_ff 2048,
            # 8 heads, 6+6 layers, vocab 30000: models.baseline's
            # "transformer"
            "train": dict(model_kw={}, seq_len=256, batch=128, steps=5),
            "flash": [
                # name, B, T, H*D, heads, causal, key bias
                ("bert_t128", 128, 128, 768, 12, False, True),
                ("transformer_t256_enc", 128, 256, 512, 8, False, True),
                ("transformer_t256_dec", 128, 256, 512, 8, True, False),
                ("stream_t1024", 32, 1024, 512, 8, False, True),
                ("seq2048_enc", 16, 2048, 512, 8, False, True),
                ("seq2048_dec", 16, 2048, 512, 8, True, False),
            ],
            # T, heads, head dim of the dropout-mask probes (f32)
            "dropout": [(128, 1, 128), (1024, 1, 128), (2048, 8, 64)],
            "conv": [
                # name, N, C_in, C_out, kernel, stride, H=W, residual
                ("c2_1x1_64to256_res", 128, 64, 256, 1, 1, 56, True),
                ("c2_3x3_64", 128, 64, 64, 3, 1, 56, False),
                ("c3_1x1_s2_256to512", 128, 256, 512, 1, 2, 56, False),
                ("c4_3x3_256", 128, 256, 256, 3, 1, 14, False),
                ("c5_1x1_512to2048_res", 128, 512, 2048, 1, 1, 7, True),
            ],
            "ce": dict(t=65536, d=512, v=30000, ref_chunk=8192),
            "scatter": dict(k=32, v=None, n=None),  # None: gate's bounds
            # nemotron3super.train.s8192's shapes: 16 Mamba-2 heads of 64
            # in one group of state 128; 8 of 512 experts of 2688 in a
            # latent of 1024, 22 picks, the router at the model's 4096;
            # then qwen3next.train.s8192's: 16 of 512 SwiGLU experts of
            # 512 at the model's 2048, 10 picks
            "ssd": dict(t=8192, heads=16, p=64, groups=1, n=128, chunk=128),
            # a decode step's attention at opt1p3b.serve.chat*'s shape
            # (plain heads) and at a full layer's of
            # mimo2flash.serve.mixedlen.sat (64 heads on 4, a sink)
            "cache_step": [
                dict(rows=16, c=1280, heads=32, kv_heads=32, dk=64, dv=64,
                     sink=False),
                dict(rows=16, c=16384, heads=64, kv_heads=4, dk=192, dv=128,
                     sink=True)],
            # a verifying step's latent attention at
            # glm47flash.serve.reason.sat's shape (two lanes of 20 heads
            # over rows of 512 + 64)
            "latent_step": dict(rows=32, c=4096, lanes=2, heads=20, r=512,
                                rope=64, nope=192, v=256),
            # a step's EVA attention at evabyte.serve.bytes.sat's shape
            # (32 heads of 128 over a window of 2048 slots and the 2048
            # summaries of a rung of 32768)
            "eva_step": dict(rows=16, window=2048, chunk=16, entries=2048,
                             heads=32, d=128),
            # a chunk run's attention at a full layer's shape of
            # mimo2flash.serve.mixedlen.sat (one row of 1024 lanes)
            "cache_chunk": dict(rows=1, lanes=1024, c=16384, heads=64,
                                kv_heads=4, dk=192, dv=128, sink=False),
            "experts": [
                dict(form="relu2", t=8192, d_model=4096, latent=1024,
                     f=2688, experts=512, held=8, top_k=22, score="sigmoid",
                     scale=5.0),
                dict(form="swiglu", t=8192, d_model=2048, latent=0, f=512,
                     experts=512, held=16, top_k=10, score="softmax",
                     scale=1.0)],
            # models.baseline's on-chip widths and batches. Depth is cut to 2
            # layers where a layer repeats (BERT 12, seq-2048 6+6): the
            # host that compiles for the chip is shared and slow, the
            # full-depth steps alone took 540 s of this script's 1200, and
            # a second layer already repeats every shape of the first.
            "configs": dict(
                bert=dict(kw=dict(seq_len=128, n_layer=2), batch=128),
                resnet50=dict(kw=dict(depth=50), batch=128),
                deepfm=dict(kw={}, batch=32768),
                seq2048=dict(kw=dict(seq_len=2048, dropout_rate=0.1,
                                     n_layer=2), batch=16),
                bert_dygraph=dict(kw=dict(seq_len=128, n_layer=2),
                                  batch=128)),
            "serve": dict(
                lm=dict(vocab=30000, d_model=512, d_ff=2048, n_head=8,
                        n_layer=6, ctx_cap=1024, pos_cap=1024),
                replay_len=512, ladder=(8,), seq_ladder=(256, 1024),
                prefill_ladder=(64,), long_prompt=300, max_new=8),
            "multichip": dict(model_kw={}, seq_len=256, batch=128, steps=3,
                              ffn_shape=(512, 2048)),
        }
    tiny = dict(src_vocab=500, trg_vocab=500, d_model=64, d_ff=128,
                n_head=4, n_layer=2)
    return {
        "train": dict(model_kw=tiny, seq_len=32, batch=8, steps=5),
        "flash": [
            ("dense_t24", 2, 24, 32, 2, False, True),
            ("dense_t24_causal", 2, 24, 32, 2, True, False),
            ("stream_t600", 1, 600, 32, 2, False, True),
        ],
        "dropout": [(16, 1, 16), (64, 2, 16)],
        "conv": [
            ("1x1_res", 2, 8, 16, 1, 1, 8, True),
            ("3x3", 2, 8, 8, 3, 1, 8, False),
            ("1x1_s2", 2, 8, 16, 1, 2, 8, False),
        ],
        "ce": dict(t=256, d=32, v=300, ref_chunk=64),
        "scatter": dict(k=16, v=600, n=2048),
        "ssd": dict(t=70, heads=4, p=8, groups=2, n=16, chunk=16),
        "cache_step": [
            dict(rows=3, c=256, heads=16, kv_heads=16, dk=16, dv=16,
                 sink=False),
            dict(rows=3, c=256, heads=16, kv_heads=2, dk=64, dv=64,
                 sink=True)],
        "latent_step": dict(rows=4, c=256, lanes=2, heads=20, r=128, rope=64,
                            nope=24, v=32),
        "eva_step": dict(rows=4, window=256, chunk=16, entries=512, heads=16,
                         d=16),
        "cache_chunk": dict(rows=2, lanes=256, c=512, heads=8, kv_heads=2,
                            dk=192, dv=128, sink=True),
        "experts": [
            dict(form="relu2", t=96, d_model=32, latent=128, f=256,
                 experts=16, held=4, top_k=5, score="sigmoid", scale=2.5),
            dict(form="swiglu", t=96, d_model=128, latent=0, f=128,
                 experts=16, held=4, top_k=5, score="softmax", scale=1.0)],
        "configs": dict(
            bert=dict(kw=dict(vocab_size=1000, seq_len=32, d_model=128,
                              d_ff=256, n_layer=2), batch=4),
            resnet50=dict(kw=dict(depth=50, class_num=10,
                                  image_shape=(3, 64, 64)), batch=2),
            deepfm=dict(kw=dict(sparse_feature_dim=1000,
                                hidden_sizes=(64, 64)), batch=16),
            seq2048=dict(kw=dict(seq_len=128, dropout_rate=0.1, **tiny),
                         batch=2),
            bert_dygraph=dict(kw=dict(vocab_size=1000, seq_len=32,
                                      d_model=128, d_ff=256, n_layer=2,
                                      n_head=4), batch=4)),
        "serve": dict(
            lm=dict(vocab=64, d_model=32, d_ff=64, n_head=2, n_layer=2,
                    ctx_cap=64, pos_cap=64),
            replay_len=64, ladder=(8,), seq_ladder=(16, 64),
            prefill_ladder=(8,), long_prompt=20, max_new=4),
        "multichip": dict(model_kw=tiny, seq_len=32, batch=8, steps=3,
                          ffn_shape=(64, 128)),
    }


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

# a Pallas call in a compiled step's HLO text
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def kernel_plans(program):
    """{op type: [kernel, ...]} as recorded at trace time by every op that
    chose between a Pallas kernel and a fallback
    (``op.attrs['_kernel_choice']``), the autodiff replay lists included.
    A demoted or refused plan carries the checks that blocked the
    preferred kernel, as in ``xla_at_add[vmem,smem]``."""
    found = {}

    def walk(ops):
        for op in ops:
            choice = op.attrs.get("_kernel_choice")
            if choice is not None:
                blocked = [r["check"] for r in choice["reasons"]
                           if r["blocking"]]
                found.setdefault(op.type, []).append(choice["kernel"] + (
                    "[%s]" % ",".join(blocked) if blocked else ""))
            for value in op.attrs.values():
                if isinstance(value, list) and value and \
                        hasattr(value[0], "attrs"):
                    walk(value)

    walk(program.global_block().ops)
    return found


def cache_record(compile_cache):
    entries, nbytes = compile_cache.entries()
    return {"compile_cache_dir": compile_cache.directory(),
            "compile_cache_entries": entries,
            "compile_cache_bytes": nbytes}


def largest_scatter_table(k):
    """Largest table height (by the thousand) whose packed [V, k] f32
    layout the scatter kernel's VMEM gate admits."""
    from paddle_tpu.ops import scatter

    v = 200_000
    while not scatter.gate(v, k, 1024, "float32", static_only=True):
        v -= 1000
    return v


def tally(names):
    return {n: names.count(n) for n in sorted(set(names))}


def build_train_program(fluid, build, seed):
    """A training program: the model, then Adam(1e-4) under
    ``fluid.amp.decorate``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    fluid.unique_name.switch()
    with fluid.program_guard(main, startup):
        spec = build()
        fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=1e-4)).minimize(spec.loss)
    return main, startup, spec


def run_steps(ctx, main, startup, spec, batch, steps, program=None):
    """Startup, then ``steps`` steps on one fixed staged batch. Returns
    (losses, seconds until the first step returned, executor, scope)."""
    import jax
    import numpy as np

    fluid = ctx["fluid"]
    exe = fluid.Executor(ctx["place"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.time()
        exe.run(startup)
        log("startup program ran in %.1fs" % (time.time() - t0))
        feed = spec.sample_batch(batch, np.random.RandomState(ctx["seed"]))
        # staged once: the loop must not re-ship the batch over the host
        # link every step
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        losses = []
        t0 = time.time()
        first_s = None
        for _ in range(steps):
            loss, = exe.run(program or main, feed=feed,
                            fetch_list=[spec.loss], return_numpy=False)
            jax.block_until_ready(loss)
            if first_s is None:
                first_s = time.time() - t0
            losses.append(float(np.asarray(loss).reshape(-1)[0]))
    return losses, first_s, exe, scope


def max_err(a, b):
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def rel_err(a, b):
    """max|a - b| / max|b|: one number for tensors of any magnitude."""
    import jax.numpy as jnp

    scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-30
    return max_err(a, b) / scale


def l2_err(a, b):
    """|a - b|_2 / |b|_2: for gradients behind a ReLU, where a handful of
    elements whose pre-activation is a rounding error from zero take the
    other branch and an element-wise maximum would only see them."""
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def release():
    """Drop what the last phase left on the device."""
    import jax

    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# phase: train — the flagship at full width through the main path
# ---------------------------------------------------------------------------

def phase_train(ctx):
    import jax
    import numpy as np

    fluid, cfg = ctx["fluid"], ctx["sizes"]["train"]
    from paddle_tpu import models

    main, startup, spec = build_train_program(
        fluid, lambda: models.transformer.transformer_base(
            seq_len=cfg["seq_len"], dropout_rate=0.1, **cfg["model_kw"]),
        ctx["seed"])
    losses, compile_s, exe, scope = run_steps(
        ctx, main, startup, spec, cfg["batch"], cfg["steps"])
    check(all(np.isfinite(losses)), "non-finite loss: %s" % losses)
    check(losses[-1] < losses[0], "loss did not fall: %s" % losses)

    params = [p.name for p in main.global_block().all_parameters()]
    platforms = sorted({d.platform for n in params
                        for d in scope.get(n).devices()})
    check(platforms == [ctx["platform"]],
          "parameters live on %s, expected %s" % (platforms,
                                                  ctx["platform"]))
    plans = kernel_plans(main).get("flash_attention", [])
    check(plans, "no attention site recorded a kernel plan")
    # the text of the executable that ran, kept from its one staging:
    # the Pallas calls XLA left in the step
    n_custom = exe.lowered_hlo_text().count(TPU_CUSTOM_CALL)
    if ctx["on_chip"]:
        check(n_custom > 0, "no tpu_custom_call in the lowered train step")
        check(not any(p.startswith("reference") for p in plans),
              "attention sites off the Pallas path: %s" % tally(plans))
    stats = ctx["device"].memory_stats() or {}
    return {
        "model": "transformer_base", "seq_len": cfg["seq_len"],
        "batch": cfg["batch"], "n_params": len(params),
        "first_step_s": round(compile_s, 1),  # startup excluded; compile
        "losses": [round(x, 4) for x in losses],
        "param_platforms": platforms,
        "tpu_custom_calls": n_custom,
        "attention_plans": tally(plans),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


# ---------------------------------------------------------------------------
# phase: kernels — every default-on Pallas family against its reference
# ---------------------------------------------------------------------------

def _flash_case(ctx, name, b, t, hd, heads, causal, with_bias):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import flash_attention as fa

    d = hd // heads
    rng = np.random.RandomState(ctx["seed"])

    def rand(scale):
        return jnp.asarray(rng.randn(b, t, hd) * scale, jnp.bfloat16)

    q, k, v, g = rand(0.3), rand(0.3), rand(0.3), rand(0.1)
    bias = (jnp.asarray(np.where(rng.rand(b, t) > 0.2, 0.0, -1e9),
                        jnp.float32) if with_bias else None)
    plan = fa.plan_for(q, k, bias, heads, causal, 0.0, None)
    if ctx["on_chip"]:
        check(plan.kernel != "reference", "%s fell back: %s" % (name, plan))

    def kernel_loss(q, k, v, bias, g):
        out = fa.flash_attention(q, k, v, heads, bias=bias, causal=causal)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    def ref_loss(q, k, v, bias, g):
        rows = q.shape[0]

        def split(x):
            return x.reshape(rows, t, heads, d).transpose(0, 2, 1, 3)

        out = fa.mha_reference(
            split(q), split(k), split(v),
            None if bias is None else bias[:, None, None, :], causal)
        out = out.transpose(0, 2, 1, 3).reshape(rows, t, hd)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    (_, out), grads = jax.jit(jax.value_and_grad(
        kernel_loss, argnums=argnums, has_aux=True))(q, k, v, bias, g)
    # batch rows are independent: the reference (which materialises the
    # [rows, H, T, T] logits) takes the first few only
    r = min(b, 4)
    (_, out_ref), grads_ref = jax.jit(jax.value_and_grad(
        ref_loss, argnums=argnums, has_aux=True))(
            q[:r], k[:r], v[:r], None if bias is None else bias[:r], g[:r])
    fwd = max_err(out[:r], out_ref)
    bwd = max(max_err(a[:r], c) for a, c in zip(grads, grads_ref))
    check(fwd < 3e-2, "%s forward error %g" % (name, fwd))
    check(bwd < 6e-2, "%s backward error %g" % (name, bwd))
    return {"case": name, "plan": plan.kernel, "fwd_err": fwd,
            "bwd_err": bwd}


def _dropout_probe(ctx, t, heads, d):
    """Make the in-kernel dropout mask observable: q = 0 gives uniform
    attention p = 1/T, and v[k, h*D + c] = [k == c] turns output column c
    of head h into keep_h(q, key=c) * 2/T (rate 0.5). The backward must
    regenerate the SAME mask: with loss = sum(out), dV[k, h*D + c] is the
    number of queries that kept key k, over T/2, for every c — predictable
    from the observed forward mask for k < D."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import flash_attention as fa

    rate, hd = 0.5, heads * d
    q0 = jnp.zeros((1, t, hd), jnp.float32)
    eye = np.zeros((t, heads, d), np.float32)
    eye[np.arange(min(t, d)), :, np.arange(min(t, d))] = 1.0
    v_eye = jnp.asarray(eye.reshape(1, t, hd))
    key = jax.random.key(ctx["seed"] + 7, impl=ctx["rng_impl"])
    plan = fa.plan_for(q0, q0, None, heads, False, rate, key)

    def fwd(v, q0, key):
        return fa.flash_attention(q0, q0, v, heads, causal=False,
                                  dropout_rate=rate, rng=key)

    grad_v = jax.jit(jax.grad(lambda v, q0, key: jnp.sum(fwd(v, q0, key))))
    out = np.asarray(jax.jit(fwd)(v_eye, q0, key))[0].reshape(t, heads, d)
    mask = out * (t * (1.0 - rate))          # mask[q, h, c] = keep_h(q, c)
    binary = bool(np.all((np.abs(mask - 1) < 1e-3) | (np.abs(mask) < 1e-3)))
    n_keys = min(t, d)
    keep_rate = float((mask[:, :, :n_keys] > 0.5).mean())
    sigma = (rate * (1 - rate) / (t * heads * n_keys)) ** 0.5
    dv = np.asarray(grad_v(v_eye, q0, key))[0].reshape(t, heads, d)
    dv_again = np.asarray(grad_v(v_eye, q0, key))[0].reshape(t, heads, d)
    # predicted[k, h] from the forward's observed mask, for keys k < D
    predicted = (mask[:, :, :n_keys] > 0.5).sum(axis=0).T \
        / (t * (1.0 - rate))
    got = dv[:n_keys]                        # [k, h, c], equal over c
    reuse = float(np.max(np.abs(got - predicted[:, :, None])))
    check(binary, "dropout T=%d: forward output is not a 0/1 mask" % t)
    check(abs(keep_rate - (1 - rate)) < 5 * sigma,
          "dropout T=%d: keep rate %g" % (t, keep_rate))
    check(float(np.max(np.abs(dv - dv_again))) == 0.0,
          "dropout T=%d: backward is not deterministic" % t)
    check(reuse < 1e-4, "dropout T=%d: backward used another mask than "
          "the forward (error %g)" % (t, reuse))
    return {"case": "dropout_t%d_h%d" % (t, heads), "plan": plan.kernel,
            "keep_rate": keep_rate, "mask_reuse_err": reuse}


def _conv_case(ctx, name, n, c, o, ksize, stride, hw, with_res):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import fused_conv as fc

    pad = (ksize - 1) // 2
    rng = np.random.RandomState(ctx["seed"])
    x = jnp.asarray(rng.randn(n, c, hw, hw), jnp.bfloat16)
    w = jnp.asarray(rng.randn(o, c, ksize, ksize) / np.sqrt(c * ksize ** 2),
                    jnp.bfloat16)
    gamma = jnp.asarray(1.0 + 0.1 * rng.randn(o), jnp.float32)
    beta = jnp.asarray(0.1 * rng.randn(o), jnp.float32)
    mean, var = jnp.zeros((o,), jnp.float32), jnp.ones((o,), jnp.float32)
    out_hw = hw // stride
    res = (jnp.asarray(rng.randn(n, o, out_hw, out_hw), jnp.bfloat16)
           if with_res else None)
    g = jnp.asarray(rng.randn(n, o, out_hw, out_hw) * 0.1, jnp.bfloat16)
    decision = fc.gate(x.shape, w.shape, (stride, stride), (pad, pad),
                       (1, 1), 1, 2, with_res)
    if ctx["on_chip"]:
        check(decision.admitted, "%s not admitted: %s" % (name, decision))

    # everything is an argument: a closed-over array would be baked into
    # the executable as a constant of its own size
    def fused(x, w, gamma, beta, res, g):
        y = fc.fused_conv_bn_act(
            x, w, gamma, beta, mean, var, strides=(stride, stride),
            paddings=(pad, pad), eps=1e-5, momentum=0.9, act="relu",
            residual=res)[0]
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    def reference(x, w, gamma, beta, res, g):
        xs = x[:, :, ::stride, ::stride]
        x2 = xs.reshape(n, c, out_hw * out_hw)
        co = fc._conv_reference(x2, w, out_hw, out_hw).astype(x.dtype)
        r2 = None if res is None else res.reshape(n, o, out_hw * out_hw)
        y = fc._epilogue_reference(co, gamma, beta, r2, None, None, 1e-5,
                                   "relu").reshape(n, o, out_hw, out_hw)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    argnums = (0, 1, 2, 3, 4) if with_res else (0, 1, 2, 3)
    (_, y), grads = jax.jit(jax.value_and_grad(
        fused, argnums=argnums, has_aux=True))(x, w, gamma, beta, res, g)
    (_, y_ref), grads_ref = jax.jit(jax.value_and_grad(
        reference, argnums=argnums, has_aux=True))(x, w, gamma, beta, res,
                                                   g)
    fwd = rel_err(y, y_ref)
    bwd = max(l2_err(a, b) for a, b in zip(grads, grads_ref))
    check(fwd < 3e-2, "%s forward error %g" % (name, fwd))
    check(bwd < 1e-1, "%s backward error %g" % (name, bwd))
    return {"case": name, "plan": decision.kernel, "fwd_err": fwd,
            "bwd_err": bwd}


def _ce_case(ctx, t, d, v, ref_chunk):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import fused_ce

    eps = 0.1
    rng = np.random.RandomState(ctx["seed"])
    x = jnp.asarray(rng.randn(t, d), jnp.bfloat16)
    w = jnp.asarray(rng.randn(d, v) * 0.05, jnp.bfloat16)
    b = jnp.asarray(rng.randn(v) * 0.1, jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, v, size=t), jnp.int32)
    g = jnp.asarray(rng.rand(t) + 0.5, jnp.float32)
    if ctx["on_chip"]:
        check(fused_ce._use_fused(x, w),
              "fused CE gate refuses T*V = %.3g" % (t * v))

    def fused(x, w, b, y, g):
        loss = fused_ce._fused(x, w, b, y, eps)
        return jnp.sum(loss * g), loss

    (_, loss), (dx, dw, db) = jax.jit(jax.value_and_grad(
        fused, argnums=(0, 1, 2), has_aux=True))(x, w, b, y, g)

    # the unfused reference materialises f32 [rows, V] logits: by chunks
    @jax.jit
    def ref_chunk_fn(xc, w, b, yc, gc):
        def f(xc, w, b):
            loss = fused_ce.ce_reference(xc, w, b, yc, eps)
            return jnp.sum(loss * gc), loss

        (_, loss), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(xc, w, b)
        return loss, grads

    loss_err = dx_err = 0.0
    dw_ref = jnp.zeros((d, v), jnp.float32)
    db_ref = jnp.zeros((v,), jnp.float32)
    dx_scale = float(jnp.max(jnp.abs(dx.astype(jnp.float32)))) + 1e-30
    for lo in range(0, t, ref_chunk):
        sl = slice(lo, lo + ref_chunk)
        loss_c, (dx_c, dw_c, db_c) = ref_chunk_fn(x[sl], w, b, y[sl], g[sl])
        loss_err = max(loss_err, max_err(loss[sl], loss_c))
        dx_err = max(dx_err, max_err(dx[sl], dx_c) / dx_scale)
        dw_ref = dw_ref + dw_c.astype(jnp.float32)
        db_ref = db_ref + db_c.astype(jnp.float32)
    dw_err, db_err = rel_err(dw, dw_ref), rel_err(db, db_ref)
    check(bool(jnp.all(jnp.isfinite(loss))), "fused CE: non-finite loss")
    check(loss_err < 5e-2, "fused CE loss error %g" % loss_err)
    check(max(dx_err, dw_err, db_err) < 5e-2,
          "fused CE grad errors dx %g dw %g db %g" % (dx_err, dw_err,
                                                      db_err))
    return {"case": "fused_ce", "logits": t * v, "loss_err": loss_err,
            "dx_err": dx_err, "dw_err": dw_err, "db_err": db_err}


def _scatter_case(ctx, k, v, n):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import scatter

    if v is None:  # the largest table and id count the gate admits
        v = largest_scatter_table(k)
        n = scatter._SMEM_IDS_BYTES // 4
    decision = scatter.gate(v, k, n, "float32")
    if ctx["on_chip"]:
        check(decision.admitted, "scatter gate: %s" % decision)
    rng = np.random.RandomState(ctx["seed"])
    base = jnp.asarray(rng.randn(v, k), jnp.float32)
    # negative (python-wrap) and out-of-range (dropped) ids included
    rows = jnp.asarray(rng.randint(-v - 50, v + 50, size=n), jnp.int32)
    vals = jnp.asarray(rng.randn(n, k), jnp.float32)
    out = jax.jit(scatter.scatter_add_rows)(base, rows, vals)
    ref = jax.jit(lambda b, r, x: b.at[r].add(x, mode="drop"))(
        base, rows, vals)
    err = max_err(out, ref)
    check(err < 1e-4, "scatter error %g" % err)
    return {"case": "scatter", "plan": decision.kernel, "table": [v, k],
            "ids": n, "err": err}


def _ssd_case(ctx, t, heads, p, groups, n, chunk):
    """Op ``mamba2_ssd``'s chunked form, bfloat16 operands, against the
    token-by-token recurrence in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import mamba2

    rng = np.random.RandomState(ctx["seed"])

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    x, bm, cm, raw = (rand(1, t, heads * p), rand(1, t, groups * n),
                      rand(1, t, groups * n), rand(1, t, heads))
    a_log, dt_bias, d = (jnp.asarray(rng.randn(heads), jnp.float32)
                         for _ in range(3))
    g = jnp.asarray(rng.randn(1, t, heads * p), jnp.float32)

    # every array is an argument: one closed over becomes a constant of
    # the executable, which XLA then folds on the host
    def ours(x, bm, cm, raw, a_log, dt_bias, d, g):
        out = mamba2.mamba2_ssd(x, bm, cm, raw, a_log, dt_bias, d, heads,
                                groups, chunk, jnp.bfloat16)
        return jnp.sum(out * g), out

    def plain(x, bm, cm, raw, a_log, dt_bias, d, g):
        f32 = jnp.float32
        out = mamba2.recurrent_mamba2(
            x.reshape(1, t, heads, p),
            jax.nn.softplus(raw.astype(f32) + dt_bias), -jnp.exp(a_log),
            bm.reshape(1, t, groups, n), cm.reshape(1, t, groups, n), d)
        out = out.reshape(1, t, heads * p)
        return jnp.sum(out * g), out

    args = (x, bm, cm, raw, a_log, dt_bias, d, g)
    (_, out), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    (_, want), wanted = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    fwd = l2_err(out, want)
    bwd = max(l2_err(a, w) for a, w in zip(grads, wanted))
    check(fwd < 3e-2, "mamba2_ssd forward error %g" % fwd)
    check(bwd < 3e-2, "mamba2_ssd backward error %g" % bwd)
    return {"case": "mamba2_ssd", "plan": "chunked_jnp", "tokens": t,
            "fwd_err": fwd, "bwd_err": bwd}


def _experts_case(ctx, form, t, d_model, latent, f, experts, held, top_k,
                  score, scale):
    """``parallel/moe.py``'s routed experts (``form`` ``relu2`` behind a
    sigmoid router in a latent, as ``nemotron3super.train.s8192`` runs them,
    or ``swiglu`` behind a softmax router at the model's width, as
    ``qwen3next.train.s8192``) against every held expert on every token
    under a one-hot weight, both from the same routing. On the chip the
    table is multiplied by the ``grouped_experts`` kernels (plan
    ``grouped_rows``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import grouped_experts
    from paddle_tpu.parallel import moe

    rng = np.random.RandomState(ctx["seed"])
    bf16 = jnp.bfloat16
    d = latent or d_model
    x = jnp.asarray(rng.randn(t, d), bf16)
    router_x = jnp.asarray(rng.randn(t, d_model), bf16) if latent else x
    router = jnp.asarray(rng.randn(d_model, experts) / d_model ** 0.5,
                         jnp.float32)
    first = [jnp.asarray(rng.randn(held, f, d) / d ** 0.5, bf16)
             for _ in range(1 if form == "relu2" else 2)]
    mats = (*first, jnp.asarray(rng.randn(held, d, f) / f ** 0.5, bf16))
    bias = jnp.zeros((experts,), jnp.float32)
    g = jnp.asarray(rng.randn(t, d), jnp.float32)
    rows = moe.block_rows_for(t * top_k)
    plan = grouped_experts.plan_for(mats, rows)
    act = grouped_experts.FORMS[form].act

    def ours(x, mats, router_x, router, bias, g):
        out, counts = moe.routed_experts(
            x, router, *((None,) if form == "relu2" else ()), *mats, top_k,
            0, block_rows=rows, form=form, score=score, bias=bias,
            scale=scale, router_x=router_x, plan=plan)
        return jnp.sum(out * g), (out, counts)

    def plain(x, mats, router_x, router, bias, g):
        weights, picks = moe.route_topk(router_x, router, top_k, True,
                                        score, bias, scale)

        def expert(out, per):
            e, *mats_e = per
            w = jnp.sum(jnp.where(picks == e, weights, 0.0), -1)
            h = act([jnp.matmul(x, m.T, preferred_element_type=jnp.float32)
                     for m in mats_e[:-1]])
            y = jnp.matmul(h.astype(bf16), mats_e[-1].T,
                           preferred_element_type=jnp.float32)
            return out + w[:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros((t, d), jnp.float32),
                              (jnp.arange(held), *mats))
        return jnp.sum(out * g), out

    args = (x, mats, router_x, router, bias, g)
    (_, (out, counts)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(*args)
    (_, want), wanted = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True))(*args)
    fwd = l2_err(out, want)
    bwd = max(l2_err(a, w) for a, w in zip(jax.tree.leaves(grads),
                                           jax.tree.leaves(wanted)))
    name = "routed_experts_%s_%s" % (form, score)
    check(int(counts.sum()) > 0, "no pick fell on a held expert")
    check(fwd < 3e-2, "%s forward error %g" % (name, fwd))
    check(bwd < 3e-2, "%s backward error %g" % (name, bwd))
    check(plan.kernel == "grouped_rows", "%s: %s" % (name, plan.describe()))
    return {"case": name, "plan": plan.kernel, "block_rows": rows,
            "load": [int(c) for c in counts],
            "moe.rows": [int(r) for r in moe.table_rows(counts, rows)],
            "fwd_err": fwd, "bwd_err": bwd}


def _cache_step_case(ctx, rows, c, heads, kv_heads, dk, dv, sink):
    """A decode step's attention over a slot table's caches: the kernel
    ``cache_step.fwd`` (a row's blocks up to its own position) against the
    ``jnp`` form that reads the rung under a mask, rows at positions from 0
    to the rung's last."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import cache_attention as ca

    rng = np.random.RandomState(ctx["seed"])
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(rows, heads * dk), bf16)
    k = jnp.asarray(rng.randn(rows, c, kv_heads * dk), bf16)
    v = jnp.asarray(rng.randn(rows, c, kv_heads * dv), bf16)
    s = jnp.asarray(rng.randn(heads), bf16) if sink else None
    pos = jnp.asarray(np.linspace(0, c - 1, rows).astype(np.int32))
    plan = ca.plan_for(q, k, v, heads, kv_heads)
    if ctx["on_chip"]:
        check(plan.kernel == "cache_step",
              "cache_step fell back: %s" % plan)
    ours, count = jax.jit(lambda q, k, v, pos, s: ca.step_blocks(
        q, k, v, pos, heads, kv_heads, s))(q, k, v, pos, s)
    rung, rung_count = jax.jit(lambda q, k, v, pos, s: ca.attend_step(
        q, k, v, pos, heads, kv_heads, 0, s))(q, k, v, pos, s)
    err = max_err(ours, rung)
    check(err < 3e-2, "cache_step error %g" % err)
    check(int(count[0]) == int(rung_count[0]), "cache_step counts %d "
          "positions, the rung form %d" % (count[0], rung_count[0]))
    return {"case": "cache_step", "plan": plan.kernel, "heads": heads,
            "kv_heads": kv_heads, "c": c, "err": err}


def _cache_chunk_case(ctx, rows, lanes, c, heads, kv_heads, dk, dv, sink):
    """A chunk run's attention over a slot table's caches: the kernel
    ``cache_chunk.fwd`` (a tile of lanes' scores in VMEM) against the ``jnp``
    form that walks the same blocks as XLA loops, the rows' chunks ending
    at positions up to the rung's last, the last row's last lanes pad
    lanes; with a wall time of each."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import cache_attention as ca

    rng = np.random.RandomState(ctx["seed"])
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(rows, lanes, heads * dk), bf16)
    k = jnp.asarray(rng.randn(rows, c, kv_heads * dk), bf16)
    v = jnp.asarray(rng.randn(rows, c, kv_heads * dv), bf16)
    s = jnp.asarray(rng.randn(heads), bf16) if sink else None
    first = np.linspace(c // 2 - lanes, c - lanes, rows).astype(np.int32)
    pos = first[:, None] + np.arange(lanes, dtype=np.int32)[None]
    pos[-1, lanes - 37:] = c
    live = pos < c
    pos = jnp.asarray(pos)
    plan = ca.chunk_plan_for(q, k, v, heads, kv_heads)
    if ctx["on_chip"]:
        check(plan.kernel == "cache_chunk",
              "cache_chunk fell back: %s" % plan)
    forms = {"kernel": jax.jit(lambda q, k, v, pos, s: ca.chunk_blocks(
                 q, k, v, pos, heads, kv_heads, s)),
             "jnp": jax.jit(lambda q, k, v, pos, s: ca.attend_chunk(
                 q, k, v, pos, heads, kv_heads, 0, s))}
    outs, ms = {}, {}
    for name, form in forms.items():
        form(q, k, v, pos, s).block_until_ready()
        t = time.perf_counter()
        outs[name] = form(q, k, v, pos, s).block_until_ready()
        ms[name] = (time.perf_counter() - t) * 1e3
    err = float(np.max(np.abs(
        np.asarray(outs["kernel"].astype(jnp.float32))
        - np.asarray(outs["jnp"].astype(jnp.float32)))[live]))
    check(err < 3e-2, "cache_chunk error %g" % err)
    return {"case": "cache_chunk", "plan": plan.kernel, "heads": heads,
            "kv_heads": kv_heads, "lanes": lanes, "c": c, "err": err,
            "wall_ms": ms}


def _latent_step_case(ctx, rows, c, lanes, heads, r, rope, nope, v):
    """A verifying step's latent attention over a slot table's latent
    caches: the kernel ``latent_step.fwd`` (a row's blocks up to the highest
    position its lanes hold) against the ``jnp`` form that scores the rung
    under a mask, rows at positions from 0 to the rung's last, the last
    row's second lane a pad lane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import cache_attention as ca
    from paddle_tpu.ops import sparse_latent

    rng = np.random.RandomState(ctx["seed"])
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(rows, lanes, heads * (nope + rope)), bf16)
    kv_b = jnp.asarray(rng.randn(r, heads * (nope + v)) * r ** -0.5, bf16)
    cache = jnp.asarray(rng.randn(rows, c, r + rope), bf16)
    first = np.linspace(0, c - 1, rows).astype(np.int32)
    pos = jnp.asarray(first[:, None] + np.arange(lanes, dtype=np.int32))
    plan = ca.latent_plan_for(q, cache, r, heads)
    if ctx["on_chip"]:
        check(plan.kernel == "latent_step",
              "latent_step fell back: %s" % plan)
    def attend(plan):
        return jax.jit(lambda q, kv_b, cache, pos:
                       sparse_latent.latent_attention_dense(
                           q, kv_b, cache, pos, heads, nope, v,
                           (nope + rope) ** -0.5, plan=plan))(
                               q, kv_b, cache, pos)

    ours, rung = attend(plan), attend(None)
    err = rel_err(ours, rung)
    check(err < 2e-2, "latent_step error %g" % err)
    check(not np.asarray(ours[-1, -1]).any(), "a pad lane is not 0")
    return {"case": "latent_step", "plan": plan.kernel, "heads": heads,
            "lanes": lanes, "c": c, "err": err}


def _eva_step_case(ctx, rows, window, chunk, entries, heads, d):
    """A step's EVA attention over a slot table's window and summary
    caches: the kernel ``eva_step.fwd`` (a row's window blocks up to its
    slot, then its summary blocks up to the entries it reads) against the
    ``jnp`` form that reads both caches whole under the mask, rows at
    positions from 0 to the rung's last, and the wall time of each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import eva_attention as ea

    rng = np.random.RandomState(ctx["seed"])
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(rows, heads * d), bf16)
    caches = [jnp.asarray(rng.randn(rows, n, heads * d), bf16)
              for n in (window, window, entries, entries)]
    pos = jnp.asarray(np.linspace(0, entries * chunk - 1, rows).astype(
        np.int32))
    plan = ea.plan_for(q, *caches, heads)
    if ctx["on_chip"]:
        check(plan.kernel == "eva_step", "eva_step fell back: %s" % plan)

    def timed(form):
        run = jax.jit(lambda q, pos, *caches: form(
            q, *caches, pos, heads, window, chunk))
        out = jax.block_until_ready(run(q, pos, *caches))
        # calls sent ahead of the device, so that the wall is the device's
        # time and not one call's dispatch
        t0 = time.perf_counter()
        for _ in range(20):
            last = run(q, pos, *caches)
        jax.block_until_ready(last)
        return out, (time.perf_counter() - t0) / 20 * 1e3

    (ours, count), ours_ms = timed(ea.step_blocks)
    (rung, rung_count), rung_ms = timed(ea.attend_step)
    err = max_err(ours, rung)
    check(err < 3e-2, "eva_step error %g" % err)
    check(np.array_equal(np.asarray(count), np.asarray(rung_count)),
          "eva_step counts %s, the rung form %s" % (count, rung_count))
    return {"case": "eva_step", "plan": plan.kernel, "heads": heads,
            "window": window, "entries": entries, "err": err,
            "count": [int(n) for n in count], "wall_ms": ours_ms,
            "rung_wall_ms": rung_ms}


def phase_kernels(ctx):
    cfg = ctx["sizes"]
    cases = []
    for case in cfg["flash"]:
        cases.append(_flash_case(ctx, *case))
        log("kernels: %s" % cases[-1])
    for probe in cfg["dropout"]:
        cases.append(_dropout_probe(ctx, *probe))
        log("kernels: %s" % cases[-1])
    for case in cfg["conv"]:
        cases.append(_conv_case(ctx, *case))
        log("kernels: %s" % cases[-1])
    cases.append(_ce_case(ctx, **cfg["ce"]))
    log("kernels: %s" % cases[-1])
    cases.append(_scatter_case(ctx, **cfg["scatter"]))
    log("kernels: %s" % cases[-1])
    cases.append(_ssd_case(ctx, **cfg["ssd"]))
    log("kernels: %s" % cases[-1])
    for case in cfg["experts"]:
        cases.append(_experts_case(ctx, **case))
        log("kernels: %s" % cases[-1])
    for case in cfg["cache_step"]:
        cases.append(_cache_step_case(ctx, **case))
        log("kernels: %s" % cases[-1])
    cases.append(_latent_step_case(ctx, **cfg["latent_step"]))
    log("kernels: %s" % cases[-1])
    cases.append(_eva_step_case(ctx, **cfg["eva_step"]))
    log("kernels: %s" % cases[-1])
    cases.append(_cache_chunk_case(ctx, **cfg["cache_chunk"]))
    log("kernels: %s" % cases[-1])
    return {"cases": cases}


# ---------------------------------------------------------------------------
# phase: configs — two train steps of every other BASELINE config
# ---------------------------------------------------------------------------

def _static_config(ctx, name, build, kw, batch):
    import numpy as np

    main, startup, spec = build_train_program(ctx["fluid"], build,
                                              ctx["seed"])
    losses, first_s, exe, _ = run_steps(ctx, main, startup, spec, batch, 2)
    check(all(np.isfinite(losses)), "%s: non-finite loss %s"
          % (name, losses))
    rec = {"config": name, "batch": batch, "model_kw": kw,
           "first_step_s": round(first_s, 1),
           "losses": [round(x, 4) for x in losses],
           "tpu_custom_calls": exe.lowered_hlo_text().count(
               TPU_CUSTOM_CALL)}
    plans = kernel_plans(main)
    if plans:
        rec["kernel_plans"] = {t: tally(names)
                               for t, names in plans.items()}
    return rec


def _bert_dygraph_config(ctx, kw, batch):
    import jax
    import numpy as np

    from paddle_tpu.models import bert_dygraph

    fluid = ctx["fluid"]
    model, _, _, _ = bert_dygraph.bert_base_dygraph(amp=True, **kw)
    feeds = bert_dygraph.sample_batch(
        batch, kw["seq_len"], kw.get("vocab_size", 30522),
        np.random.RandomState(ctx["seed"]))
    with fluid.dygraph.guard():
        model(*feeds)  # materialises the lazily built parameters
    step, params, opt_state = bert_dygraph.make_train_step(model)
    jstep = jax.jit(step, donate_argnums=(0, 1))
    feeds = tuple(jax.device_put(f) for f in feeds)
    key = jax.random.PRNGKey(ctx["seed"])
    losses = []
    t0 = time.time()
    first_s = None
    for _ in range(2):
        key, sub = jax.random.split(key)
        loss, params, opt_state = jstep(params, opt_state, sub, *feeds)
        jax.block_until_ready(loss)
        if first_s is None:
            first_s = time.time() - t0
        losses.append(float(loss))
    check(all(np.isfinite(losses)), "bert_dygraph: non-finite loss %s"
          % losses)
    return {"config": "bert_dygraph", "batch": batch, "model_kw": kw,
            "first_step_s": round(first_s, 1),
            "losses": [round(x, 4) for x in losses]}


def phase_configs(ctx):
    from paddle_tpu import models

    cfg = ctx["sizes"]["configs"]
    builders = {
        "bert": lambda kw: models.bert.bert_base(**kw),
        "resnet50": lambda kw: models.resnet.resnet_imagenet(**kw),
        "deepfm": lambda kw: models.deepfm.deepfm(**kw),
        "seq2048": lambda kw: models.transformer.transformer_base(**kw),
    }
    configs = []
    for name in ("bert", "resnet50", "deepfm", "seq2048"):
        kw, batch = cfg[name]["kw"], cfg[name]["batch"]
        configs.append(_static_config(
            ctx, name, lambda kw=kw, name=name: builders[name](kw), kw,
            batch))
        log("configs: %s" % configs[-1])
        release()
    configs.append(_bert_dygraph_config(ctx, cfg["bert_dygraph"]["kw"],
                                        cfg["bert_dygraph"]["batch"]))
    log("configs: %s" % configs[-1])
    return {"configs": configs}


# ---------------------------------------------------------------------------
# phase: serve — the decode tier answers requests; replay checks them
# ---------------------------------------------------------------------------

def phase_serve(ctx):
    import numpy as np

    fluid, cfg = ctx["fluid"], ctx["sizes"]["serve"]
    from paddle_tpu import models, serving
    from paddle_tpu.inference import Predictor

    lm = cfg["lm"]
    full_kw = {k: v for k, v in lm.items() if k != "ctx_cap"}
    scope = fluid.Scope()
    exe = fluid.Executor(ctx["place"])

    def build(builder, **kw):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = ctx["seed"]
        fluid.unique_name.switch()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            out = builder(**kw)
        return main, startup, out

    # one weight-sharing family on one scope: only the full program's
    # startup runs, the step and chunk programs name the same parameters
    full_main, full_start, full_spec = build(
        models.transformer.transformer_lm, seq_len=cfg["replay_len"],
        **full_kw)
    step_main, _, (step_fetch, step_spec) = build(
        models.transformer.transformer_lm_step, **lm)
    chunk_main, _, (chunk_fetch, chunk_spec) = build(
        models.transformer.transformer_lm_chunk, **lm)

    def feeds_of(spec):
        return [spec["token_feed"], spec["pos_feed"]] \
            + [c["feed"] for c in spec["cache_feeds"]]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_serve_") as root:
        dirs = {n: os.path.join(root, n) for n in ("full", "step", "chunk")}
        with fluid.scope_guard(scope):
            exe.run(full_start)
            logits_var = full_main.global_block().var(
                full_spec.extras["logits"])
            fluid.io.save_inference_model(
                dirs["full"], ["ids", "lbl"], [logits_var], exe,
                main_program=full_main)
            fluid.io.save_inference_model(
                dirs["step"], feeds_of(step_spec), step_fetch, exe,
                main_program=step_main)
            fluid.io.save_inference_model(
                dirs["chunk"], feeds_of(chunk_spec), chunk_fetch, exe,
                main_program=chunk_main)
        serving.save_decode_spec(dirs["step"], step_spec)

        rng = np.random.RandomState(ctx["seed"])
        vocab, max_new = lm["vocab"], cfg["max_new"]

        def prompt(n):
            return [int(x) for x in rng.randint(1, vocab, size=n)]

        shared = prompt(12)
        prompts = [prompt(3), shared + prompt(2), prompt(1),
                   shared + prompt(5), prompt(cfg["long_prompt"]),
                   prompt(7), prompt(2), prompt(17)]
        engine = serving.ServingEngine(
            dirs["step"], decode=True, num_replicas=1,
            ladder=cfg["ladder"], seq_ladder=cfg["seq_ladder"],
            prefix_cache=True,
            decode_prefill={"predictor": Predictor(dirs["chunk"]),
                            "spec": chunk_spec,
                            "ladder": cfg["prefill_ladder"]})
        try:
            # the first prompt alone, so its prefix is harvested before
            # the request that shares it is admitted
            first = engine.predict(prompts[1], timeout_s=900.0,
                                   max_new_tokens=max_new)
            futures = [engine.submit(p, timeout_s=900.0,
                                     max_new_tokens=max_new)
                       for i, p in enumerate(prompts) if i != 1]
            outs = [np.asarray(f.result(900.0)).ravel() for f in futures]
            outs.insert(1, np.asarray(first).ravel())
            metrics = engine.metrics()
            compiled = sum(engine.compiled_shape_counts())
            bound = engine.compile_cache_bound
            # the step executable's record: whether the hand-over of the
            # caches engages on this installation
            step_record = engine.decode_compile_records()[0]
        finally:
            engine.shutdown()

        # teacher-forced replay through the plain full-sequence program:
        # position p-1's argmax must be the token the decode tier emitted
        # at p. Different executables round differently, so a token also
        # passes when the replay puts it within 5% of a logit standard
        # deviation of its own argmax (a numerical tie).
        replay = Predictor(dirs["full"])
        length = cfg["replay_len"]
        ids = np.zeros((len(prompts), length), np.int64)
        for i, (p, out) in enumerate(zip(prompts, outs)):
            check(len(out) == max_new, "request %d returned %d tokens"
                  % (i, len(out)))
            seq = list(p) + [int(x) for x in out]
            check(len(seq) <= length, "replay window too short")
            ids[i, :len(seq)] = seq
        logits, = replay.run({"ids": ids, "lbl": np.zeros_like(ids)})
        exact = ties = 0
        for i, (p, out) in enumerate(zip(prompts, outs)):
            for j, tok in enumerate(out):
                row = logits[i, len(p) + j - 1].astype(np.float64)
                if int(np.argmax(row)) == int(tok):
                    exact += 1
                    continue
                gap = float(row.max() - row[int(tok)])
                check(gap <= 0.05 * float(row.std()),
                      "request %d token %d: decode tier emitted %d, the "
                      "full-sequence replay prefers %d by %g (logit std "
                      "%g)" % (i, j, int(tok), int(np.argmax(row)), gap,
                               float(row.std())))
                ties += 1
    check(compiled <= bound, "compiled %d executables, bound %d"
          % (compiled, bound))
    check(metrics["prefill_chunks"] > 0, "no prefill chunk was dispatched")
    check(metrics["prefix_hits"] > 0, "the shared prefix was never reused")
    return {"lm": lm, "requests": len(prompts),
            "prompt_lens": [len(p) for p in prompts],
            "tokens_checked": exact + ties, "tokens_exact": exact,
            "tokens_numerical_tie": ties,
            "executables_compiled": compiled, "compile_cache_bound": bound,
            "prefill_chunks": metrics["prefill_chunks"],
            "prefix_hits": metrics["prefix_hits"],
            # engaged where alias_bytes >= donated_feed_bytes > 0
            "step_alias_bytes": (step_record["memory"] or {}).get(
                "alias_bytes"),
            "step_donated_feed_bytes": step_record["donated_feed_bytes"],
            "attention_plans_replay": tally(
                kernel_plans(replay._program).get("flash_attention", []))}


# ---------------------------------------------------------------------------
# --multichip: one chip against a ('dp', 'mp') = 2x2 mesh of four
# ---------------------------------------------------------------------------

def phase_multichip(ctx):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu import models
    from paddle_tpu.parallel import sharding_check

    fluid, cfg = ctx["fluid"], ctx["sizes"]["multichip"]
    devices = jax.devices()
    check(len(devices) == 4, "--multichip needs 4 devices, JAX reports %d"
          % len(devices))

    def build():
        # dropout off: the two runs draw their masks differently (Pallas
        # in-kernel PRNG on one chip, partitioned jax.random on the mesh),
        # so only a deterministic program can be compared step by step
        return build_train_program(
            fluid, lambda: models.transformer.transformer_base(
                seq_len=cfg["seq_len"], dropout_rate=0.0,
                **cfg["model_kw"]), ctx["seed"])

    main, startup, spec = build()
    one, one_first_s, _, _ = run_steps(ctx, main, startup, spec,
                                       cfg["batch"], cfg["steps"])
    one_plans = tally(kernel_plans(main)["flash_attention"])
    release()

    main, startup, spec = build()
    mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "mp"))
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=spec.loss.name, mesh=mesh, dp_axis="dp")
    four, four_first_s, exe, scope = run_steps(
        ctx, main, startup, spec, cfg["batch"], cfg["steps"],
        program=compiled)
    hlo = exe.lowered_hlo_text()
    mesh_plans = tally(kernel_plans(main)["flash_attention"])

    check(all(np.isfinite(one + four)), "non-finite loss: %s / %s"
          % (one, four))
    worst = max(abs(a - b) / abs(a) for a, b in zip(one, four))
    check(worst <= 2e-3, "mesh losses %s vs one-chip losses %s"
          % (four, one))
    check("all-reduce" in hlo, "no all-reduce in the meshed step")
    sharding_check.assert_param_sharded(hlo, "enc0_ffn_fc1.w",
                                        cfg["ffn_shape"])
    w = scope.get("enc0_ffn_fc1.w")
    check(len(w.sharding.device_set) == 4,
          "enc0_ffn_fc1.w lives on %d devices" % len(w.sharding.device_set))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if ctx["on_chip"]:
        check(all(b for b in in_use), "a device holds no bytes: %s" % in_use)
    return {"model": "transformer_base", "batch": cfg["batch"],
            "mesh": {"dp": 2, "mp": 2},
            "losses_one_chip": [round(x, 4) for x in one],
            "losses_mesh": [round(x, 4) for x in four],
            "worst_relative_difference": worst,
            "first_step_s": [round(one_first_s, 1), round(four_first_s, 1)],
            "all_reduces_in_hlo": hlo.count(" all-reduce("),
            "tpu_custom_calls_mesh": hlo.count("tpu_custom_call"),
            "attention_plans_one_chip": one_plans,
            "attention_plans_mesh": mesh_plans,
            "ffn_weight_local_shape": list(
                w.addressable_shards[0].data.shape),
            "bytes_in_use": in_use}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the 2x2-mesh path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny shapes, kernels in interpret mode")
    ap.add_argument("--seed", type=int, default=1,
                    help="weights and data (> 0: Program.random_seed 0 "
                         "means nondeterministic)")
    ap.add_argument("--phase", action="append", metavar="NAME",
                    help="run only this phase (may be given again); "
                         "default: all of them")
    args = ap.parse_args()
    if args.seed <= 0:
        ap.error("--seed must be positive")

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.multichip:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax

    devices = jax.devices()
    device = devices[0]
    described = {"platform": device.platform, "kind": device.device_kind,
                 "count": len(devices)}
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        # no result on stdout: nothing here may be read as a run
        print("chip_smoke: JAX finds no TPU (%s); --rehearse runs the CPU "
              "rehearsal. %s" % (described, json.dumps(
                  {"ok": False, "device": described})), file=sys.stderr)
        return 2

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache, native
    from paddle_tpu.distributed.launch import local_tpu_chips

    if args.rehearse and not args.multichip:
        # (the mesh rehearsal keeps the gates honest instead: interpret
        # mode would put kernels under the mesh that the chip never sees)
        from paddle_tpu.ops import (cache_attention, eva_attention,
                                    flash_attention, fused_ce, fused_conv,
                                    grouped_experts, scatter)

        for mod in (cache_attention, eva_attention, flash_attention,
                    fused_ce, fused_conv, grouped_experts, scatter):
            mod._INTERPRET = True

    ctx = {
        "fluid": fluid, "seed": args.seed, "on_chip": on_chip,
        "platform": device.platform, "device": device,
        "sizes": sizes(args.rehearse),
        "place": fluid.TPUPlace(0) if on_chip else fluid.CPUPlace(),
        "rng_impl": "rbg" if on_chip else "threefry2x32",
    }
    emit({"phase": "setup", "ok": True, "device": described,
          "rehearse": args.rehearse,
          "tpu_chips_on_host": local_tpu_chips(),
          "native_data_plane": ("built from native_src/"
                                if native.native_available() else
                                "unavailable: pure-Python fallback"),
          **cache_record(compile_cache)})

    phases = ([("multichip", phase_multichip)] if args.multichip else
              [("train", phase_train), ("kernels", phase_kernels),
               ("configs", phase_configs), ("serve", phase_serve)])
    if args.phase:
        unknown = set(args.phase) - {name for name, _ in phases}
        if unknown:
            ap.error("no phase %s" % ", ".join(sorted(unknown)))
        phases = [(name, fn) for name, fn in phases if name in args.phase]
    ok = True
    for name, fn in phases:
        log("phase %s" % name)
        t0 = time.time()
        try:
            record = fn(ctx)
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            record = {"error": "%s: %s" % (type(e).__name__,
                                           str(e)[:2000])}
            ok = False
        emit(dict({"phase": name, "ok": "error" not in record,
                   "wall_s": round(time.time() - t0, 1)}, **record))
        release()

    emit({"phase": "teardown", "ok": True,
          "wall_s": round(time.time() - T_START, 1),
          **cache_record(compile_cache)})
    emit({"ok": ok, "device": described})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
