"""Plain reference of EvaByte's decoder (EvaByte/EvaByte ``config.json``,
``model_type`` ``evabyte``, ``attention_class`` ``eva``: Zheng et al.,
*Efficient Attention via Control Variates*, arXiv:2302.04542, as the
released ``eva.py`` / ``eva_pt_ref.py`` simplify it): the full forward pass
over one sequence, in ``jax.numpy`` and float32, every product through
``ops`` (``reference/precision.py``: float32 at ``Precision.HIGHEST``, or
the fp8 control). No cache, no window cache, no chunks of lanes, no
batching, nothing of the program imported.

``logits(params, tokens, args, ops)``: ``params`` by the names the program's
builder gives the leaves (``eva.embed_tokens``, ``eva.l3.attn.q``, ...), in
whatever type they are served in, brought to float32 a layer at a time;
``tokens`` [T] int; ``args`` the configuration's builder keys (the source's
keys, ``layers_held`` ``[first, count]``). Returns float32 [T, vocab_size]:
row ``t`` is the distribution of byte ``t + 1`` (head 0).

Layer ``l`` (``h`` the stream, float32; position ``t`` = the index along T;
``W`` = ``window_size``, ``C`` = ``chunk_size``, heads ``i`` of ``D``, ``s =
D ** -0.5``; no bias):

* ``y = rms(h) * (1 + w_in)``; ``q = y W_q``, ``k = y W_k``, ``v = y W_v``;
  rotary on all ``D`` dims of every q and k head, pairs (i, i + D/2),
  frequencies ``theta^(-2i/D)``;
* summaries, for ALL chunks of the sequence at once: chunk ``c`` holds the
  positions ``C c .. C c + C - 1``; ``a_j = softmax_j(s k_j . phi_i)`` over
  them, ``kbar_c = sum_j a_j k_j + mu_i``, ``vbar_c = sum_j a_j v_j`` (the
  rotated keys are pooled; ``phi``, ``mu`` [H*D], a head's D side by side);
* attention: the query at ``t`` in window ``w = t // W`` reads the SINGLETONS
  ``S_t = {j : w W <= j <= t}`` and the SUMMARIES ``R_t = {c : c < w W /
  C}``, one softmax over the scores ``s q . k_j`` and ``s q . kbar_c``; the
  output the weighted sum of ``v_j`` and ``vbar_c``; ``h += concat(o) W_o``;
* ``h += W_down(silu(W_gate z) * (W_up z))``, ``z = rms(h) * (1 + w_post)``.

After the last layer held: ``rms(h) * (1 + w)``, then head 0.

Departures from the source, each noted where it is made: the summaries of a
window's own chunks are computed and never read (the source never forms
them); the last chunk of a sequence whose length is no multiple of ``C`` is
padded and never read; queries go in blocks of ``BLOCK`` rows so that a
32768-byte pass fits beside the served weights, a block against the keys of
its own window alone where the block lies in one window (the keys of every
other window have weight zero by ``S_t``), and the feed-forward in the same
blocks (11008 x 32768 hidden units are 1.4 GB in float32)."""

import functools

import jax
import jax.numpy as jnp

BLOCK = 512
_F32 = jnp.float32


def _rms(x, w, eps, unit_offset):
    w = w.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w if unit_offset else w)


def _rope(x, theta):
    """x [T, H, D]: pairs (i, i + D/2) turned by ``t * theta^(-2i/D)``."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / d)
    angle = jnp.arange(t, dtype=_F32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _summaries(k, v, phi, mu, chunk, ops):
    """k, v [T, H, D] -> kbar, vbar [ceil(T / C), H, D], every chunk of the
    sequence at once (a sequence that ends inside a chunk is padded with
    zeros: that chunk's summary belongs to the last window and is never
    read)."""
    t, h, d = k.shape
    count = -(-t // chunk)
    pad = ((0, count * chunk - t), (0, 0), (0, 0))
    kc = jnp.pad(k, pad).reshape(count, chunk, h, d)
    vc = jnp.pad(v, pad).reshape(count, chunk, h, d)
    logits = ops.einsum("cjhd,hd->cjh", kc, phi) * d ** -0.5
    a = jax.nn.softmax(logits, axis=1)
    return (ops.einsum("cjh,cjhd->chd", a, kc) + mu,
            ops.einsum("cjh,cjhd->chd", a, vc))


def _attend(q, k, v, kbar, vbar, window, chunk, ops):
    """q, k, v [T, H, D]; kbar, vbar [N, H, D] -> [T, H * D]."""
    t, h, d = q.shape
    size = BLOCK if t % BLOCK == 0 and window % BLOCK == 0 else t
    # a block that lies in one window is scored against that window's keys
    # alone; else against every key (the mask is the same index sets)
    span = window if size < t and t % window == 0 else t
    scale = d ** -0.5
    entries = jnp.arange(kbar.shape[0])

    def block(args):
        qb, first = args
        at = first + jnp.arange(size)                    # [S] positions
        start = (first // window) * window if span < t else 0
        keys = jax.lax.dynamic_slice_in_dim(k, start, span, 0)
        vals = jax.lax.dynamic_slice_in_dim(v, start, span, 0)
        j = start + jnp.arange(span)
        w = at // window
        singles = (j[None, :] >= (w * window)[:, None]) \
            & (j[None, :] <= at[:, None])                 # S_t
        pooled = entries[None, :] < (w * (window // chunk))[:, None]  # R_t
        s = jnp.concatenate([
            jnp.where(singles[None], ops.einsum("qhd,khd->hqk", qb, keys)
                      * scale, -jnp.inf),
            jnp.where(pooled[None], ops.einsum("qhd,khd->hqk", qb, kbar)
                      * scale, -jnp.inf)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        return ops.einsum("hqk,khd->qhd", p[..., :span], vals) \
            + ops.einsum("hqk,khd->qhd", p[..., span:], vbar)

    out = jax.lax.map(block, (q.reshape(t // size, size, h, d),
                              jnp.arange(t // size) * size))
    return out.reshape(t, h * d)


def _swiglu(y, gate, up, down, ops):
    """Matrices [in, out]; rows in blocks of ``BLOCK``."""
    t = y.shape[0]
    size = BLOCK if t % BLOCK == 0 else t

    def block(rows):
        return ops.dot(jax.nn.silu(ops.dot(rows, gate)) * ops.dot(rows, up),
                       down)

    return jax.lax.map(block, y.reshape(t // size, size, -1)).reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("sizes", "ops"))
def _layer(x, p, sizes, ops):
    a = dict(sizes)
    t = x.shape[0]
    h, eps, offset = a["heads"], a["eps"], a["unit_offset"]
    d = x.shape[1] // h
    f = {name: leaf.astype(_F32) for name, leaf in p.items()}
    y = _rms(x, f["input_norm.w"], eps, offset)
    q = _rope(ops.dot(y, f["attn.q"]).reshape(t, h, d), a["theta"])
    k = _rope(ops.dot(y, f["attn.k"]).reshape(t, h, d), a["theta"])
    v = ops.dot(y, f["attn.v"]).reshape(t, h, d)
    kbar, vbar = _summaries(k, v, f["attn.phi"].reshape(h, d),
                            f["attn.mu"].reshape(h, d), a["chunk"], ops)
    mixed = _attend(q, k, v, kbar, vbar, a["window"], a["chunk"], ops)
    x = x + ops.dot(mixed, f["attn.o"])
    z = _rms(x, f["post_norm.w"], eps, offset)
    return x + _swiglu(z, f["mlp.gate"], f["mlp.up"], f["mlp.down"], ops)


@functools.partial(jax.jit, static_argnames=("eps", "unit_offset", "ops"))
def _head(x, w, head, eps, unit_offset, ops):
    return ops.dot(_rms(x, w, eps, unit_offset), head.astype(_F32))


def layer_sizes(args):
    """The static sizes :func:`_layer` takes."""
    return (("heads", int(args["num_attention_heads"])),
            ("eps", float(args["rms_norm_eps"])),
            ("unit_offset", bool(args.get("norm_add_unit_offset", True))),
            ("theta", float(args["rope_theta"])),
            ("window", int(args["window_size"])),
            ("chunk", int(args["chunk_size"])))


def logits(params, tokens, args, ops):
    first, count = args.get("layers_held") or (0, args["num_hidden_layers"])
    sizes = layer_sizes(args)
    x = jnp.take(params["eva.embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(_F32)
    for l in range(first, first + count):
        prefix = "eva.l%d." % l
        leaves = {k[len(prefix):]: v for k, v in params.items()
                  if k.startswith(prefix)}
        x = _layer(x, leaves, sizes=sizes, ops=ops)
    return _head(x, params["eva.norm.w"], params["eva.lm_head"],
                 eps=float(args["rms_norm_eps"]),
                 unit_offset=bool(args.get("norm_add_unit_offset", True)),
                 ops=ops)
