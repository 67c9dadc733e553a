"""BERT-base pretraining built from DYGRAPH modules (BASELINE config 4:
"fluid dygraph -> XLA" — ref ``imperative/layers.py`` Layer carrying whole
models, e.g. ``tests/unittests/test_imperative_*``).

The imperative model composes ``dygraph.nn`` modules (Embedding, FC,
LayerNorm, Dropout) plus the same Pallas flash-attention and fused-CE
primitives the static twin lowers to; ``Layer.functional(rng=True)``
exports the pure ``apply(params, key, *feeds) -> loss`` that jits into the
identical XLA step (parity-tested against ``models/bert.py`` in
``tests/test_dygraph_bert.py``)."""


import jax
import jax.numpy as jnp
import numpy as np

from ..dygraph import nn as dnn
from ..dygraph.base import VarBase, record, to_variable
from ..dygraph.layers import Layer

__all__ = ["BertPretrain", "bert_base_dygraph", "make_train_step"]


def _cast(amp, *xs):
    if not amp:
        return xs if len(xs) > 1 else xs[0]
    out = tuple(x.astype(jnp.bfloat16)
                if hasattr(x, "dtype") and x.dtype == jnp.float32 else x
                for x in xs)
    return out if len(out) > 1 else out[0]


class _MultiHeadAttention(Layer):
    """Bias-free QKV/out projections + the flash-attention kernel —
    the dygraph twin of ``layers.multi_head_attention``."""

    def __init__(self, d_model, n_head, dropout_rate, amp=False):
        super().__init__("mha")
        self._n_head = n_head
        self._rate = dropout_rate
        self._amp = amp
        self._wq = self.create_parameter([d_model, d_model])
        self._wk = self.create_parameter([d_model, d_model])
        self._wv = self.create_parameter([d_model, d_model])
        self._wo = self.create_parameter([d_model, d_model])

    def forward(self, x, key_bias):
        from ..ops.flash_attention import flash_attention
        from ..dygraph import base

        n_head, amp = self._n_head, self._amp
        rate = self._rate if self.training else 0.0
        rng = base.next_key() if rate else None

        def fn(xv, bias, wq, wk, wv, wo):
            xv, wq, wk, wv, wo = _cast(amp, xv, wq, wk, wv, wo)
            q, k, v = xv @ wq, xv @ wk, xv @ wv
            ctx = flash_attention(q, k, v, n_head, bias=bias,
                                  dropout_rate=rate, rng=rng)
            return ctx @ wo

        return record(fn, to_variable(x), to_variable(key_bias),
                      self._wq, self._wk, self._wv, self._wo)


class _Sublayer(Layer):
    """Post-norm residual wrapper: LN(x + dropout(f(x)))."""

    def __init__(self, inner, dropout_rate, d_model):
        super().__init__("sub")
        self.inner = inner
        self.drop = dnn.Dropout(p=dropout_rate)
        self.norm = dnn.LayerNorm(normalized_shape=d_model)

    def forward(self, x, *args):
        y = self.drop(self.inner(x, *args) if args else self.inner(x))
        return self.norm(record(lambda a, b: a + b, to_variable(x), y))


@jax.custom_vjp
def _ffn_bf16(x, w1, b1, w2, b2):
    o, _ = _ffn_bf16_fwd(x, w1, b1, w2, b2)
    return o


def _ffn_bf16_fwd(x, w1, b1, w2, b2):
    """Explicit bf16 FFN with a hand-written backward: XLA's autodiff of
    the composed form re-computes the gelu vjp chain INSIDE the dW
    fusion's operand (profiled 2.5x the dW matmul floor per layer;
    optimization_barrier measured net-negative). Saving z and emitting
    clean bf16-operand dots sidesteps the fusion pathologies."""
    xb = x.astype(jnp.bfloat16)
    w1b, w2b = w1.astype(jnp.bfloat16), w2.astype(jnp.bfloat16)
    z = xb @ w1b + b1.astype(jnp.bfloat16)
    h = jax.nn.gelu(z, approximate=True)
    o = h @ w2b + b2.astype(jnp.bfloat16)
    # zero-size carrier records the primal dtype (a raw dtype is
    # not a valid jax residual)
    return o, (xb, w1b, w2b, z, jnp.zeros((0,), x.dtype))


def _ffn_bf16_bwd(res, do):
    xb, w1b, w2b, z, x_proto = res
    do = do.astype(jnp.bfloat16)
    lead = do.shape[:-1]
    do2 = do.reshape(-1, do.shape[-1])
    z2 = z.reshape(-1, z.shape[-1])
    x2 = xb.reshape(-1, xb.shape[-1])
    h2, gelu_vjp = jax.vjp(
        lambda t: jax.nn.gelu(t, approximate=True), z2)
    dh = do2 @ w2b.T                                   # [T, d_ff] bf16
    dz, = gelu_vjp(dh)                                 # bf16, one pass
    dw2 = jnp.dot(h2.T, do2, preferred_element_type=jnp.float32)
    db2 = jnp.sum(do2.astype(jnp.float32), axis=0)
    dw1 = jnp.dot(x2.T, dz, preferred_element_type=jnp.float32)
    db1 = jnp.sum(dz.astype(jnp.float32), axis=0)
    dx = (dz @ w1b.T).reshape(lead + (xb.shape[-1],)).astype(x_proto.dtype)
    return dx, dw1, db1, dw2, db2


_ffn_bf16.defvjp(_ffn_bf16_fwd, _ffn_bf16_bwd)


class _FFN(Layer):
    def __init__(self, d_model, d_ff, amp=False):
        super().__init__("ffn")
        self._amp = amp
        self._w1 = self.create_parameter([d_model, d_ff])
        self._b1 = self.create_parameter([d_ff], is_bias=True)
        self._w2 = self.create_parameter([d_ff, d_model])
        self._b2 = self.create_parameter([d_model], is_bias=True)

    def forward(self, x):
        amp = self._amp

        def fn(xv, w1, b1, w2, b2):
            if amp:
                return _ffn_bf16(xv, w1, b1, w2, b2)
            h = jax.nn.gelu(xv @ w1 + b1, approximate=False)
            return h @ w2 + b2

        return record(fn, to_variable(x), self._w1, self._b1, self._w2,
                      self._b2)


class BertPretrain(Layer):
    def __init__(self, vocab_size=30522, seq_len=128, d_model=768,
                 d_ff=3072, n_head=12, n_layer=12, dropout_rate=0.1,
                 max_position=512, type_vocab=2, amp=False):
        super().__init__("bert_dy")
        self._seq_len = seq_len
        self._vocab = vocab_size
        self._amp = amp
        self.word_emb = dnn.Embedding(size=[vocab_size, d_model])
        self.pos_emb = dnn.Embedding(
            size=[max(max_position, seq_len), d_model])
        self.seg_emb = dnn.Embedding(size=[type_vocab, d_model])
        self.emb_norm = dnn.LayerNorm(normalized_shape=d_model)
        self.emb_drop = dnn.Dropout(p=dropout_rate)
        self.attn = []
        self.ffn = []
        for i in range(n_layer):
            attn = _Sublayer(
                _MultiHeadAttention(d_model, n_head, dropout_rate, amp),
                dropout_rate, d_model)
            ffn = _Sublayer(_FFN(d_model, d_ff, amp), dropout_rate, d_model)
            self.add_sublayer("attn%d" % i, attn)
            self.add_sublayer("ffn%d" % i, ffn)
            self.attn.append(attn)
            self.ffn.append(ffn)
        self.mlm_transform = dnn.FC(size=d_model, num_flatten_dims=2,
                                    act="gelu")
        self.mlm_norm = dnn.LayerNorm(normalized_shape=d_model)
        self._mlm_w = self.create_parameter([d_model, vocab_size],
                                            name="mlm_out.w_dy")
        self._mlm_b = self.create_parameter([vocab_size], is_bias=True,
                                            name="mlm_out.b_dy")
        self.pooler = dnn.FC(size=d_model, act="tanh")
        self.nsp_out = dnn.FC(size=2)

    def encode(self, input_ids, segment_ids, input_len):
        seq_len, amp = self._seq_len, self._amp
        pos = jnp.arange(seq_len, dtype=jnp.int32)
        x = record(lambda a, b, c: a + b + c,
                   self.word_emb(input_ids), self.seg_emb(segment_ids),
                   self.pos_emb(VarBase(pos, stop_gradient=True)))
        x = self.emb_drop(self.emb_norm(x))
        if amp:  # bf16-resident stream from the embeddings on
            x = record(lambda v: _cast(True, v), x)

        lens = to_variable(input_len)
        key_bias = record(
            lambda lv: jnp.where(
                jnp.arange(seq_len)[None, :] < lv.reshape(-1, 1),
                0.0, -1e9).astype(jnp.float32),
            VarBase(lens.value(), stop_gradient=True))
        for attn, ffn in zip(self.attn, self.ffn):
            x = attn(x, key_bias)
            x = ffn(x)
        return x

    def forward(self, input_ids, segment_ids, input_len, mlm_labels,
                mlm_weights, nsp_label):
        from ..ops.fused_ce import linear_smooth_ce

        amp = self._amp
        x = self.encode(input_ids, segment_ids, input_len)

        h = self.mlm_norm(self.mlm_transform(x))
        mlm_labels = VarBase(to_variable(mlm_labels).value(),
                             stop_gradient=True)
        nsp_label = VarBase(to_variable(nsp_label).value(),
                            stop_gradient=True)

        def mlm_fn(hv, w, b, lbl, wts):
            hv, w = _cast(amp, hv, w)
            ce = linear_smooth_ce(hv, w, b, lbl.astype(jnp.int32), 0.0)
            wts = wts.reshape(ce.shape)
            return jnp.sum(ce * wts) / (jnp.sum(wts) + 1e-6)

        mlm_loss = record(mlm_fn, h, self._mlm_w, self._mlm_b,
                          mlm_labels, to_variable(mlm_weights))

        cls = record(lambda xv: xv[:, 0, :], x)
        nsp_logits = self.nsp_out(self.pooler(cls))

        def nsp_fn(lg, lbl):
            lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
            ids = lbl.reshape(-1).astype(jnp.int32)
            return -jnp.mean(jnp.take_along_axis(
                lp, ids[:, None], axis=-1))

        nsp_loss = record(nsp_fn, nsp_logits, nsp_label)
        return record(lambda a, b: a + b, mlm_loss, nsp_loss)


def bert_base_dygraph(vocab_size=30522, seq_len=128, d_model=768,
                      d_ff=3072, n_head=12, n_layer=12, dropout_rate=0.1,
                      amp=False):
    """Build the imperative BERT and return (layer, feed_order,
    flops_per_example, tokens_per_example)."""
    model = BertPretrain(vocab_size, seq_len, d_model, d_ff, n_head,
                         n_layer, dropout_rate, amp=amp)
    per_layer_mac = (4 * d_model * d_model + 2 * d_model * d_ff
                     + 2 * seq_len * d_model)
    total_mac = n_layer * per_layer_mac + d_model * vocab_size
    feeds = ("input_ids", "segment_ids", "input_len", "mlm_labels",
             "mlm_weights", "nsp_label")
    return model, feeds, 2 * 3 * total_mac * seq_len, seq_len


def make_train_step(model, learning_rate=1e-4, b1=0.9, b2=0.999, eps=1e-8,
                    optimizer="adam", weight_decay=0.01):
    """jit-ready train step over the functional export:
    ``step(params, opt_state, key, *feeds) -> (loss, params', opt_state')``.
    The dygraph -> XLA path: one compiled step, donated state.
    ``optimizer``: "adam" or "lamb" (the BERT-pretraining recipe —
    same rule as the static ``lamb`` kernel, optimizer_ops.py:_lamb)."""
    if optimizer not in ("adam", "lamb"):
        raise ValueError("unknown optimizer %r (adam|lamb)" % optimizer)
    apply_fn, params0 = model.functional(rng=True)

    def loss_fn(params, key, *feeds):
        return apply_fn(params, key, *feeds)

    opt0 = {
        "m": jax.tree_util.tree_map(jnp.zeros_like, params0),
        "v": jax.tree_util.tree_map(jnp.zeros_like, params0),
        "t": jnp.zeros((), jnp.int32),
    }

    def step(params, opt_state, key, *feeds):
        loss, grads = jax.value_and_grad(loss_fn)(params, key, *feeds)
        t = opt_state["t"] + 1
        tf = t.astype(jnp.float32)
        m = jax.tree_util.tree_map(
            lambda mm, g: b1 * mm + (1 - b1) * g, opt_state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda vv, g: b2 * vv + (1 - b2) * g * g, opt_state["v"], grads)
        if optimizer == "lamb":
            def upd(p, mm, vv):
                m_hat = mm / (1 - b1 ** tf)
                v_hat = vv / (1 - b2 ** tf)
                r = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p
                p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
                r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
                trust = jnp.where((p_norm > 0) & (r_norm > 0),
                                  p_norm / r_norm, 1.0)
                return p - learning_rate * trust * r
        else:
            lr_t = learning_rate * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)

            def upd(p, mm, vv):
                return p - lr_t * mm / (jnp.sqrt(vv) + eps)
        new_params = jax.tree_util.tree_map(upd, params, m, v)
        return loss, new_params, {"m": m, "v": v, "t": t}

    return step, params0, opt0


def sample_batch(batch, seq_len, vocab_size, rng):
    """Synthetic batch matching ``models/bert.py`` feed schema/order."""
    return (
        rng.randint(0, vocab_size, (batch, seq_len)).astype(np.int32),
        rng.randint(0, 2, (batch, seq_len)).astype(np.int32),
        np.full((batch,), seq_len, np.int32),
        rng.randint(0, vocab_size, (batch, seq_len)).astype(np.int32),
        (rng.rand(batch, seq_len) < 0.15).astype(np.float32),
        rng.randint(0, 2, (batch, 1)).astype(np.int32),
    )
