"""Neural layers (ref ``python/paddle/fluid/layers/nn.py`` — 153 layers).

Every layer appends symbolic ops; shapes use -1 for the batch dim. The op
impls (``core/opimpl``) lower to jnp/lax, so a stack of these layers traces
into one fused XLA computation. Docstring citations point at the reference
layer definitions for parity checking.
"""

import numpy as np

from ..core.layer_helper import LayerHelper
from ..core.initializer import (ConstantInitializer, NormalInitializer,
                                UniformInitializer, XavierInitializer)
from ..core.param_attr import ParamAttr

__all__ = [
    "fc", "embedding", "conv2d", "conv3d", "conv2d_transpose",
    "depthwise_conv2d", "pool2d", "adaptive_pool2d", "batch_norm",
    "layer_norm", "group_norm", "dropout", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy",
    "smooth_softmax_with_cross_entropy", "fused_linear_smooth_ce",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "smooth_l1",
    "huber_loss", "label_smooth", "kldiv_loss", "bpr_loss", "hinge_loss",
    "log_loss", "margin_rank_loss", "mse_loss",
    "mean", "mul", "matmul", "scale", "clip", "clip_by_norm",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "reduce_prod",
    "topk", "argmax", "argmin", "argsort", "l2_normalize",
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod",
    "relu", "prelu", "maxout", "swish", "gelu", "brelu", "leaky_relu",
    "elu", "relu6", "pow", "stanh", "hard_sigmoid", "lrn",
    "one_hot", "lod_reset", "pad", "pad2d", "image_resize", "resize_bilinear",
    "resize_nearest", "grid_sampler", "pixel_shuffle", "im2sequence",
    "multi_head_attention", "scaled_dot_product_attention",
    "cached_multi_head_attention", "kv_cache_write", "cached_attention",
    "eva_summary", "eva_attention", "gated_feed_forward",
    "optimization_barrier",
    "cached_multi_head_attention_chunk", "kv_cache_write_chunk",
    "sparse_index", "latent_attention", "last_live_lane",
    "self_draft_accept",
    "row_conv", "autoincreased_step_counter", "cos_sim",
    "split", "warpctc", "nce", "hsigmoid", "cumsum",
    "linear_chain_crf", "crf_decoding",
    "dynamic_lstm", "dynamic_gru", "lstm", "gru_unit",
    "rms_norm", "rotary", "causal_self_attention",
    "causal_conv1d", "gated_delta_net", "mamba2_mixer", "routed_experts",
    "beam_search", "beam_search_gather", "beam_search_decode",
]


def _dtype(x):
    return str(x.dtype)


def _conv_out(size, k, s, p, d=1):
    if size is None or size < 0:
        return -1
    return (size + 2 * p - (d * (k - 1) + 1)) // s + 1


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None, param_dtype=None, precision=None):
    """Fully-connected layer (ref ``nn.py`` fc). Multiple inputs are summed
    after projection, matching the reference. ``param_dtype``: the type the
    weight is kept in where that is not the input's (a float32 input read
    against a weight the rest of the program keeps in bfloat16; the product
    comes out in the input's type). ``precision``: ``"highest"`` makes a
    float32 product exact on an MXU, which by default rounds its operands to
    bfloat16 (several passes)."""
    helper = LayerHelper("fc", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    import copy as _copy
    attrs = ParamAttr._to_attr(param_attr)
    if not isinstance(attrs, list):
        # one attr per input (ref fc w_0/w_1 suffixes): copies so unnamed
        # attrs each generate a fresh name; explicitly named attrs get a
        # _<i> suffix so the weights don't collide
        copies = [attrs]
        for i in range(1, len(inputs)):
            c = _copy.copy(attrs)
            if c.name is not None:
                c.name = "%s_%d" % (c.name, i)
            copies.append(c)
        attrs = copies
    mul_results = []
    for inp, attr in zip(inputs, attrs):
        in_shape = inp.shape
        flat_dim = int(np.prod(in_shape[num_flatten_dims:]))
        w = helper.create_parameter(attr, shape=[flat_dim, size],
                                    dtype=param_dtype or _dtype(inp))
        out_shape = tuple(in_shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(
            dtype=_dtype(inp), shape=out_shape)
        mul_attrs = {"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1}
        if precision is not None:
            mul_attrs["precision"] = str(precision)
        helper.append_op("mul", {"X": inp, "Y": w}, {"Out": tmp}, mul_attrs)
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            dtype=_dtype(inputs[0]), shape=mul_results[0].shape)
        helper.append_op("sum", {"X": mul_results}, {"Out": pre_bias}, {})
    pre_act = helper.append_bias_op(pre_bias)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    """Embedding lookup (ref ``nn.py`` embedding / ``lookup_table_op``).
    ``is_sparse`` marks the gradient for scatter-style updates;
    ``is_distributed`` marks the table for mesh sharding (the pserver
    distributed-lookup-table analog, see parallel/sharded_embedding)."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    w = helper.create_parameter(helper.param_attr, shape=list(size), dtype=dtype)
    w.is_distributed = is_distributed
    if is_sparse:
        # SelectedRows parity (ref ``framework/selected_rows.h:32``): the
        # gradient materializes as (rows, values) and optimizers take their
        # scatter-update branch instead of a full-table dense update.
        w.is_sparse_grad = True
    in_shape = input.shape
    base = in_shape[:-1] if (in_shape and in_shape[-1] == 1) else in_shape
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(base) + (size[1],))
    helper.append_op(
        "lookup_table", {"W": w, "Ids": input}, {"Out": out},
        {"is_sparse": is_sparse, "padding_idx": padding_idx if padding_idx is not None else -1})
    return out


# ---------------------------------------------------------------------------
# convolution / pooling
# ---------------------------------------------------------------------------

def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    """2-D convolution, NCHW (ref ``nn.py`` conv2d / ``conv_op.cc``).
    ``use_cudnn`` accepted for parity (XLA picks the conv algorithm)."""
    helper = LayerHelper("conv2d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    k = _pair(filter_size)
    s = _pair(stride)
    p = _pair(padding)
    d = _pair(dilation)
    n, c, h, w_ = input.shape
    std = (2.0 / (k[0] * k[1] * c)) ** 0.5
    filt = helper.create_parameter(
        helper.param_attr, shape=[num_filters, c // groups, k[0], k[1]],
        dtype=_dtype(input),
        default_initializer=NormalInitializer(0.0, std))
    out_shape = (n, num_filters, _conv_out(h, k[0], s[0], p[0], d[0]),
                 _conv_out(w_, k[1], s[1], p[1], d[1]))
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=out_shape)
    helper.append_op(
        "conv2d", {"Input": input, "Filter": filt}, {"Output": out},
        {"strides": list(s), "paddings": list(p), "dilations": list(d),
         "groups": groups})
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters],
                                    dtype=_dtype(input), is_bias=True)
        tmp = helper.create_variable_for_type_inference(
            dtype=_dtype(input), shape=out_shape)
        helper.append_op("elementwise_add", {"X": out, "Y": b}, {"Out": tmp},
                         {"axis": 1})
        out = tmp
    return helper.append_activation(out)


def depthwise_conv2d(input, num_filters, filter_size, **kwargs):
    kwargs["groups"] = input.shape[1]
    return conv2d(input, num_filters, filter_size, **kwargs)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    k = tuple(filter_size) if isinstance(filter_size, (list, tuple)) else (filter_size,) * 3
    s = tuple(stride) if isinstance(stride, (list, tuple)) else (stride,) * 3
    p = tuple(padding) if isinstance(padding, (list, tuple)) else (padding,) * 3
    d = tuple(dilation) if isinstance(dilation, (list, tuple)) else (dilation,) * 3
    n, c = input.shape[0], input.shape[1]
    spatial = input.shape[2:]
    filt = helper.create_parameter(
        helper.param_attr, shape=[num_filters, c // groups] + list(k),
        dtype=_dtype(input))
    out_shape = (n, num_filters) + tuple(
        _conv_out(sz, k[i], s[i], p[i], d[i]) for i, sz in enumerate(spatial))
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=out_shape)
    helper.append_op(
        "conv3d", {"Input": input, "Filter": filt}, {"Output": out},
        {"strides": list(s), "paddings": list(p), "dilations": list(d),
         "groups": groups})
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters],
                                    dtype=_dtype(input), is_bias=True)
        tmp = helper.create_variable_for_type_inference(
            dtype=_dtype(input), shape=out_shape)
        helper.append_op("elementwise_add", {"X": out, "Y": b}, {"Out": tmp},
                         {"axis": 1})
        out = tmp
    return helper.append_activation(out)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    s = _pair(stride)
    p = _pair(padding)
    d = _pair(dilation)
    n, c, h, w_ = input.shape
    if filter_size is None:
        assert output_size is not None
        osz = _pair(output_size)
        k = tuple(osz[i] - (input.shape[2 + i] - 1) * s[i] + 2 * p[i]
                  for i in range(2))
    else:
        k = _pair(filter_size)
    filt = helper.create_parameter(
        helper.param_attr, shape=[c, num_filters // groups, k[0], k[1]],
        dtype=_dtype(input))
    oh = (h - 1) * s[0] - 2 * p[0] + d[0] * (k[0] - 1) + 1 if h > 0 else -1
    ow = (w_ - 1) * s[1] - 2 * p[1] + d[1] * (k[1] - 1) + 1 if w_ > 0 else -1
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(n, num_filters, oh, ow))
    helper.append_op(
        "conv2d_transpose", {"Input": input, "Filter": filt},
        {"Output": out},
        {"strides": list(s), "paddings": list(p), "dilations": list(d),
         "groups": groups})
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters],
                                    dtype=_dtype(input), is_bias=True)
        tmp = helper.create_variable_for_type_inference(
            dtype=_dtype(input), shape=out.shape)
        helper.append_op("elementwise_add", {"X": out, "Y": b}, {"Out": tmp},
                         {"axis": 1})
        out = tmp
    return helper.append_activation(out)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    helper = LayerHelper("pool2d", name=name)
    k = _pair(pool_size)
    s = _pair(pool_stride)
    p = _pair(pool_padding)
    n, c, h, w_ = input.shape
    if global_pooling:
        out_shape = (n, c, 1, 1)
    else:
        rnd = (lambda a, b: -(-a // b)) if ceil_mode else (lambda a, b: a // b)
        oh = rnd(h + 2 * p[0] - k[0], s[0]) + 1 if h > 0 else -1
        ow = rnd(w_ + 2 * p[1] - k[1], s[1]) + 1 if w_ > 0 else -1
        out_shape = (n, c, oh, ow)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=out_shape)
    helper.append_op(
        "pool2d", {"X": input}, {"Out": out},
        {"pooling_type": pool_type, "ksize": list(k), "strides": list(s),
         "paddings": list(p), "global_pooling": global_pooling,
         "ceil_mode": ceil_mode, "exclusive": exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    k = _pair(pool_size)
    n, c = input.shape[0], input.shape[1]
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(n, c, k[0], k[1]))
    helper.append_op(
        "pool2d", {"X": input}, {"Out": out},
        {"pooling_type": pool_type, "ksize": list(k), "adaptive": True})
    return out


# ---------------------------------------------------------------------------
# normalization / dropout
# ---------------------------------------------------------------------------

def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    """BatchNorm (ref ``nn.py`` batch_norm / ``batch_norm_op.cc``). Moving
    stats are persistable state vars updated functionally each step."""
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = _dtype(input)
    scale = helper.create_parameter(
        helper.param_attr, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(
        helper.bias_attr, shape=[c], dtype=dtype, is_bias=True)
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False),
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    variance = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False),
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    mean.stop_gradient = True
    variance.stop_gradient = True
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    shape=input.shape)
    saved_mean = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(c,), stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(c,), stop_gradient=True)
    helper.append_op(
        "batch_norm",
        {"X": input, "Scale": scale, "Bias": bias, "Mean": mean,
         "Variance": variance},
        {"Y": out, "MeanOut": mean, "VarianceOut": variance,
         "SavedMean": saved_mean, "SavedVariance": saved_var},
        {"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
         "data_layout": data_layout, "use_global_stats": use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None, param_dtype=None):
    """``param_dtype``: the type scale and shift are kept in where that is
    not the input's (see ``fc``)."""
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = _dtype(input)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": input}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, shape=norm_shape, dtype=param_dtype or dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = s
    if shift:
        b = helper.create_parameter(helper.bias_attr, shape=norm_shape,
                                    dtype=param_dtype or dtype, is_bias=True)
        inputs["Bias"] = b
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    shape=input.shape)
    mean = helper.create_variable_for_type_inference(
        dtype=dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True)
    var = helper.create_variable_for_type_inference(
        dtype=dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True)
    helper.append_op("layer_norm", inputs,
                     {"Y": out, "Mean": mean, "Variance": var},
                     {"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1]
    dtype = _dtype(input)
    scale = helper.create_parameter(
        helper.param_attr, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, shape=[c], dtype=dtype,
                                   is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    shape=input.shape)
    helper.append_op("group_norm",
                     {"X": input, "Scale": scale, "Bias": bias},
                     {"Y": out}, {"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    mask = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=x.shape, stop_gradient=True)
    helper.append_op("dropout", {"X": x}, {"Out": out, "Mask": mask},
                     {"dropout_prob": dropout_prob, "is_test": is_test,
                      "dropout_implementation": dropout_implementation})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("lrn", {"X": input}, {"Out": out},
                     {"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


# ---------------------------------------------------------------------------
# generic op-emitters used by many layers
# ---------------------------------------------------------------------------

def _unary_layer(op_type, x, attrs=None, name=None, out_shape=None,
                 out_dtype=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(
        dtype=out_dtype or _dtype(x),
        shape=x.shape if out_shape is None else out_shape)
    helper.append_op(op_type, {"X": x}, {"Out": out}, attrs or {})
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    return _unary_layer("softmax", input, {"axis": axis}, name)


def log_softmax(input, axis=-1, name=None):
    return _unary_layer("log_softmax", input, {"axis": axis}, name)


def relu(x, name=None):
    return _unary_layer("relu", x, name=name)


def relu6(x, threshold=6.0, name=None):
    return _unary_layer("relu6", x, {"threshold": threshold}, name)


def leaky_relu(x, alpha=0.02, name=None):
    return _unary_layer("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _unary_layer("elu", x, {"alpha": alpha}, name)


def gelu(x, approximate=None, name=None):
    """``approximate=None`` (default) lets the op pick: exact erf in f32,
    tanh-approx under AMP (see ``opimpl/math_ops.py:_gelu``). Pass an
    explicit bool to pin the form."""
    attrs = {} if approximate is None else {"approximate": approximate}
    return _unary_layer("gelu", x, attrs, name)


def swish(x, beta=1.0, name=None):
    return _unary_layer("swish", x, {"beta": beta}, name)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _unary_layer("brelu", x, {"t_min": t_min, "t_max": t_max}, name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _unary_layer("stanh", x, {"scale_a": scale_a, "scale_b": scale_b},
                        name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _unary_layer("hard_sigmoid", x, {"slope": slope, "offset": offset},
                        name)


def pow(x, factor=1.0, name=None):
    return _unary_layer("pow", x, {"factor": factor}, name)


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(
        helper.param_attr, shape=alpha_shape, dtype=_dtype(x),
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("prelu", {"X": x, "Alpha": alpha}, {"Out": out},
                     {"mode": mode})
    return out


def maxout(x, groups, name=None):
    n, c, h, w = x.shape
    return _unary_layer("maxout", x, {"groups": groups}, name,
                        out_shape=(n, c // groups, h, w))


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("scale", {"X": x}, {"Out": out},
                     {"scale": scale, "bias": bias,
                      "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def optimization_barrier(x, name=None):
    """``x`` as it is computed: the compiler fuses nothing across this op
    and carries no layout through it (``jax.lax.optimization_barrier``).
    Put between a projection and ops that view its product a head at a
    time where the heads are no multiple of 128 wide: the TPU compiler
    otherwise lays the projection's weight out for that view, a copy of
    the whole matrix in every run (MiMo-V2's 192-wide query heads: 100 MB
    a layer a decode step)."""
    return _unary_layer("optimization_barrier", x, name=name)


def clip(x, min, max, name=None):
    return _unary_layer("clip", x, {"min": min, "max": max}, name)


def clip_by_norm(x, max_norm, name=None):
    return _unary_layer("clip_by_norm", x, {"max_norm": max_norm}, name)


def mean(x, name=None):
    return _unary_layer("mean", x, name=name, out_shape=())


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    from ..core.op_registry import static_bcast_shape

    helper = LayerHelper(op_type, act=act, name=name)
    try:
        out_shape = static_bcast_shape(x.shape, y.shape, axis)
    except ValueError:
        # statically infeasible: declare x's shape and let the analysis
        # shape pass report the mismatch with provenance
        out_shape = x.shape
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=out_shape)
    helper.append_op(op_type, {"X": x, "Y": y}, {"Out": out}, {"axis": axis})
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mod", x, y, axis, act, name)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out_shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=out_shape)
    helper.append_op("mul", {"X": x, "Y": y}, {"Out": out},
                     {"x_num_col_dims": x_num_col_dims,
                      "y_num_col_dims": y_num_col_dims})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
    out_shape = tuple(batch) + (xs[-2], ys[-1]) if len(xs) >= 2 and len(ys) >= 2 else None
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=out_shape)
    helper.append_op("matmul", {"X": x, "Y": y}, {"Out": out},
                     {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                      "alpha": alpha})
    return out


def _reduce_layer(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    if dim is None:
        out_shape = ()
        reduce_all = True
        dims = [0]
    else:
        dims = dim if isinstance(dim, (list, tuple)) else [dim]
        reduce_all = False
        nd = len(input.shape)
        axes = {d % nd for d in dims}
        if keep_dim:
            out_shape = tuple(1 if i in axes else s
                              for i, s in enumerate(input.shape))
        else:
            out_shape = tuple(s for i, s in enumerate(input.shape)
                              if i not in axes)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=out_shape)
    helper.append_op(op_type, {"X": input}, {"Out": out},
                     {"dim": list(dims), "keep_dim": keep_dim,
                      "reduce_all": reduce_all})
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    return _unary_layer("cumsum", x,
                        {"axis": axis, "exclusive": exclusive,
                         "reverse": reverse}, name)


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    nd = len(input.shape)
    axis = dim % nd
    in_sz = input.shape[axis]
    if isinstance(num_or_sections, int):
        sections = [in_sz // num_or_sections] * num_or_sections
        attrs = {"num": num_or_sections, "axis": axis}
    else:
        sections = list(num_or_sections)
        attrs = {"sections": sections, "axis": axis}
    outs = []
    for sec in sections:
        shape = tuple(sec if i == axis else s for i, s in enumerate(input.shape))
        outs.append(helper.create_variable_for_type_inference(
            dtype=_dtype(input), shape=shape))
    helper.append_op("split", {"X": input}, {"Out": outs}, attrs)
    return outs


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    out_shape = tuple(input.shape[:-1]) + (k,)
    values = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=out_shape)
    indices = helper.create_variable_for_type_inference(
        dtype="int32", shape=out_shape, stop_gradient=True)
    helper.append_op("top_k", {"X": input},
                     {"Out": values, "Indices": indices}, {"k": k})
    return values, indices


def argmax(x, axis=0, name=None):
    shape = tuple(s for i, s in enumerate(x.shape) if i != axis % len(x.shape))
    return _unary_layer("argmax", x, {"axis": axis}, name, out_shape=shape,
                        out_dtype="int32")


def argmin(x, axis=0, name=None):
    shape = tuple(s for i, s in enumerate(x.shape) if i != axis % len(x.shape))
    return _unary_layer("argmin", x, {"axis": axis}, name, out_shape=shape,
                        out_dtype="int32")


def argsort(input, axis=-1, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    ids = helper.create_variable_for_type_inference(
        dtype="int32", shape=input.shape, stop_gradient=True)
    helper.append_op("argsort", {"X": input},
                     {"Out": out, "Indices": ids}, {"axis": axis})
    return out, ids


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    norm = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                     shape=x.shape)
    helper.append_op("norm", {"X": x}, {"Out": out, "Norm": norm},
                     {"axis": axis, "epsilon": epsilon})
    return out


def cos_sim(X, Y, name=None):
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    prod = elementwise_mul(xn, yn)
    return reduce_sum(prod, dim=-1, keep_dim=True)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy(input, label, soft_label=False, ignore_index=-100,
                  name=None):
    helper = LayerHelper("cross_entropy", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=tuple(input.shape[:-1]) + (1,))
    helper.append_op("cross_entropy", {"X": input, "Label": label},
                     {"Y": out},
                     {"soft_label": soft_label, "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    loss = helper.create_variable_for_type_inference(
        dtype=_dtype(logits), shape=tuple(logits.shape[:-1]) + (1,))
    softmax_out = helper.create_variable_for_type_inference(
        dtype=_dtype(logits), shape=logits.shape)
    helper.append_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label},
                     {"Loss": loss, "Softmax": softmax_out},
                     {"soft_label": soft_label, "ignore_index": ignore_index})
    if return_softmax:
        return loss, softmax_out
    return loss


def smooth_softmax_with_cross_entropy(logits, label, epsilon=0.0):
    """Fused label-smoothed softmax CE (closed form, single logits pass).

    TPU-first replacement for the reference's ``label_smooth`` +
    ``softmax_with_cross_entropy`` pair (``operators/label_smooth_op.cc``,
    ``softmax_with_cross_entropy_op.cc``), which materializes a full
    [..., V] soft-label tensor. Returns per-position loss with the class
    axis reduced away (shape ``logits.shape[:-1]``)."""
    helper = LayerHelper("smooth_softmax_with_cross_entropy")
    loss = helper.create_variable_for_type_inference(
        dtype="float32", shape=tuple(logits.shape[:-1]))
    helper.append_op("smooth_softmax_ce",
                     {"Logits": logits, "Label": label},
                     {"Loss": loss}, {"epsilon": float(epsilon)})
    return loss


def fused_linear_smooth_ce(input, label, size, epsilon=0.0,
                           param_attr=None, bias_attr=None, name=None):
    """Vocab projection + label-smoothed softmax CE, fused (the TPU
    replacement for ``fc(size=V)`` + ``smooth_softmax_with_cross_entropy``:
    on TPU the [.., V] logits stay in VMEM — see ``ops/fused_ce.py``).
    ``input``: [..., D]; ``label``: int ids shaped like ``input[:-1]``.
    Returns per-position f32 loss of shape ``input.shape[:-1]``."""
    helper = LayerHelper("fused_linear_smooth_ce", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    d_in = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr, shape=[d_in, size],
                                dtype=_dtype(input))
    inputs = {"X": input, "W": w, "Label": label}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[size],
                                    dtype=_dtype(input), is_bias=True)
        inputs["Bias"] = b
    loss = helper.create_variable_for_type_inference(
        dtype="float32", shape=tuple(input.shape[:-1]))
    helper.append_op("fused_linear_smooth_ce", inputs, {"Loss": loss},
                     {"epsilon": float(epsilon)})
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     {"X": x, "Label": label}, {"Out": out},
                     {"ignore_index": ignore_index, "normalize": normalize})
    return out


def square_error_cost(input, label, name=None):
    helper = LayerHelper("square_error_cost", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("square_error_cost", {"X": input, "Y": label},
                     {"Out": out}, {})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=1.0,
              name=None):
    helper = LayerHelper("smooth_l1_loss", name=name)
    diff = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                     shape=x.shape)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(x.shape[0], 1))
    inputs = {"X": x, "Y": y}
    if inside_weight is not None:
        inputs["InsideWeight"] = inside_weight
    if outside_weight is not None:
        inputs["OutsideWeight"] = outside_weight
    helper.append_op("smooth_l1_loss", inputs,
                     {"Diff": diff, "Out": out}, {"sigma": sigma})
    return out


def huber_loss(input, label, delta, name=None):
    helper = LayerHelper("huber_loss", name=name)
    residual = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                         shape=input.shape)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("huber_loss", {"X": input, "Y": label},
                     {"Residual": residual, "Out": out}, {"delta": delta})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    shape=label.shape)
    inputs = {"X": label}
    if prior_dist is not None:
        inputs["PriorDist"] = prior_dist
    helper.append_op("label_smooth", inputs, {"Out": out},
                     {"epsilon": float(epsilon)})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    shape = () if reduction in ("mean", "sum", "batchmean") else x.shape
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=shape)
    helper.append_op("kldiv_loss", {"X": x, "Target": target},
                     {"Loss": out}, {"reduction": reduction})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(input.shape[0], 1))
    helper.append_op("bpr_loss", {"X": input, "Label": label}, {"Y": out}, {})
    return out


def hinge_loss(input, label, name=None):
    helper = LayerHelper("hinge_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("hinge_loss", {"Logits": input, "Labels": label},
                     {"Loss": out}, {})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("log_loss", {"Predicted": input, "Labels": label},
                     {"Loss": out}, {"epsilon": epsilon})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(left),
                                                    shape=left.shape)
    act = helper.create_variable_for_type_inference(dtype=_dtype(left),
                                                    shape=left.shape)
    helper.append_op("margin_rank_loss",
                     {"X1": left, "X2": right, "Label": label},
                     {"Out": out, "Activated": act}, {"margin": margin})
    return out


def mse_loss(input, label, name=None):
    helper = LayerHelper("mse_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=())
    helper.append_op("mse_loss", {"X": input, "Y": label}, {"Out": out}, {})
    return out


def warpctc(input, label, blank=0, norm_by_times=False, input_length=None,
            label_length=None, name=None):
    """CTC loss (ref ``warpctc_op.cc``): padded ``[B, T, C]`` logits
    (softmax applied internally, warp-ctc convention), ``label`` [B, L],
    per-example ``input_length``/``label_length`` [B] (defaulting to the
    padded sizes). Returns [B, 1] negative log likelihood. The alpha
    recursion runs as a lax.scan in log space — no external warp-ctc lib,
    gradient via autodiff through the scan."""
    helper = LayerHelper("warpctc", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(input.shape[0], 1))
    inputs = {"Logits": input, "Label": label}
    if input_length is not None:
        inputs["LogitsLength"] = input_length
    if label_length is not None:
        inputs["LabelLength"] = label_length
    helper.append_op("warpctc", inputs, {"Loss": out},
                     {"blank": blank, "norm_by_times": norm_by_times})
    return out


def linear_chain_crf(input, label, param_attr=None, length=None, name=None):
    """Linear-chain CRF negative log likelihood (ref
    ``linear_chain_crf_op.cc``): ``input`` [B, T, D] emissions, ``label``
    [B, T]; creates the [D+2, D] transition parameter (row 0 start, row 1
    end, rows 2.. pairwise). Returns [B, 1] cost to minimize."""
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr,
                         name=name)
    size = input.shape[-1]
    transition = helper.create_parameter(
        helper.param_attr, shape=[size + 2, size], dtype=_dtype(input))
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(input.shape[0], 1))
    inputs = {"Emission": input, "Transition": transition, "Label": label}
    if length is not None:
        inputs["Length"] = length
    helper.append_op("linear_chain_crf", inputs,
                     {"LogLikelihood": out}, {})
    return out


def crf_decoding(input, param_attr, label=None, length=None, name=None):
    """Viterbi decode with the CRF's transition parameter (ref
    ``crf_decoding_op.cc``); pass the same ``param_attr`` name used by
    ``linear_chain_crf``. With ``label`` given, returns the per-position
    correctness mask instead of the path (reference semantics)."""
    helper = LayerHelper("crf_decoding", param_attr=param_attr, name=name)
    size = input.shape[-1]
    from ..core import framework as _fw
    attr = ParamAttr._to_attr(param_attr)
    gb = _fw.default_main_program().global_block()
    if attr.name and gb.has_var(attr.name):
        # reuse the trained transition var in-program (no duplicate init)
        transition = gb.var(attr.name)
    else:
        # separate infer program: create under the shared name; values come
        # from the scope at run time
        transition = helper.create_parameter(
            helper.param_attr, shape=[size + 2, size], dtype=_dtype(input))
    out = helper.create_variable_for_type_inference(
        dtype="int32", shape=tuple(input.shape[:2]))
    inputs = {"Emission": input, "Transition": transition}
    if label is not None:
        inputs["Label"] = label
    if length is not None:
        inputs["Length"] = length
    helper.append_op("crf_decoding", inputs, {"ViterbiPath": out}, {})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None, sampler="uniform",
        custom_dist=None, seed=0, is_sparse=False):
    """Noise-contrastive estimation loss (ref ``nce_op.cc``): ``input``
    [B, D], ``label`` [B, 1]; samples ``num_neg_samples`` noise classes per
    example (uniform or log_uniform). ``seed`` != 0 fixes the sample draw
    (reference parity); 0 threads the executor PRNG."""
    if custom_dist is not None or sample_weight is not None:
        raise NotImplementedError(
            "nce custom_dist/sample_weight are not supported; use "
            "sampler='uniform' or 'log_uniform'")
    if sampler not in ("uniform", "log_uniform"):
        raise ValueError("unsupported nce sampler %r" % (sampler,))
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=_dtype(input))
    b = helper.create_parameter(helper.bias_attr,
                                shape=[num_total_classes],
                                dtype=_dtype(input), is_bias=True)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(input.shape[0], 1))
    helper.append_op(
        "nce", {"Input": input, "Label": label, "Weight": w, "Bias": b},
        {"Cost": out},
        {"num_neg_samples": num_neg_samples or 10, "sampler": sampler,
         "seed": seed})
    return out


def _hsigmoid_simple_code_tables(num_classes):
    """Default complete-binary-tree paths (ref ``math/matrix_bit_code.h``
    SimpleCode): class c maps to code c + num_classes; node index at bit i
    is (code >> (i+1)) - 1, bit value (code >> i) & 1."""
    rows = []
    for c in range(num_classes):
        code = c + num_classes
        length = code.bit_length() - 1
        rows.append(([(code >> (i + 1)) - 1 for i in range(length)],
                     [(code >> i) & 1 for i in range(length)]))
    max_len = max(len(r[0]) for r in rows)
    table = [r[0] + [-1] * (max_len - len(r[0])) for r in rows]
    codes = [[float(v) for v in r[1]] + [0.0] * (max_len - len(r[1]))
             for r in rows]
    return table, codes


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    """Hierarchical sigmoid (ref ``hierarchical_sigmoid_op.cc``): log-time
    softmax over a class tree. Default: complete binary tree with
    ``num_classes - 1`` internal nodes; custom: ``path_table``/``path_code``
    vars [B, L] (pad with -1)."""
    helper = LayerHelper("hsigmoid", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    n_nodes = num_classes if is_custom else num_classes - 1
    w = helper.create_parameter(helper.param_attr, shape=[n_nodes, dim],
                                dtype=_dtype(input))
    b = helper.create_parameter(helper.bias_attr, shape=[n_nodes],
                                dtype=_dtype(input), is_bias=True)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(input.shape[0], 1))
    inputs = {"Input": input, "Label": label, "W": w, "Bias": b}
    attrs = {"num_classes": num_classes}
    if is_custom:
        inputs["PathTable"] = path_table
        inputs["PathCode"] = path_code
    else:
        table, codes = _hsigmoid_simple_code_tables(num_classes)
        attrs["path_table"] = table
        attrs["path_code"] = codes
    helper.append_op("hsigmoid", inputs, {"Cost": out}, attrs)
    return out


# ---------------------------------------------------------------------------
# beam-search decode (ref ``nn.py`` beam_search / beam_search_decode over
# ``operators/beam_search_op.cc``; TPU-native dense [B, K] re-design — see
# ``core/opimpl/decode_ops.py``)
# ---------------------------------------------------------------------------

def beam_search(pre_ids, pre_scores, scores, beam_size, end_id,
                return_parent_idx=True, name=None):
    """One pruning step: ``pre_ids``/``pre_scores`` [B, K], ``scores``
    [B, K, V] next-token log-probs. Returns (selected_ids, selected_scores,
    parent_idx), each [B, K]. Step 0 convention: initialize pre_scores to
    [0, -1e9, ...] so the duplicated start beams collapse to one."""
    helper = LayerHelper("beam_search", name=name)
    b, k = tuple(pre_ids.shape)[:2]
    sel_ids = helper.create_variable_for_type_inference(
        dtype=str(pre_ids.dtype), shape=(b, k))
    sel_scores = helper.create_variable_for_type_inference(
        dtype=str(pre_scores.dtype), shape=(b, k))
    parent = helper.create_variable_for_type_inference(
        dtype="int32", shape=(b, k))
    helper.append_op(
        "beam_search_step",
        {"PreIds": pre_ids, "PreScores": pre_scores, "Scores": scores},
        {"SelectedIds": sel_ids, "SelectedScores": sel_scores,
         "ParentIdx": parent},
        {"beam_size": beam_size, "end_id": end_id})
    if return_parent_idx:
        return sel_ids, sel_scores, parent
    return sel_ids, sel_scores


def beam_search_gather(x, parent_idx, name=None):
    """Reorder per-beam state ``x`` [B, K, ...] by ``parent_idx`` [B, K]
    (the reference reorders hidden state via LoD; here an explicit gather)."""
    helper = LayerHelper("beam_search_gather", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=x.shape)
    helper.append_op("beam_search_gather", {"X": x, "Ids": parent_idx},
                     {"Out": out}, {})
    return out


def beam_search_decode(ids_array, parents_array, length, final_scores,
                       beam_size, end_id, name=None):
    """Backtrack per-step (ids, parents) arrays — written by ``array_write``
    inside the decode loop — into sentences [B, K, T] + scores [B, K]
    (ref ``beam_search_decode_op.cc``)."""
    helper = LayerHelper("beam_search_decode", name=name)
    sent = helper.create_variable_for_type_inference(dtype="int64",
                                                     shape=None)
    sscores = helper.create_variable_for_type_inference(
        dtype=str(final_scores.dtype), shape=final_scores.shape)
    helper.append_op(
        "beam_search_decode",
        {"IdsArray": ids_array, "ParentsArray": parents_array,
         "Length": length, "FinalScores": final_scores},
        {"SentenceIds": sent, "SentenceScores": sscores},
        {"beam_size": beam_size, "end_id": end_id})
    return sent, sscores


# ---------------------------------------------------------------------------
# misc tensor-ish layers that live in nn.py in the reference
# ---------------------------------------------------------------------------

def one_hot(input, depth, name=None):
    base = input.shape[:-1] if input.shape and input.shape[-1] == 1 else input.shape
    return _unary_layer("one_hot", input, {"depth": depth}, name,
                        out_shape=tuple(base) + (depth,), out_dtype="float32")


def lod_reset(x, y=None, target_lod=None):
    """LoD is not a TPU concept; identity for API parity (sequence info
    travels as explicit length tensors)."""
    return x


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    shape = tuple(
        (s + paddings[2 * i] + paddings[2 * i + 1]) if s >= 0 else -1
        for i, s in enumerate(x.shape))
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=shape)
    helper.append_op("pad", {"X": x}, {"Out": out},
                     {"paddings": list(paddings), "pad_value": pad_value})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    n, c, h, w = input.shape
    shape = (n, c, h + paddings[0] + paddings[1] if h >= 0 else -1,
             w + paddings[2] + paddings[3] if w >= 0 else -1)
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=shape)
    helper.append_op("pad2d", {"X": input}, {"Out": out},
                     {"paddings": list(paddings), "mode": mode,
                      "pad_value": pad_value})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    helper = LayerHelper("image_resize", name=name)
    n, c, h, w = input.shape
    if out_shape is not None:
        oh, ow = out_shape
    else:
        oh, ow = int(h * scale), int(w * scale)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(n, c, oh, ow))
    op_type = "bilinear_interp" if resample.upper() == "BILINEAR" else "nearest_interp"
    helper.append_op(op_type, {"X": input}, {"Out": out},
                     {"out_h": oh, "out_w": ow,
                      "align_corners": align_corners})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        actual_shape, align_corners)


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    n, c = x.shape[0], x.shape[1]
    oh, ow = grid.shape[1], grid.shape[2]
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(n, c, oh, ow))
    helper.append_op("grid_sampler", {"X": x, "Grid": grid},
                     {"Output": out}, {})
    return out


def pixel_shuffle(x, upscale_factor, name=None):
    helper = LayerHelper("pixel_shuffle", name=name)
    n, c, h, w = x.shape
    r = upscale_factor
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(n, c // (r * r), h * r, w * r))
    helper.append_op("pixel_shuffle", {"X": x}, {"Out": out},
                     {"upscale_factor": r})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", name=name)
    k = _pair(filter_size)
    s = _pair(stride)
    p = padding if isinstance(padding, (list, tuple)) else [padding] * 4
    n, c, h, w = input.shape
    oh = (h + p[0] + p[2] - k[0]) // s[0] + 1 if h > 0 else -1
    ow = (w + p[1] + p[3] - k[1]) // s[1] + 1 if w > 0 else -1
    rows = n * oh * ow if n > 0 and oh > 0 and ow > 0 else -1
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(rows, c * k[0] * k[1]))
    helper.append_op("im2sequence", {"X": input}, {"Out": out},
                     {"kernels": list(k), "strides": list(s),
                      "paddings": list(p)})
    return out


# ---------------------------------------------------------------------------
# recurrent layers (ref ``nn.py`` dynamic_lstm/dynamic_gru over
# ``operators/lstm_op.cc``/``gru_op.cc``; TPU-native: lax.scan over padded
# [B, T, *] batches + explicit lengths instead of LoD)
# ---------------------------------------------------------------------------

def dynamic_lstm(input, size, lengths=None, h_0=None, c_0=None,
                 param_attr=None, bias_attr=None,
                 use_peepholes=False, is_reverse=False,
                 gate_activation="sigmoid", cell_activation="tanh",
                 candidate_activation="tanh", dtype=None, name=None):
    """LSTM over a pre-projected sequence (ref ``nn.py`` dynamic_lstm).

    ``input`` is ``[B, T, 4H]`` — the x@W projection done by a preceding
    ``fc`` (matching the reference contract where ``size = 4*hidden`` and the
    input projection is the user's fc). ``lengths`` `[B]` masks padding (the
    LoD replacement); ``h_0``/``c_0`` `[B, H]` seed the recurrent state
    (zeros when omitted). Returns ``(hidden [B,T,H], cell [B,T,H])``.
    ``use_peepholes`` accepted for API parity (ignored: peephole connections
    are off the MXU critical path and rarely used)."""
    helper = LayerHelper("dynamic_lstm", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    hidden_size = size // 4
    dtype = dtype or _dtype(input)
    w = helper.create_parameter(helper.param_attr,
                                shape=[hidden_size, 4 * hidden_size],
                                dtype=dtype)
    b = helper.create_parameter(helper.bias_attr, shape=[4 * hidden_size],
                                dtype=dtype, is_bias=True)
    b_sz, t_sz = input.shape[0], input.shape[1]
    hidden = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, hidden_size))
    cell = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, hidden_size))
    inputs = {"Input": input, "Weight": w, "Bias": b}
    if lengths is not None:
        inputs["Lengths"] = lengths
    if h_0 is not None:
        inputs["H0"] = h_0
    if c_0 is not None:
        inputs["C0"] = c_0
    helper.append_op("lstm_seq", inputs, {"Hidden": hidden, "Cell": cell},
                     {"is_reverse": is_reverse})
    return hidden, cell


def dynamic_lstmp(input, size, proj_size, lengths=None, h_0=None, c_0=None,
                  param_attr=None, bias_attr=None, use_peepholes=False,
                  is_reverse=False, gate_activation="sigmoid",
                  cell_activation="tanh", candidate_activation="tanh",
                  proj_activation="tanh", cell_clip=0.0, proj_clip=0.0,
                  dtype=None, name=None):
    """Projection LSTM (ref ``nn.py`` dynamic_lstmp / ``lstmp_op.cc``):
    the recurrent state is the P-dim projection of the hidden state.
    ``input`` is ``[B, T, 4H]`` pre-projected; returns
    ``(projection [B,T,P], cell [B,T,H])``."""
    helper = LayerHelper("dynamic_lstmp", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    hidden_size = size // 4
    dtype = dtype or _dtype(input)
    w = helper.create_parameter(helper.param_attr,
                                shape=[proj_size, 4 * hidden_size],
                                dtype=dtype)
    import copy as _copy
    pattr = ParamAttr._to_attr(param_attr)
    pattr = _copy.copy(pattr)
    if pattr.name is not None:
        pattr.name = pattr.name + "_proj"
    wp = helper.create_parameter(pattr, shape=[hidden_size, proj_size],
                                 dtype=dtype)
    b = helper.create_parameter(helper.bias_attr, shape=[4 * hidden_size],
                                dtype=dtype, is_bias=True)
    b_sz, t_sz = input.shape[0], input.shape[1]
    proj = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, proj_size))
    cell = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, hidden_size))
    inputs = {"Input": input, "Weight": w, "ProjWeight": wp, "Bias": b}
    if lengths is not None:
        inputs["Lengths"] = lengths
    if h_0 is not None:
        inputs["H0"] = h_0
    if c_0 is not None:
        inputs["C0"] = c_0
    helper.append_op("lstmp_seq", inputs,
                     {"Projection": proj, "Cell": cell},
                     {"is_reverse": is_reverse, "cell_clip": cell_clip,
                      "proj_clip": proj_clip,
                      "proj_activation": proj_activation})
    return proj, cell


def attention_lstm(input, size, lengths=None, h_0=None, c_0=None,
                   param_attr=None, bias_attr=None, name=None):
    """Attention LSTM (ref ``attention_lstm_op.cc``): each step attends
    over the whole sequence with c_{t-1} and feeds the pooled vector to
    an LSTM cell. ``input`` [B, T, M]; returns (hidden [B,T,D], cell)."""
    helper = LayerHelper("attention_lstm", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    d = size
    m = int(input.shape[-1])
    dtype = _dtype(input)
    aw = helper.create_parameter(helper.param_attr, shape=[m + d, 1],
                                 dtype=dtype)
    ab = helper.create_parameter(helper.bias_attr, shape=[1], dtype=dtype,
                                 is_bias=True)
    asc = helper.create_parameter(None, shape=[1], dtype=dtype)
    asb = helper.create_parameter(None, shape=[1], dtype=dtype,
                                  is_bias=True)
    import copy as _copy
    pattr = _copy.copy(ParamAttr._to_attr(param_attr))
    if pattr.name is not None:
        pattr.name = pattr.name + "_lstm"
    lw = helper.create_parameter(pattr, shape=[m + d, 4 * d], dtype=dtype)
    lb = helper.create_parameter(None, shape=[4 * d], dtype=dtype,
                                 is_bias=True)
    b_sz, t_sz = input.shape[0], input.shape[1]
    hidden = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, d))
    cell = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, d))
    inputs = {"X": input, "AttentionWeight": aw, "AttentionBias": ab,
              "AttentionScalar": asc, "AttentionScalarBias": asb,
              "LSTMWeight": lw, "LSTMBias": lb}
    if lengths is not None:
        inputs["Lengths"] = lengths
    if h_0 is not None:
        inputs["H0"] = h_0
    if c_0 is not None:
        inputs["C0"] = c_0
    helper.append_op("attention_lstm", inputs,
                     {"Hidden": hidden, "Cell": cell}, {})
    return hidden, cell


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """Tree-based convolution (ref ``nn.py`` tree_conv /
    ``tree_conv_op.cc``, TBCNN): continuous-binary-tree filters over
    subtree patches. Returns [*, N, output_size, num_filters] (batched)
    like the reference's [N, output_size, num_filters]."""
    helper = LayerHelper("tree_conv", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = _dtype(nodes_vector)
    fdim = int(nodes_vector.shape[-1])
    w = helper.create_parameter(
        helper.param_attr, shape=[fdim, 3, output_size, num_filters],
        dtype=dtype)
    lead = tuple(nodes_vector.shape[:-1])
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=lead + (output_size, num_filters))
    helper.append_op("tree_conv",
                     {"NodesVector": nodes_vector, "EdgeSet": edge_set,
                      "Filter": w},
                     {"Out": out}, {"max_depth": max_depth})
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr,
                                    shape=[num_filters], dtype=dtype,
                                    is_bias=True)
        biased = helper.create_variable_for_type_inference(
            dtype=dtype, shape=out.shape)
        helper.append_op("elementwise_add", {"X": out, "Y": b},
                         {"Out": biased}, {"axis": -1})
        out = biased
    return helper.append_activation(out)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    """3-D pooling over NCDHW input (ref ``nn.py`` pool3d)."""
    def _t3(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    helper = LayerHelper("pool3d", name=name)
    k, s, p = _t3(pool_size), _t3(pool_stride), _t3(pool_padding)
    n, c, d, h, w_ = input.shape
    if global_pooling:
        out_shape = (n, c, 1, 1, 1)
    else:
        rnd = (lambda a, b: -(-a // b)) if ceil_mode \
            else (lambda a, b: a // b)
        dims = [rnd(sp + 2 * pp - kk, st) + 1 if sp > 0 else -1
                for sp, kk, st, pp in zip((d, h, w_), k, s, p)]
        out_shape = (n, c) + tuple(dims)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=out_shape)
    helper.append_op(
        "pool3d", {"X": input}, {"Out": out},
        {"pooling_type": pool_type, "ksize": k, "strides": s,
         "paddings": p, "global_pooling": global_pooling,
         "ceil_mode": ceil_mode, "exclusive": exclusive})
    return out


def adaptive_pool3d(input, pool_size, pool_type="max", name=None):
    """Adaptive 3-D pooling to a fixed output size (equal bins)."""
    helper = LayerHelper("adaptive_pool3d", name=name)
    k = list(pool_size) if isinstance(pool_size, (list, tuple)) \
        else [pool_size] * 3
    n, c = input.shape[0], input.shape[1]
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(input), shape=(n, c) + tuple(k))
    helper.append_op("pool3d", {"X": input}, {"Out": out},
                     {"pooling_type": pool_type, "ksize": k,
                      "strides": k, "paddings": [0, 0, 0],
                      "adaptive": True})
    return out


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """3-D transposed convolution over NCDHW (ref ``nn.py``
    conv3d_transpose / ``conv_transpose_op.cc``)."""
    def _t3(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    helper = LayerHelper("conv3d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    s, p, dl = _t3(stride), _t3(padding), _t3(dilation)
    fs = _t3(filter_size)
    n, cin, d, h, w_ = input.shape
    dtype = _dtype(input)
    w = helper.create_parameter(
        helper.param_attr,
        shape=[cin, num_filters // groups] + fs, dtype=dtype)
    dims = [(sp - 1) * st - 2 * pp + dd * (kk - 1) + 1 if sp > 0 else -1
            for sp, st, pp, dd, kk in zip((d, h, w_), s, p, dl, fs)]
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(n, num_filters) + tuple(dims))
    helper.append_op("conv3d_transpose",
                     {"Input": input, "Filter": w}, {"Output": out},
                     {"strides": s, "paddings": p, "dilations": dl,
                      "groups": groups})
    pre_act = out
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters],
                                    dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(
            dtype=dtype, shape=out.shape)
        helper.append_op("elementwise_add", {"X": out, "Y": b},
                         {"Out": pre_act}, {"axis": 1})
    return helper.append_activation(pre_act)


def lstm(input, init_h=None, init_c=None, max_len=None, hidden_size=None,
         num_layers=1, dropout_prob=0.0, is_bidirec=False, lengths=None,
         is_test=False, name=None, default_initializer=None, seed=-1):
    """Multi-layer (optionally bidirectional) LSTM on ``[B, T, D]`` input
    (ref ``nn.py`` lstm / ``cudnn_lstm_op``). The per-layer input projection
    is an fc (MXU matmul batched over [B*T]); recurrence is lax.scan.
    ``init_h``/``init_c`` `[B, H]` seed layer 0's forward direction (zeros
    when omitted; deeper layers / the reverse direction always start at
    zero). ``max_len`` is unused (static shapes carry the length).
    Returns ``(out [B,T,H*dirs], last_h, last_c)`` where last_* are
    ``[B, H*dirs]`` of the final layer."""
    from . import tensor as tensor_layers
    from .sequence_lod import sequence_first_step, sequence_last_step

    x = input
    hidden = None
    cell = None
    h_r = c_r = None
    for layer in range(num_layers):
        lname = None if name is None else "%s_l%d" % (name, layer)
        proj = fc(x, size=4 * hidden_size, num_flatten_dims=2,
                  name=None if lname is None else lname + "_proj")
        hidden, cell = dynamic_lstm(proj, 4 * hidden_size, lengths=lengths,
                                    h_0=init_h if layer == 0 else None,
                                    c_0=init_c if layer == 0 else None,
                                    name=lname)
        if is_bidirec:
            proj_r = fc(x, size=4 * hidden_size, num_flatten_dims=2,
                        name=None if lname is None else lname + "_proj_r")
            h_r, c_r = dynamic_lstm(proj_r, 4 * hidden_size, lengths=lengths,
                                    is_reverse=True, name=lname)
            hidden = tensor_layers.concat([hidden, h_r], axis=-1)
        if dropout_prob and layer < num_layers - 1:
            hidden = dropout(hidden, dropout_prob, is_test=is_test)
        x = hidden
    # final states per direction: forward direction ends at t=len-1; the
    # reverse scan's final state sits at original position 0
    fwd_h = sequence_last_step(
        hidden if not is_bidirec else
        tensor_layers.slice(hidden, axes=[2], starts=[0],
                            ends=[hidden_size]), lengths=lengths)
    fwd_c = sequence_last_step(cell, lengths=lengths)
    if is_bidirec:
        last_h = tensor_layers.concat(
            [fwd_h, sequence_first_step(h_r)], axis=-1)
        last_c = tensor_layers.concat(
            [fwd_c, sequence_first_step(c_r)], axis=-1)
    else:
        last_h, last_c = fwd_h, fwd_c
    return hidden, last_h, last_c


def dynamic_gru(input, size, lengths=None, param_attr=None, bias_attr=None,
                is_reverse=False, gate_activation="sigmoid",
                candidate_activation="tanh", origin_mode=False, h_0=None,
                name=None):
    """GRU over a pre-projected ``[B, T, 3H]`` sequence (ref ``nn.py``
    dynamic_gru / ``gru_op.cc``); ``size`` is the hidden width H."""
    helper = LayerHelper("dynamic_gru", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dtype = _dtype(input)
    w = helper.create_parameter(helper.param_attr, shape=[size, 3 * size],
                                dtype=dtype)
    b = helper.create_parameter(helper.bias_attr, shape=[3 * size],
                                dtype=dtype, is_bias=True)
    b_sz, t_sz = input.shape[0], input.shape[1]
    hidden = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(b_sz, t_sz, size))
    inputs = {"Input": input, "Weight": w, "Bias": b}
    if lengths is not None:
        inputs["Lengths"] = lengths
    helper.append_op("gru_seq", inputs, {"Hidden": hidden},
                     {"is_reverse": is_reverse, "origin_mode": origin_mode})
    return hidden


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid", origin_mode=False,
             name=None):
    """Single GRU step (ref ``gru_unit_op``): ``input`` [B, 3H] pre-projected,
    ``hidden`` [B, H] previous state. Returns the new hidden [B, H] (the
    reference also returns gates/reset_hidden_prev; composed models only use
    the hidden)."""
    helper = LayerHelper("gru_unit", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    hidden_size = size // 3
    dtype = _dtype(input)
    w = helper.create_parameter(helper.param_attr,
                                shape=[hidden_size, 3 * hidden_size],
                                dtype=dtype)
    b = helper.create_parameter(helper.bias_attr, shape=[3 * hidden_size],
                                dtype=dtype, is_bias=True)
    new_hidden = helper.create_variable_for_type_inference(
        dtype=dtype, shape=hidden.shape)
    helper.append_op("gru_unit",
                     {"Input": input, "HiddenPrev": hidden, "Weight": w,
                      "Bias": b},
                     {"Hidden": new_hidden}, {"origin_mode": origin_mode})
    return new_hidden


def rms_norm(input, epsilon=1e-6, zero_centered=False, norm_dim=None,
             gate=None, gate_first=False, shared_weight=True,
             param_attr=None, name=None, param_dtype=None):
    """RMS norm over the last axis, or over each trailing group of
    ``norm_dim`` elements of it (one head of a packed [.., H*D] axis, with
    one weight of ``norm_dim`` shared by the heads, or, ``shared_weight``
    False, a weight as long as the axis): ``x * rsqrt(mean(x^2) + epsilon)
    * w`` in float32; ``zero_centered`` applies ``1 + w`` (the weight then
    starts at 0). ``gate``: a tensor shaped like ``input``; the result is
    multiplied by ``silu(gate)`` (the gated norm), or, ``gate_first``, the
    input is, before the norm. ``param_dtype``: the type the weight is kept
    in where that is not the input's (see ``fc``)."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    dim = int(norm_dim or input.shape[-1])
    w = helper.create_parameter(
        helper.param_attr,
        shape=[dim if shared_weight else int(input.shape[-1])],
        dtype=param_dtype or _dtype(input),
        default_initializer=ConstantInitializer(
            0.0 if zero_centered else 1.0))
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    inputs = {"X": input, "Scale": w}
    attrs = {"epsilon": float(epsilon), "zero_centered": bool(zero_centered),
             "norm_dim": dim}
    if gate is not None:
        inputs["Gate"] = gate
        if gate_first:
            attrs["gate_first"] = True
    helper.append_op("rms_norm", inputs, {"Y": out}, attrs)
    return out


def rotary(x, num_heads, rotary_dim, theta=10000.0, pos=None,
           interleaved=False, offset=0, name=None):
    """Rotary positions on ``rotary_dim`` dims of each head of a packed
    [B, T, H*D] tensor, from dim ``offset`` of the head on; the rest of each
    head passes through. Pairing rotate-half (j, j + rotary_dim/2), or,
    ``interleaved``, neighbours (2j, 2j + 1). Position: the index along T,
    or, ``pos`` given, what it feeds: [B, T] int for a chunk whose lanes sit
    anywhere in their rows' caches, [B] int with x [B, H*D] for a decode
    step (one token a row)."""
    helper = LayerHelper("rotary", name=name)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    inputs = {"X": x}
    if pos is not None:
        inputs["Pos"] = pos
    attrs = {"num_heads": int(num_heads), "rotary_dim": int(rotary_dim),
             "theta": float(theta)}
    if interleaved:
        attrs["interleaved"] = True
    if offset:
        attrs["offset"] = int(offset)
    helper.append_op("rotary", inputs, {"Out": out}, attrs)
    return out


def _named(name, tag):
    return None if name is None else name + "." + tag


def _projection(helper, x, dout, name, sharding=None):
    """x [.., D] times a [D, dout] weight called ``name``, no bias."""
    w = helper.create_parameter(
        ParamAttr(name=name, initializer=XavierInitializer(),
                  sharding=sharding),
        shape=[x.shape[-1], dout], dtype=_dtype(x))
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=tuple(x.shape[:-1]) + (dout,))
    helper.append_op("matmul", {"X": x, "Y": w}, {"Out": out}, {})
    return out


def causal_self_attention(x, num_heads, num_kv_heads, head_dim,
                          rotary_dim=0, rope_theta=10000.0, epsilon=1e-6,
                          qk_norm=False, output_gate=False, name=None):
    """Causal self-attention with grouped-query heads: ``num_kv_heads``
    key/value heads serve ``num_heads`` query heads (head ``h`` reads ``h //
    (H / Hkv)``). ``qk_norm``: q and k take a zero-centred RMS norm over the
    head dimension; ``rotary_dim`` > 0: rotary positions on the first
    ``rotary_dim`` dims of each head (0: no position encoding);
    ``output_gate``: ``q_proj`` gives query and gate, interleaved a head
    ([.., H, 2*D], the halves of the last axis), and the attention output is
    multiplied by ``sigmoid(gate)`` before ``o_proj``. The head counts are
    those this chip holds: given a share of a layer's heads, ``o_proj``
    gives that share's addend of the layer's output. x: [B, T, D_model].
    Parameters: ``<name>.{q_proj,k_proj,v_proj,o_proj}`` and, with
    ``qk_norm``, ``<name>.{q_norm.w,k_norm.w}``."""
    from . import ops as op_layers
    from . import tensor

    helper = LayerHelper("causal_self_attention", name=name)
    b, t = x.shape[0], x.shape[1]
    q = _projection(helper, x, num_heads * head_dim * (1 + bool(output_gate)),
                    _named(name, "q_proj"), (None, "mp"))
    if output_gate:
        qg = tensor.reshape(q, [-1, t, num_heads, 2 * head_dim])
        q, g = split(qg, 2, dim=-1)
        q = tensor.reshape(q, [-1, t, num_heads * head_dim])
        g = tensor.reshape(g, [-1, t, num_heads * head_dim])
    k = _projection(helper, x, num_kv_heads * head_dim,
                    _named(name, "k_proj"), (None, "mp"))
    v = _projection(helper, x, num_kv_heads * head_dim,
                    _named(name, "v_proj"), (None, "mp"))
    if qk_norm:
        q = rms_norm(q, epsilon, zero_centered=True, norm_dim=head_dim,
                     param_attr=ParamAttr(name=_named(name, "q_norm.w")))
        k = rms_norm(k, epsilon, zero_centered=True, norm_dim=head_dim,
                     param_attr=ParamAttr(name=_named(name, "k_norm.w")))
    if rotary_dim:
        q = rotary(q, num_heads, rotary_dim, rope_theta)
        k = rotary(k, num_kv_heads, rotary_dim, rope_theta)
    ctx = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(b, t, num_heads * head_dim))
    helper.append_op("flash_attention", {"Q": q, "K": k, "V": v},
                     {"Out": ctx},
                     {"num_heads": int(num_heads),
                      "num_kv_heads": int(num_kv_heads),
                      "dropout_rate": 0.0, "causal": True})
    if output_gate:
        ctx = elementwise_mul(ctx, op_layers.sigmoid(g))
    wo = helper.create_parameter(
        ParamAttr(name=_named(name, "o_proj"),
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[num_heads * head_dim, x.shape[-1]], dtype=_dtype(x))
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("matmul", {"X": ctx, "Y": wo}, {"Out": out}, {})
    return out


def causal_conv1d(x, kernel=4, act="silu", param_attr=None, bias_attr=None,
                  name=None):
    """Causal depthwise convolution along T of x [B, T, C], filter
    [C, kernel], a bias [C] where ``bias_attr`` is given, then ``act``
    ('silu' or None)."""
    helper = LayerHelper("causal_conv1d", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    c = int(x.shape[-1])
    lim = (3.0 / kernel) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, shape=[c, int(kernel)], dtype=_dtype(x),
        default_initializer=UniformInitializer(-lim, lim))
    inputs = {"X": x, "Filter": w}
    if bias_attr:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr, shape=[c], dtype=_dtype(x), is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("causal_conv1d", inputs, {"Out": out},
                     {"act": act or ""})
    return out


def mamba2_mixer(x, num_heads, head_dim, num_groups, state_size,
                 conv_kernel=4, epsilon=1e-5, chunk=128, name=None):
    """Mamba-2 mixer (x: [B, T, D_model]) over the heads this chip holds:
    ``in_proj`` gives ``[z | x B C | dt]`` (H*P, H*P + 2*G*N and H
    columns); ``[x B C]`` pass a causal depthwise convolution with a bias
    and SiLU; the state-space scan (op ``mamba2_ssd``, ``ops/mamba2.py``)
    over a [P, N] state a head with ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)`` and the skip ``D x``; the output times ``silu(z)`` takes
    an RMS norm in groups of ``H*P / G`` channels (the gate BEFORE the
    norm), then ``out_proj``. ``num_heads`` of ``head_dim`` in
    ``num_groups`` groups: given a share of a layer's heads with their
    groups, ``out_proj`` gives that share's addend of the layer's output.
    Parameters: ``<name>.{in_proj,conv,conv_bias,A_log,dt_bias,D,norm.w,
    out_proj}``."""
    helper = LayerHelper("mamba2_mixer", name=name)
    b, t = x.shape[0], x.shape[1]
    inner, bc = num_heads * head_dim, num_groups * state_size
    proj = _projection(helper, x, 2 * inner + 2 * bc + num_heads,
                       _named(name, "in_proj"), (None, "mp"))
    z, xbc, dt = split(proj, [inner, inner + 2 * bc, num_heads], dim=-1)
    xbc = causal_conv1d(
        xbc, conv_kernel, "silu",
        param_attr=ParamAttr(name=_named(name, "conv")),
        bias_attr=ParamAttr(name=_named(name, "conv_bias")))
    xs, bm, cm = split(xbc, [inner, bc, bc], dim=-1)

    def per_head(tag, initializer):
        return helper.create_parameter(
            ParamAttr(name=_named(name, tag)), shape=[num_heads],
            dtype="float32", default_initializer=initializer)

    # decays start slow, as state-space layers are started: A in (1, 16],
    # softplus(dt_bias) in about (0.001, 0.1)
    core = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(b, t, inner))
    helper.append_op(
        "mamba2_ssd",
        {"X": xs, "Bm": bm, "Cm": cm, "Dt": dt,
         "ALog": per_head("A_log", UniformInitializer(0.0, 2.77)),
         "DtBias": per_head("dt_bias", UniformInitializer(-6.9, -2.25)),
         "D": per_head("D", ConstantInitializer(1.0))},
        {"Out": core},
        {"num_heads": int(num_heads), "num_groups": int(num_groups),
         "chunk": int(chunk)})
    core = rms_norm(core, epsilon, norm_dim=inner // num_groups, gate=z,
                    gate_first=True, shared_weight=False,
                    param_attr=ParamAttr(name=_named(name, "norm.w")))
    wo = helper.create_parameter(
        ParamAttr(name=_named(name, "out_proj"),
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[inner, x.shape[-1]], dtype=_dtype(x))
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("matmul", {"X": core, "Y": wo}, {"Out": out}, {})
    return out


def gated_delta_net(x, num_k_heads, num_v_heads, head_k_dim, head_v_dim,
                    conv_kernel=4, epsilon=1e-6, chunk=64, name=None):
    """Gated DeltaNet linear attention (x: [B, T, D_model]): ``in_proj_qkvz``
    gives, a key head, q and k of ``head_k_dim`` and the v and z of its
    ``num_v_heads / num_k_heads`` value heads (the published per-group
    interleaving); ``in_proj_ba`` the write strengths b and decays a of
    those value heads. [q|k|v] pass a causal depthwise convolution with
    SiLU, then the gated delta rule (op ``gated_delta_rule``,
    ``ops/gated_delta.py``); the output takes an RMS norm over the head
    dimension gated by ``silu(z)``, then ``out_proj``. Parameters:
    ``<name>.{in_proj_qkvz,in_proj_ba,conv,A_log,dt_bias,norm.w,out_proj}``."""
    from . import tensor

    helper = LayerHelper("gated_delta_net", name=name)
    b, t = x.shape[0], x.shape[1]
    rep = num_v_heads // num_k_heads
    kd, vd = num_k_heads * head_k_dim, num_v_heads * head_v_dim
    group = 2 * head_k_dim + 2 * rep * head_v_dim
    qkvz = _projection(helper, x, num_k_heads * group,
                       _named(name, "in_proj_qkvz"), (None, "mp"))
    qkvz = tensor.reshape(qkvz, [-1, t, num_k_heads, group])
    q, k, v, z = split(qkvz, [head_k_dim, head_k_dim, rep * head_v_dim,
                              rep * head_v_dim], dim=-1)
    q = tensor.reshape(q, [-1, t, kd])
    k = tensor.reshape(k, [-1, t, kd])
    v = tensor.reshape(v, [-1, t, vd])
    z = tensor.reshape(z, [-1, t, vd])
    ba = _projection(helper, x, 2 * num_v_heads, _named(name, "in_proj_ba"))
    ba = tensor.reshape(ba, [-1, t, num_k_heads, 2 * rep])
    bb, aa = split(ba, 2, dim=-1)
    bb = tensor.reshape(bb, [-1, t, num_v_heads])
    aa = tensor.reshape(aa, [-1, t, num_v_heads])
    mixed = causal_conv1d(
        tensor.concat([q, k, v], axis=-1), conv_kernel, "silu",
        param_attr=ParamAttr(name=_named(name, "conv")))
    q, k, v = split(mixed, [kd, kd, vd], dim=-1)
    # decays start slow (a state that remembers hundreds of tokens), as
    # state-space layers are started: A in (0.05, 1], softplus(dt_bias) in
    # about (0.02, 0.3)
    a_log = helper.create_parameter(
        ParamAttr(name=_named(name, "A_log")), shape=[num_v_heads],
        dtype="float32", default_initializer=UniformInitializer(-3.0, 0.0))
    dt_bias = helper.create_parameter(
        ParamAttr(name=_named(name, "dt_bias")), shape=[num_v_heads],
        dtype="float32", default_initializer=UniformInitializer(-4.0, -1.0))
    core = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(b, t, vd))
    helper.append_op(
        "gated_delta_rule",
        {"Q": q, "K": k, "V": v, "A": aa, "B": bb, "ALog": a_log,
         "DtBias": dt_bias}, {"Out": core},
        {"num_k_heads": int(num_k_heads), "num_v_heads": int(num_v_heads),
         "chunk": int(chunk)})
    core = rms_norm(core, epsilon, norm_dim=head_v_dim, gate=z,
                    param_attr=ParamAttr(name=_named(name, "norm.w")))
    wo = helper.create_parameter(
        ParamAttr(name=_named(name, "out_proj"),
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[vd, x.shape[-1]], dtype=_dtype(x))
    out = helper.create_variable_for_type_inference(dtype=_dtype(x),
                                                    shape=x.shape)
    helper.append_op("matmul", {"X": core, "Y": wo}, {"Out": out}, {})
    return out


def routed_experts(x, num_experts, top_k, moe_intermediate_size,
                   shared_intermediate_size=0, experts_held=None,
                   norm_topk_prob=True, score="softmax",
                   selection_bias=False, scale=1.0, form="swiglu",
                   shared_gate=True, latent_size=0, name=None):
    """Mixture-of-experts block with routed and shared experts on a chip that
    holds a share of the routed ones (``parallel/moe.py``): a router over
    all ``num_experts`` (``score``: a ``softmax`` over them or a ``sigmoid``
    each; ``selection_bias``: a persistable [num_experts] buffer, zeros, no
    gradient, added to the scores for the choice alone), the ``top_k``
    largest weights (divided by their sum with ``norm_topk_prob``, times
    ``scale``), experts of width ``moe_intermediate_size`` and ``form``
    ``swiglu`` (three matrices) or ``relu2`` (``down(relu(up x)^2)``).
    ``latent_size`` > 0: the routed experts read and write a latent of that
    width, ``x @ latent_down`` in and ``@ latent_up`` out (plain matmuls
    round the op), while the router and the shared expert read x.
    ``experts_held``: ``(first, count)`` or a ``range`` of the expert ids
    this chip holds (default: all); the routed sum runs over the picks that
    fall on them, and no token is dropped whatever the routing.
    ``shared_intermediate_size`` > 0 adds one shared expert of the same
    form, that many units wide (those this chip holds), behind a sigmoid
    gate if ``shared_gate``. Returns ``(out, load)``; ``load`` [count] int32
    is a persistable counter of the tokens each held expert took in the
    last step, and ``<name>.rows`` [2] int32 one of the rows that held a
    token and the rows the experts' products ran over. Parameters:
    ``<name>.router`` (``.router_bias``),
    ``<name>.experts.{gate,up,down}`` ([count, out, in], the published
    per-expert layout; no ``gate`` for ``relu2``),
    ``<name>.shared.{gate_proj,up_proj,down_proj}``, ``<name>.shared_gate``,
    ``<name>.{latent_down,latent_up}``."""
    from . import tensor

    helper = LayerHelper("routed_experts", name=name)
    d, f = int(x.shape[-1]), int(moe_intermediate_size)
    dtype = _dtype(x)
    if experts_held is None:
        first, held = 0, int(num_experts)
    elif isinstance(experts_held, range):
        first, held = experts_held.start, len(experts_held)
    else:
        first, held = (int(n) for n in experts_held)
    if first < 0 or held < 1 or first + held > num_experts:
        raise ValueError("experts_held [%d, %d) is not within the %d experts"
                         % (first, first + held, num_experts))
    if form not in ("swiglu", "relu2"):
        raise ValueError("unknown expert form %r" % (form,))

    def p(tag, shape, fan_in, fan_out):
        lim = (6.0 / (fan_in + fan_out)) ** 0.5
        return helper.create_parameter(
            ParamAttr(name=_named(name, tag),
                      initializer=UniformInitializer(-lim, lim)),
            shape=shape, dtype=dtype)

    de = int(latent_size) or d       # the width the routed experts work in
    inputs = {"X": x, "Router": p("router", [d, num_experts], d,
                                  num_experts)}
    if form == "swiglu":
        inputs["ExpertGate"] = p("experts.gate", [held, f, de], de, f)
    inputs["ExpertUp"] = p("experts.up", [held, f, de], de, f)
    inputs["ExpertDown"] = p("experts.down", [held, de, f], f, de)
    if selection_bias:
        inputs["RouterBias"] = helper.create_parameter(
            ParamAttr(name=_named(name, "router_bias"), trainable=False,
                      initializer=ConstantInitializer(0.0)),
            shape=[num_experts], dtype="float32")
    if shared_intermediate_size:
        fs = int(shared_intermediate_size)
        if form == "swiglu":
            inputs["SharedGate"] = p("shared.gate_proj", [d, fs], d, fs)
        inputs["SharedUp"] = p("shared.up_proj", [d, fs], d, fs)
        inputs["SharedDown"] = p("shared.down_proj", [fs, d], fs, d)
        if shared_gate:
            inputs["SharedExpertGate"] = p("shared_gate", [d, 1], d, 1)
    attrs = {"top_k": int(top_k), "first_expert": first,
             "norm_topk_prob": bool(norm_topk_prob), "score": score,
             "scale": float(scale), "form": form}
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(x.shape[:-1]) + (de,))
    outputs = {"Out": out}
    if latent_size:
        inputs["ExpertX"] = _projection(helper, x, de,
                                        _named(name, "latent_down"))
        if shared_intermediate_size:
            outputs["SharedOut"] = helper.create_variable_for_type_inference(
                dtype=dtype, shape=x.shape)
    load = tensor.create_global_var(
        shape=[held], value=0, dtype="int32", persistable=True,
        name=_named(name, "load"))
    load.stop_gradient = True
    outputs["Load"] = load
    rows = tensor.create_global_var(
        shape=[2], value=0, dtype="int32", persistable=True,
        name=_named(name, "rows"))
    rows.stop_gradient = True
    outputs["Rows"] = rows
    helper.append_op("routed_experts", inputs, outputs, attrs)
    if latent_size:
        out = _projection(helper, out, d, _named(name, "latent_up"))
        if shared_intermediate_size:
            out = elementwise_add(out, outputs["SharedOut"])
    return out, load


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    """Row convolution / lookahead conv (ref ``row_conv_op``): realized as a
    sequence_conv with context [0, future_context_size]."""
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act, name=name)
    d = input.shape[-1]
    ctx = future_context_size + 1
    w = helper.create_parameter(helper.param_attr, shape=[ctx * d, d],
                                dtype=_dtype(input))
    out = helper.create_variable_for_type_inference(dtype=_dtype(input),
                                                    shape=input.shape)
    helper.append_op("sequence_conv", {"X": input, "Filter": w},
                     {"Out": out},
                     {"contextLength": ctx, "contextStart": 0})
    return helper.append_activation(out)


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Global step counter var, incremented once per executor run (ref
    ``layers/nn.py`` autoincreased_step_counter). Backing state is a
    persistable scalar initialized by the startup program."""
    helper = LayerHelper("global_step_counter")
    name = counter_name or "@STEP_COUNTER@"
    gb = helper.main_program.global_block()
    if name in gb.vars:
        # idempotent: one increment per program per counter
        for op in gb.ops:
            if op.type == "increment" and op.output("Out").name == name:
                return gb.vars[name]
    counter = gb.create_var(
        name=name, shape=(1,), dtype="int64", persistable=True)
    startup_block = helper.startup_program.global_block()
    if not any(op.output("Out") is not None and op.output("Out").name == name
               for op in startup_block.ops):
        sp_var = startup_block.create_var(name=name, shape=(1,),
                                          dtype="int64", persistable=True)
        startup_block.append_op(
            "fill_constant", outputs={"Out": sp_var},
            attrs={"shape": (1,), "dtype": "int64", "value": begin - step})
    helper.append_op("increment", {"X": counter}, {"Out": counter},
                     {"step": float(step)})
    return counter


# ---------------------------------------------------------------------------
# attention (ref nn.py scaled_dot_product_attention; multi-head used by the
# transformer model). Uses the Pallas flash-attention kernel on TPU.
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(queries, keys, values, num_heads=1,
                                 dropout_rate=0.0):
    helper = LayerHelper("scaled_dot_product_attention")
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(queries), shape=queries.shape)
    helper.append_op(
        "flash_attention", {"Q": queries, "K": keys, "V": values},
        {"Out": out},
        {"num_heads": num_heads, "dropout_rate": dropout_rate,
         "causal": False})
    return out


def multi_head_attention(queries, keys, values, attn_bias=None, d_key=None,
                         d_value=None, d_model=None, n_head=1,
                         dropout_rate=0.0, causal=False, param_attr=None,
                         name=None):
    """Fused multi-head attention: QKV projections (MXU matmuls) + flash
    attention (Pallas kernel on TPU) + output projection. The reference
    composes this from primitive layers in its transformer test
    (``tests/unittests/dist_transformer.py``); here it is a first-class layer
    so the hot path is one fused kernel."""
    helper = LayerHelper("multi_head_attention", param_attr=param_attr,
                         name=name)
    d_model = d_model or queries.shape[-1]
    d_key = d_key or d_model // n_head
    d_value = d_value or d_model // n_head
    dtype = _dtype(queries)

    def proj(x, dout, tag):
        w = helper.create_parameter(
            ParamAttr(name=None if name is None else name + "." + tag,
                      initializer=XavierInitializer(),
                      sharding=(None, "mp")),
            shape=[x.shape[-1], dout], dtype=dtype)
        out = helper.create_variable_for_type_inference(
            dtype=dtype, shape=tuple(x.shape[:-1]) + (dout,))
        helper.append_op("matmul", {"X": x, "Y": w}, {"Out": out}, {})
        return out

    q = proj(queries, d_key * n_head, "q")
    k = proj(keys, d_key * n_head, "k")
    v = proj(values, d_value * n_head, "v")
    ctx = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(queries.shape[:-1]) + (d_value * n_head,))
    inputs = {"Q": q, "K": k, "V": v}
    if attn_bias is not None:
        inputs["Bias"] = attn_bias
    helper.append_op("flash_attention", inputs, {"Out": ctx},
                     {"num_heads": n_head, "dropout_rate": dropout_rate,
                      "causal": causal})
    # output projection sharded mp on input dim (row-parallel): its matmul
    # reduces over the sharded axis -> GSPMD inserts the psum
    wo = helper.create_parameter(
        ParamAttr(name=None if name is None else name + ".out",
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[d_value * n_head, d_model], dtype=dtype)
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(queries.shape[:-1]) + (d_model,))
    helper.append_op("matmul", {"X": ctx, "Y": wo}, {"Out": out}, {})
    return out


def kv_cache_write(cache, x, pos, ring=False, name=None):
    """Per-row cache update: ``cache[b, pos[b]] = x[b]`` (see
    ``core/opimpl/attention_ops.py``). ``cache``: [B, C, ...], ``x``:
    [B, ...], ``pos``: [B] int. The tail is the cache's own: a key or value
    row of H*D, one latent row ``[c | k_pe]`` for all heads, an index key.
    ``ring``: the cache is a ring of C positions, ``cache[b, pos[b] % C] =
    x[b]``; what reads it decides what the slots mean: a sliding window
    (:func:`cached_attention`) or a window that restarts at every multiple
    of C and masks the stale slots above ``pos % C`` (:func:`eva_attention`).
    A cache written at a stride and derived from another is
    :func:`eva_summary`'s. Returns the updated cache tensor."""
    helper = LayerHelper("kv_cache_write", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(cache), shape=cache.shape)
    helper.append_op("kv_cache_write",
                     {"Cache": cache, "X": x, "Pos": pos}, {"Out": out},
                     {"ring": True} if ring else {})
    return out


def cached_attention(q, cache_k, cache_v, pos, num_heads, num_kv_heads=None,
                     window=0, sink_attr=None, ring=False, new_k=None,
                     new_v=None, count=False, name=None):
    """Attention of a step's or a chunk's queries over a slot table's
    key/value caches (ops ``cached_attention`` / ``cached_attention_chunk``,
    ``core/opimpl/attention_ops.py``): ``q`` [B, H*Dk] with ``pos`` [B], or
    [B, K, H*Dk] with ``pos`` [B, K]; ``cache_k`` [B, C, Hkv*Dk] and
    ``cache_v`` [B, C, Hkv*Dv], each of its own width, with the current
    rows written. ``num_kv_heads`` < ``num_heads``: grouped heads, query
    head h reads key/value head ``h // (H / Hkv)``. ``window`` > 0: a query
    reads its own position and the ``window - 1`` before it. ``sink_attr``:
    creates a learned scalar a query head ([H], the query's type) that
    joins the softmax's denominator and takes no value. ``ring``: the
    caches are rings of C positions (position p at slot ``p % C``, written
    by ``kv_cache_write(.., ring=True)``); a step reads the ring with its
    token written, a chunk reads the rings AS THEY WERE BEFORE it and its
    own ``new_k`` / ``new_v`` [B, K, ..] beside them, and writes the rings
    afterwards. ``count``: also return [1] int32, the positions the rows
    read (a step only). Returns [.., H*Dv], or ``(out, count)``. ONE key and
    one value cache, and a ring's window SLIDES (``p - window < s <= p``);
    one softmax over two caches of different kinds, a block-aligned window
    and a cache of chunk summaries, is :func:`eva_attention`."""
    chunk = len(q.shape) == 3
    helper = LayerHelper("cached_attention_chunk" if chunk
                         else "cached_attention", param_attr=sink_attr,
                         name=name)
    heads = int(num_heads)
    kv_heads = int(num_kv_heads or heads)
    inputs = {"Q": q, "CacheK": cache_k, "CacheV": cache_v, "Pos": pos}
    attrs = {"num_heads": heads}
    if kv_heads != heads:
        attrs["num_kv_heads"] = kv_heads
    if window:
        attrs["window"] = int(window)
    if ring:
        attrs["ring"] = True
        if chunk:
            inputs.update(NewK=new_k, NewV=new_v)
    if sink_attr is not None:
        inputs["Sink"] = helper.create_parameter(
            helper.param_attr, shape=[heads], dtype=_dtype(q),
            default_initializer=ConstantInitializer(0.0))
    width = int(cache_v.shape[-1])
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(q), shape=tuple(q.shape[:-1]) + (
            width if width < 0 else width // kv_heads * heads,))
    outputs = {"Out": out}
    if count:
        if chunk:
            raise ValueError("cached_attention counts the positions a step "
                             "reads, not a chunk's")
        outputs["Count"] = helper.create_variable_for_type_inference(
            dtype="int32", shape=(1,))
        outputs["Count"].stop_gradient = True
    helper.append_op(helper.layer_type, inputs, outputs, attrs)
    return (out, outputs["Count"]) if count else out


def _eva_inputs(chunk, win_k, win_v, sum_k, sum_v, pos, new_k, new_v):
    inputs = {"WinK": win_k, "WinV": win_v, "SumK": sum_k, "SumV": sum_v,
              "Pos": pos}
    if chunk:
        if new_k is None or new_v is None:
            raise ValueError("a chunk run reads the window caches as they "
                             "were before it: it needs new_k and new_v, "
                             "its own rows")
        inputs.update(NewK=new_k, NewV=new_v)
    return inputs


def _pad_pos(pad_pos):
    if pad_pos is None:
        raise ValueError("a chunk run over a window cache needs pad_pos, "
                         "the position the scheduler gives a pad lane")
    return int(pad_pos)


def eva_summary(win_k, win_v, sum_k, sum_v, pos, num_heads, chunk_size,
                phi_attr=None, mu_attr=None, new_k=None, new_v=None,
                pad_pos=None, name=None):
    """The summariser of EVA attention and its two cache writes (ops
    ``eva_summary`` / ``eva_summary_chunk``, ``ops/eva_attention.py``): a
    cache written at a stride, derived from another. ``win_k``, ``win_v``
    [B, W, H*D]: the window caches (position p at slot ``p % W``, written by
    ``kv_cache_write(.., ring=True)``); ``sum_k``, ``sum_v`` [B, L, H*D]: the
    summary caches, chunk c (positions ``chunk_size * c`` on) at entry c.
    Creates the layer's pooling query ``phi`` and key offset ``mu``, [H*D]
    each. A step (``pos`` [B], the window caches with its token written)
    writes the chunk its position ends, if it ends one; a chunk run (``pos``
    [B, K], the window caches AS THEY WERE BEFORE it, ``new_k`` / ``new_v``
    [B, K, H*D] its own rows, ``pad_pos`` what the scheduler gives a pad
    lane) every chunk whose last position is a live lane. Returns the two
    summary caches."""
    chunk = len(pos.shape) == 2
    helper = LayerHelper("eva_summary_chunk" if chunk else "eva_summary",
                         name=name)
    width = int(win_k.shape[-1])
    inputs = _eva_inputs(chunk, win_k, win_v, sum_k, sum_v, pos, new_k,
                         new_v)
    for slot, attr in (("Phi", phi_attr), ("Mu", mu_attr)):
        inputs[slot] = helper.create_parameter(
            ParamAttr._to_attr(attr), shape=[width], dtype=_dtype(win_k),
            default_initializer=ConstantInitializer(0.0))
    attrs = {"num_heads": int(num_heads), "chunk": int(chunk_size)}
    if chunk:
        attrs["pad_pos"] = _pad_pos(pad_pos)
    outs = {slot + "Out": helper.create_variable_for_type_inference(
        dtype=_dtype(cache), shape=cache.shape)
        for slot, cache in (("SumK", sum_k), ("SumV", sum_v))}
    helper.append_op(helper.layer_type, inputs, outs, attrs)
    return outs["SumKOut"], outs["SumVOut"]


def eva_attention(q, win_k, win_v, sum_k, sum_v, pos, num_heads, window_size,
                  chunk_size, new_k=None, new_v=None, pad_pos=None,
                  name=None):
    """EVA attention, ONE softmax over two caches (ops ``eva_attention`` /
    ``eva_attention_chunk``, ``ops/eva_attention.py``): the query at position
    p reads the window cache's positions of its own block-aligned window,
    ``(p // window_size) * window_size .. p``, and the summary cache's
    entries of every earlier window. A step: ``q`` [B, H*D], ``pos`` [B],
    the window caches with its token written; returns ``(out, count)``,
    ``count`` [3] int32: the window slots and the summary entries the rows
    read and the context positions they hold. A chunk run: ``q`` [B, K,
    H*D], ``pos`` [B, K], the window caches AS THEY WERE BEFORE the run with
    ``new_k`` / ``new_v`` beside them, the summary caches with the run's
    summaries written (:func:`eva_summary` first), ``pad_pos`` what the
    scheduler gives a pad lane, ``K <= window_size``; returns ``out``."""
    chunk = len(q.shape) == 3
    helper = LayerHelper("eva_attention_chunk" if chunk else "eva_attention",
                         name=name)
    inputs = _eva_inputs(chunk, win_k, win_v, sum_k, sum_v, pos, new_k,
                         new_v)
    inputs["Q"] = q
    out = helper.create_variable_for_type_inference(dtype=_dtype(q),
                                                    shape=q.shape)
    outputs = {"Out": out}
    if not chunk:
        outputs["Count"] = helper.create_variable_for_type_inference(
            dtype="int32", shape=(3,))
        outputs["Count"].stop_gradient = True
    attrs = {"num_heads": int(num_heads), "window": int(window_size),
             "chunk": int(chunk_size)}
    if chunk:
        attrs["pad_pos"] = _pad_pos(pad_pos)
    helper.append_op(helper.layer_type, inputs, outputs, attrs)
    return out if chunk else (out, outputs["Count"])


def gated_feed_forward(x, intermediate_size, size, num_flatten_dims=1,
                       name=None):
    """The dense gated feed-forward layer (SwiGLU): ``(swish(x W_gate) * (x
    W_up)) W_down``, no bias; the parameters ``<name>.gate`` and
    ``<name>.up`` [D, intermediate_size] and ``<name>.down``
    [intermediate_size, size]."""
    def linear(y, width, tag):
        site = None if name is None else name + "." + tag
        return fc(y, size=width, num_flatten_dims=num_flatten_dims,
                  param_attr=ParamAttr(name=site), bias_attr=False,
                  name=site)

    hidden = elementwise_mul(swish(linear(x, intermediate_size, "gate")),
                             linear(x, intermediate_size, "up"))
    return linear(hidden, size, "down")


def cached_multi_head_attention(x, cache_k, cache_v, pos, d_model=None,
                                n_head=1, name=None):
    """One-token incremental attention sharing
    :func:`multi_head_attention`'s weights (same ``name`` -> same
    ``name.q/.k/.v/.out`` parameters), for KV-cached decode step programs:
    project the current token ``x`` [B, d_model], write its K/V rows into
    the fixed-capacity caches at each row's own ``pos``, attend over the
    filled prefix, and apply the output projection. Returns
    ``(out [B, d_model], new_cache_k, new_cache_v)`` — the updated caches
    are carried by the decode scheduler between steps."""
    helper = LayerHelper("cached_multi_head_attention", name=name)
    d_model = d_model or x.shape[-1]
    dtype = _dtype(x)

    def proj(inp, tag):
        w = helper.create_parameter(
            ParamAttr(name=None if name is None else name + "." + tag,
                      initializer=XavierInitializer(),
                      sharding=(None, "mp")),
            shape=[inp.shape[-1], d_model], dtype=dtype)
        out = helper.create_variable_for_type_inference(
            dtype=dtype, shape=tuple(inp.shape[:-1]) + (d_model,))
        helper.append_op("matmul", {"X": inp, "Y": w}, {"Out": out}, {})
        return out

    q = proj(x, "q")
    k = proj(x, "k")
    v = proj(x, "v")
    new_k = kv_cache_write(cache_k, k, pos, name=helper.name + "_kw")
    new_v = kv_cache_write(cache_v, v, pos, name=helper.name + "_vw")
    ctx = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(x.shape[:-1]) + (d_model,))
    helper.append_op("cached_attention",
                     {"Q": q, "CacheK": new_k, "CacheV": new_v, "Pos": pos},
                     {"Out": ctx}, {"num_heads": n_head})
    wo = helper.create_parameter(
        ParamAttr(name=None if name is None else name + ".out",
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[d_model, d_model], dtype=dtype)
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(x.shape[:-1]) + (d_model,))
    helper.append_op("matmul", {"X": ctx, "Y": wo}, {"Out": out}, {})
    return out, new_k, new_v


def kv_cache_write_chunk(cache, x, pos, ring=False, pad_pos=None,
                         few=False, name=None):
    """K-row cache update: ``cache[b, pos[b, j]] = x[b, j]`` (see
    ``core/opimpl/attention_ops.py``). ``cache``: [B, C, ...] of any tail
    (as :func:`kv_cache_write`), ``x``: [B, K, ...], ``pos``: [B, K] int.
    Out-of-range positions drop, so a padded chunk lane writes nothing.
    ``ring``: the cache is a ring of C positions; a lane whose position is
    ``pad_pos`` or more is a pad lane (taken modulo C it would land on a
    live slot, so the program is told what the scheduler pads with), and of
    a row's live lanes only the last C land, at ``pos % C``. ``few``: K is
    a step's one or two lanes, and each is written by a dynamic update of
    its one row, which writes the cache as it lies on the device whatever
    its layout (not with ``ring``). A chunk program whose window restarts
    (:func:`eva_attention`) writes its window caches with ``ring`` too, after
    its lanes have read them as they were. Returns the updated cache."""
    helper = LayerHelper("kv_cache_write_chunk", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(cache), shape=cache.shape)
    attrs = {}
    if few:
        if ring:
            raise ValueError("a ring's chunk write is one scatter")
        attrs = {"few": True}
    if ring:
        if pad_pos is None:
            raise ValueError("a ring's chunk write needs pad_pos, the "
                             "position the scheduler gives a pad lane")
        attrs = {"ring": True, "pad_pos": int(pad_pos)}
    helper.append_op("kv_cache_write_chunk",
                     {"Cache": cache, "X": x, "Pos": pos}, {"Out": out},
                     attrs)
    return out


def cached_multi_head_attention_chunk(x, cache_k, cache_v, pos,
                                      d_model=None, n_head=1, name=None):
    """K-token incremental attention sharing
    :func:`multi_head_attention`'s weights (same ``name`` -> same
    ``name.q/.k/.v/.out`` parameters) — the chunked-prefill /
    speculative-verify sibling of :func:`cached_multi_head_attention`:
    project a K-token chunk ``x`` [B, K, d_model], write its K/V rows
    into the fixed-capacity caches at each row's own ``pos`` [B, K],
    attend each query over the filled prefix plus the chunk's earlier
    tokens (per-query causal mask ``c <= pos[b, j]``), and apply the
    output projection. Returns ``(out [B, K, d_model], new_cache_k,
    new_cache_v)``."""
    helper = LayerHelper("cached_multi_head_attention_chunk", name=name)
    d_model = d_model or x.shape[-1]
    dtype = _dtype(x)

    def proj(inp, tag):
        w = helper.create_parameter(
            ParamAttr(name=None if name is None else name + "." + tag,
                      initializer=XavierInitializer(),
                      sharding=(None, "mp")),
            shape=[inp.shape[-1], d_model], dtype=dtype)
        out = helper.create_variable_for_type_inference(
            dtype=dtype, shape=tuple(inp.shape[:-1]) + (d_model,))
        helper.append_op("matmul", {"X": inp, "Y": w}, {"Out": out}, {})
        return out

    q = proj(x, "q")
    k = proj(x, "k")
    v = proj(x, "v")
    new_k = kv_cache_write_chunk(cache_k, k, pos, name=helper.name + "_kw")
    new_v = kv_cache_write_chunk(cache_v, v, pos, name=helper.name + "_vw")
    ctx = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(x.shape[:-1]) + (d_model,))
    helper.append_op("cached_attention_chunk",
                     {"Q": q, "CacheK": new_k, "CacheV": new_v, "Pos": pos},
                     {"Out": ctx}, {"num_heads": n_head})
    wo = helper.create_parameter(
        ParamAttr(name=None if name is None else name + ".out",
                  initializer=XavierInitializer(), sharding=("mp", None)),
        shape=[d_model, d_model], dtype=dtype)
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=tuple(x.shape[:-1]) + (d_model,))
    helper.append_op("matmul", {"X": ctx, "Y": wo}, {"Out": out}, {})
    return out, new_k, new_v


def sparse_index(q, w, cache_k, pos, num_heads, top_k, name=None):
    """The learned indexer of sparse attention over a cache (ops
    ``sparse_index`` / ``sparse_index_chunk``, ``ops/sparse_latent.py``):
    ``q`` index queries of ``num_heads`` heads, ``w`` a weight a head,
    ``cache_k`` [B, C, D] the cached index keys with the current ones
    written, ``pos`` the positions fed. Scores ``sum_j w_j relu(q_j . k)``
    in float32 over the positions up to ``pos`` (the products in ``q``'s
    type: exact from float32 queries and keys, one MXU pass from bfloat16
    queries whatever the keys are cached in), then the exact ``top_k``
    largest. A step (q [B, H*D], pos [B]) names the set by positions:
    [B, top_k] int32, ``C`` where fewer are cached; a chunk (q [B, K, H*D],
    pos [B, K]) as a membership mask [B, K, C] bool. Returns ``(selected,
    count)``; ``count`` [2] int32: positions selected and positions cached,
    summed over the live rows or lanes. Several attention layers may read
    one ``selected``."""
    chunk = len(q.shape) == 3
    helper = LayerHelper("sparse_index_chunk" if chunk else "sparse_index",
                         name=name)
    cap = int(cache_k.shape[1])
    if chunk:
        selected = helper.create_variable_for_type_inference(
            dtype="bool", shape=tuple(q.shape[:2]) + (cap,))
    else:
        selected = helper.create_variable_for_type_inference(
            dtype="int32",
            shape=(q.shape[0], top_k if cap < 0 else min(top_k, cap)))
    count = helper.create_variable_for_type_inference(dtype="int32",
                                                      shape=(2,))
    for var in (selected, count):
        var.stop_gradient = True
    helper.append_op(helper.layer_type,
                     {"Q": q, "W": w, "CacheK": cache_k, "Pos": pos},
                     {"Mask" if chunk else "Index": selected,
                      "Count": count},
                     {"num_heads": int(num_heads), "top_k": int(top_k)})
    return selected, count


def latent_attention(q, cache, selected, pos, num_heads, kv_lora_rank,
                     qk_nope_head_dim, v_head_dim, scale, param_attr=None,
                     dense=False, name=None):
    """Latent (MLA) attention over the set :func:`sparse_index` selected,
    in the absorbed form (ops ``latent_attention`` /
    ``latent_attention_chunk``): ``q`` heads of ``[q_nope | q_pe]`` with
    the rotary part turned, ``cache`` [B, C, R+P] ONE row ``[normed latent |
    rotary key]`` a position with the current rows written. Creates the
    latent's up-projection ``kv_b`` [R, H * (N + V)] (``param_attr``),
    whose two parts a head take the query into the latent and the mixed
    latent out to V. Step: q [B, H*(N+P)], ``selected`` [B, S] positions;
    chunk: q [B, K, H*(N+P)], ``selected`` [B, K, C] mask, ``pos`` [B, K].
    ``selected`` None (a model that has no indexer; q [B, K, H*(N+P)],
    ``pos`` [B, K]): lane k reads every position up to ``pos[b, k]``, a
    chunk's lanes in blocks under a streaming softmax, and with ``dense``
    (a step's one or two lanes) all of a row's queries at once (op
    ``latent_attention_dense``: on one TPU a kernel that reads a row's
    cache up to the highest position its lanes hold, elsewhere the whole
    cache under the mask). Returns [.., H*V]."""
    chunk = len(q.shape) == 3
    if selected is None and not chunk:
        raise ValueError("latent attention without a selection takes q "
                         "[B, K, H*(N+P)] and pos [B, K]")
    helper = LayerHelper(
        "latent_attention_dense" if selected is None and dense
        else "latent_attention_chunk" if chunk else "latent_attention",
        param_attr=param_attr, name=name)
    kv_b = helper.create_parameter(
        helper.param_attr,
        shape=[int(kv_lora_rank),
               int(num_heads) * (int(qk_nope_head_dim) + int(v_head_dim))],
        dtype=_dtype(q), default_initializer=XavierInitializer())
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(q),
        shape=tuple(q.shape[:-1]) + (int(num_heads) * int(v_head_dim),))
    inputs = {"Q": q, "KvB": kv_b, "Cache": cache}
    if chunk:
        inputs["Pos"] = pos
        if selected is not None:
            inputs["Mask"] = selected
    else:
        inputs["Index"] = selected
    helper.append_op(helper.layer_type, inputs, {"Out": out},
                     {"num_heads": int(num_heads),
                      "nope_dim": int(qk_nope_head_dim),
                      "v_dim": int(v_head_dim), "scale": float(scale)})
    return out


def last_live_lane(x, pos, cache, name=None):
    """``x`` [B, K, D] -> [B, D]: each row's lane of highest position inside
    ``cache`` [B, C, ..] (``pos`` [B, K] < C; lane 0 of a row of pad lanes
    alone). What a chunk program builds a head on where only a row's last
    prompt lane needs one."""
    helper = LayerHelper("last_live_lane", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=_dtype(x), shape=(x.shape[0], x.shape[2]))
    helper.append_op("last_live_lane", {"X": x, "Pos": pos, "Cache": cache},
                     {"Out": out}, {})
    return out


def self_draft_accept(tok, greedy, draft, pos, cache, name=None):
    """The greedy accept rule of a step that verifies one draft a row,
    inside the executable (op ``self_draft_accept``): ``tok`` [B, 2] the
    committed token and the draft of the next, ``greedy`` [B, 2] the model's
    best token after each lane, ``draft`` [B, 2] the prediction module's
    after each lane, ``pos`` [B, 2] the lanes' positions in ``cache``
    [B, C, ..] (a lane past it is a pad lane: no draft was fed). Returns
    ``(yield, judged, next_tok, next_pos)``: [B, 4] int32, how many tokens
    the row yields (2 iff a draft was fed and is the model's own token after
    the committed one), the two tokens, and the draft of the token after
    those that stand; [2] int32, the drafts judged and those that stood;
    and [B, 2] each, ``tok`` and ``pos`` of the step to come if every row
    goes on (for a loop that feeds it before it has read this one)."""
    helper = LayerHelper("self_draft_accept", name=name)
    rows = tok.shape[0]
    out = helper.create_variable_for_type_inference(dtype="int32",
                                                    shape=(rows, 4))
    judged = helper.create_variable_for_type_inference(dtype="int32",
                                                       shape=(2,))
    next_tok = helper.create_variable_for_type_inference(
        dtype=_dtype(tok), shape=(rows, 2))
    next_pos = helper.create_variable_for_type_inference(
        dtype=_dtype(pos), shape=(rows, 2))
    for var in (out, judged, next_tok, next_pos):
        var.stop_gradient = True
    helper.append_op("self_draft_accept",
                     {"Tok": tok, "Greedy": greedy, "Draft": draft,
                      "Pos": pos, "Cache": cache},
                     {"Yield": out, "Judged": judged, "NextTok": next_tok,
                      "NextPos": next_pos}, {})
    return out, judged, next_tok, next_pos
