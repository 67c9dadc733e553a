"""Plain reference of ResNet-50 (He et al. 2015, bottleneck, 3-4-6-3) on
NCHW images: forward and loss in ``jax.numpy``, float32, no kernels;
gradients by ``jax.grad``.

As the repo's builder and the reference framework's
``benchmark/fluid/models/resnet.py`` have it: the stride of a down-sampling
block sits on its 3x3 convolution, convolutions carry no bias, batch norm
normalizes with the batch's own biased variance (training mode, epsilon
1e-5), a 3x3/2 max pool follows the stem, and the loss is the mean softmax
cross-entropy. The running statistics do not enter the training loss and
are not followed.

Convolution ``i`` and batch norm ``i`` are numbered in the order the builder
creates them: the stem, then for each block its projection shortcut (where
the shape changes) before its three convolutions. Each bottleneck block is
recomputed in the backward pass (``jax.checkpoint``) so that float32
activations of the whole batch fit beside nothing else on one chip; batch
norm needs the whole batch, so the rows cannot be cut into blocks.
"""

import jax
import jax.numpy as jnp

STAGES = (3, 4, 6, 3)


def _conv_bn(ops, p, i, x, stride, relu):
    w = p["conv2d_%d.w_0_0" % i]
    y = ops.conv(x, w, stride, (w.shape[2] - 1) // 2)
    mean = jnp.mean(y, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), (0, 2, 3), keepdims=True)
    y = (y - mean) * jax.lax.rsqrt(var + 1e-5)
    y = (y * p["batch_norm_%d.w_0_0" % i][None, :, None, None]
         + p["batch_norm_%d.b_0_0" % i][None, :, None, None])
    return jax.nn.relu(y) if relu else y


def _bottleneck(ops, first, project, stride, p, x):
    i = first
    short = x
    if project:
        short = _conv_bn(ops, p, i, x, stride, False)
        i += 1
    y = _conv_bn(ops, p, i, x, 1, True)
    y = _conv_bn(ops, p, i + 1, y, stride, True)
    y = _conv_bn(ops, p, i + 2, y, 1, False)
    return jax.nn.relu(short + y)


def loss(params, batch, args, ops):
    x = _conv_bn(ops, params, 0, batch["img"], 2, True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))
    i = 1
    for stage, count in enumerate(STAGES):
        for block in range(count):
            project = block == 0
            stride = 2 if (block == 0 and stage > 0) else 1
            fn = jax.checkpoint(
                lambda p, x, i=i, project=project, stride=stride:
                _bottleneck(ops, i, project, stride, p, x))
            x = fn(params, x)
            i += 4 if project else 3
    x = jnp.mean(x, (2, 3))
    logits = ops.dot(x, params["fc_0.w_0_0"]) + params["fc_0.b_0_0"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    label = batch["label"].reshape(-1)
    return -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=-1))
