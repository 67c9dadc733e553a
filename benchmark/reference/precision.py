"""The two precisions a plain reference is computed in.

``exact``: float32 operands, ``Precision.HIGHEST`` (on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes).

``fp8``: the control. The configurations state bfloat16 compute; the nearest
precision below it is 8-bit floating point, the step that would tempt a
later PR. Every matrix multiplication and convolution quantizes its two
operands to float8_e4m3fn and, in the backward pass, the incoming gradient
to float8_e5m2, each with one scale per tensor taken from its largest
magnitude (the usual fp8 training recipe); products accumulate in float32.
A run of the control has to come out as NOT correct.
"""

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _quantize(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _fp8(bilinear):
    """Wrap a bilinear ``f(a, b)`` so that operands and cotangent pass
    through 8-bit floating point."""

    @jax.custom_vjp
    def f(a, b):
        return bilinear(_quantize(a, jnp.float8_e4m3fn),
                        _quantize(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        qa = _quantize(a, jnp.float8_e4m3fn)
        qb = _quantize(b, jnp.float8_e4m3fn)
        return bilinear(qa, qb), (qa, qb)

    def bwd(saved, g):
        _, vjp = jax.vjp(bilinear, *saved)
        return vjp(_quantize(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f


class Ops:
    """The bilinear operations a reference is written in."""

    def __init__(self, wrap):
        self._wrap = wrap

    def einsum(self, spec, a, b):
        return self._wrap(functools.partial(
            jnp.einsum, spec, precision=_HIGHEST))(a, b)

    def dot(self, x, w):
        """[..., k] @ [k, n]"""
        return self.einsum("...k,kn->...n", x, w)

    def conv(self, x, w, stride, padding):
        """NCHW image, OIHW filter."""
        return self._wrap(functools.partial(
            jax.lax.conv_general_dilated, window_strides=(stride, stride),
            padding=((padding, padding), (padding, padding)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=_HIGHEST))(x, w)


exact = Ops(lambda f: f)
fp8 = Ops(_fp8)

BY_NAME = {"exact": exact, "fp8": fp8}
