"""Everything a run draws from ``--seed``: the weights and the host batches.

The weights are made by the benchmark, not by the program's startup program,
so that the plain reference can be given the same ones without taking
anything the program has made. All leaves come out of ONE jitted call, on
the device, in float32 (the type the configurations keep their master
weights in). The same seed gives the same weights and batches; a seed may be
any whole number up to a little over 2**31.
"""

import re

import numpy as np


def _fold(seed):
    """Two non-negative 31-bit words of a seed that may pass 2**31."""
    seed = int(seed)
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


# ``Program.random_seed`` of every run. Nothing of a cell depends on it: the
# benchmark's weights replace the startup program's, and the timed steps draw
# no random numbers (dropout 0). It is a constant, and not made from
# ``--seed``, so that no executable can come to depend on the run's seed.
PROGRAM_SEED = 1


def init_kind(name, rules):
    """The first rule of the configuration's ``init`` list whose pattern
    matches the whole parameter name decides how the leaf is drawn."""
    for pattern, kind in rules:
        if re.fullmatch(pattern, name):
            return kind
    raise KeyError("no init rule of the configuration matches parameter %r"
                   % name)


def _draw(key, shape, kind):
    import jax
    import jax.numpy as jnp

    normal = jax.random.normal(key, shape, jnp.float32)
    if kind == "fan_in":
        # [in, out] matrices and [out, in, kh, kw] filters
        fan_in = shape[0] if len(shape) == 2 else int(np.prod(shape[1:]))
        return normal * (1.0 / np.sqrt(fan_in))
    if kind == "he_fan_in":
        fan_in = shape[0] if len(shape) == 2 else int(np.prod(shape[1:]))
        return normal * np.sqrt(2.0 / fan_in)
    if kind == "embedding":
        return normal * (1.0 / np.sqrt(shape[-1]))
    if kind == "scale":
        return 1.0 + 0.1 * normal
    if kind == "small_scale":
        # the last batch norm of a residual branch: a branch that starts
        # small keeps activations and gradients of a deep net in range
        return 0.2 * (1.0 + 0.1 * normal)
    if kind == "bias":
        return 0.02 * normal
    raise KeyError("unknown init kind %r" % kind)


def make_weights_fn(specs, seed):
    """``specs``: [(name, shape, kind)]. Returns a function of no arguments
    that makes {name: float32 array} on the default device in one jitted
    call. The seed goes in as an argument, so one executable serves every
    seed and only the first run of a cell compiles it (baked in as a
    constant, it made every unseen seed compile for 45 s: my chip runs,
    PR 23)."""
    import jax

    lo, hi = (np.int32(word) for word in _fold(seed))
    specs = [(n, tuple(int(d) for d in s), k) for n, s, k in specs]

    @jax.jit
    def make(lo, hi):
        root = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return {name: _draw(jax.random.fold_in(root, i), shape, kind)
                for i, (name, shape, kind) in enumerate(specs)}

    return lambda: make(lo, hi)


def _dim(value, sizes):
    return int(sizes[value]) if isinstance(value, str) else int(value)


def make_batches(feeds, sizes, seed, count):
    """``count`` distinct host batches as numpy, from the configuration's
    ``feeds`` rules and the traffic's sizes (``batch`` is the global batch).
    Every row of every batch is drawn afresh, so all rows differ."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xB47C4]))
    batches = []
    for _ in range(count):
        batch = {}
        for name, rule in feeds.items():
            shape = tuple(_dim(d, sizes) for d in rule["shape"])
            dtype = np.dtype(rule["dtype"])
            if rule["kind"] == "int":
                batch[name] = rng.integers(
                    _dim(rule["low"], sizes), _dim(rule["high"], sizes),
                    size=shape).astype(dtype)
            elif rule["kind"] == "full":
                batch[name] = np.full(shape, _dim(rule["value"], sizes),
                                      dtype)
            elif rule["kind"] == "normal":
                batch[name] = rng.standard_normal(shape, np.float32).astype(
                    dtype)
            else:
                raise KeyError("unknown feed kind %r" % rule["kind"])
        batches.append(batch)
    return batches
