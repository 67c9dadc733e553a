"""Adam as Fluid 1.3 documents it (``adam_op.h``): bias correction folded
into the step size, epsilon added to sqrt(v) uncorrected.

``build`` makes the program's optimizer; ``init`` / ``update`` are the plain
reference's, float32 ``jax.numpy``; ``first_gradient`` says how the gradient
the optimizer was given is read back from the program's state after ONE
step: moment1 = (1 - beta1) * g.
"""


def build(fluid, hp):
    return fluid.optimizer.Adam(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"])


def first_gradient(hp):
    """(suffix of the state variable, factor): g = state * factor."""
    return "_moment1_0", 1.0 / (1.0 - hp["beta1"])


def effective_gradient(params, grads, hp):
    """The gradient as the optimizer gets it."""
    return grads


def init(params):
    import jax
    import jax.numpy as jnp

    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.float32)}


def update(params, grads, state, hp):
    import jax
    import jax.numpy as jnp

    b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["epsilon"], \
        hp["learning_rate"]
    t = state["t"] + 1.0
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = jax.tree.map(lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps),
                       params, m, v)
    return new, {"m": m, "v": v, "t": t}
