"""SGD with momentum and L2 weight decay as Fluid 1.3 applies them
(``momentum_op.h``; ``regularizer.L2Decay`` adds ``coeff * p`` to the
gradient before the optimizer sees it): v = mu * v + g', p = p - lr * v.

``first_gradient``: after ONE step from zero velocity, v = g' (the gradient
as the optimizer gets it, decay included).
"""


def build(fluid, hp):
    reg = (fluid.regularizer.L2Decay(hp["l2_decay"])
           if hp.get("l2_decay") else None)
    return fluid.optimizer.Momentum(
        learning_rate=hp["learning_rate"], momentum=hp["momentum"],
        regularization=reg)


def first_gradient(hp):
    return "_velocity_0", 1.0


def effective_gradient(params, grads, hp):
    """The gradient as the optimizer gets it: weight decay included."""
    import jax

    decay = hp.get("l2_decay", 0.0)
    return jax.tree.map(lambda g, p: g + decay * p, grads, params)


def init(params):
    import jax
    import jax.numpy as jnp

    return {"v": jax.tree.map(jnp.zeros_like, params)}


def update(params, grads, state, hp):
    import jax

    mu, lr, decay = hp["momentum"], hp["learning_rate"], \
        hp.get("l2_decay", 0.0)
    v = jax.tree.map(lambda v, g, p: mu * v + g + decay * p, state["v"],
                     grads, params)
    new = jax.tree.map(lambda p, v: p - lr * v, params, v)
    return new, {"v": v}
