"""How ``correct`` is decided for a training cell.

``follow``: the configuration's plain reference (``benchmark/reference/``)
takes the same seeded weights and the same first host batches as the program
and follows the same three optimizer steps, in float32 at ``highest``
precision (or, as the control, in fp8: ``reference/precision.py``). It
imports nothing of the program and is given nothing the program made.

``compare``: each step's loss; the norm of the first gradient as the
optimizer gets it; the norm of the parameters' change after the three steps.
The two norms are taken leaf by leaf and compared by the worst leaf: the gap
between the program's norm and the reference's (not the norm of their
difference), measured against the reference's norm of that leaf or of the
median leaf, whichever is larger, since some gradients are all but zero.
The gradient's gaps are also averaged over the large leaves, the number that
tells a lower precision from the stated one (``large_leaf_mean_gap``).
Each number has its own limit, from the configuration's ``limits``.
"""

import json
import os
import statistics
import time

import numpy as np

from benchmark import harness


def follow(run, weights_fn, batches, builder_args, precision):
    """Follow ``len(batches)`` steps with the reference. Returns what
    ``train.first_steps`` returns for the program."""
    import jax
    import jax.numpy as jnp

    cfg = run.config
    reference = harness.load_module(run.path(cfg["reference"]))
    ops = harness.load_module(os.path.join(
        harness.HERE, "reference", "precision.py")).BY_NAME[precision]
    optimizer = harness.load_module(os.path.join(
        harness.HERE, "optimizers", cfg["optimizer"]["name"] + ".py"))
    hp = cfg["optimizer"]
    rows = int(run.traffic["sizes"]["batch"])
    block = int(run.traffic.get("reference_row_block") or rows)
    if rows % block:
        raise ValueError("reference_row_block %d does not divide the batch "
                         "of %d rows" % (block, rows))
    nblocks = rows // block

    def loss_fn(params, batch):
        return reference.loss(params, batch, builder_args, ops)

    def loss_and_grads(params, batch):
        if nblocks == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        # blocks of rows, each with the same number of samples, so the
        # batch's mean is the mean of the blocks' means
        blocks = jax.tree.map(
            lambda a: a.reshape((nblocks, block) + a.shape[1:]), batch)

        def body(carry, one):
            value, grads = jax.value_and_grad(loss_fn)(params, one)
            return (carry[0] + value,
                    jax.tree.map(jnp.add, carry[1], grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (value, grads), _ = jax.lax.scan(body, zero, blocks)
        return value / nblocks, jax.tree.map(lambda g: g / nblocks, grads)

    def norms(tree):
        return {n: jnp.linalg.norm(v.ravel()) for n, v in tree.items()}

    @jax.jit
    def step(params, state, batch):
        value, grads = loss_and_grads(params, batch)
        new_params, new_state = optimizer.update(params, grads, state, hp)
        seen = optimizer.effective_gradient(params, grads, hp)
        return new_params, new_state, value, norms(seen)

    @jax.jit
    def change_norms(params, start):
        return norms(jax.tree.map(jnp.subtract, params, start))

    device = run.devices[0]
    with jax.default_device(device):
        params = weights_fn()
        if len(run.devices) > 1:
            params = jax.device_put(params, device)
        state = optimizer.init(params)
        losses, grad_norms = [], None
        for k, batch in enumerate(batches):
            t0 = time.perf_counter()
            batch = {n: jnp.asarray(_narrow(v)) for n, v in batch.items()}
            params, state, value, seen = step(params, state, batch)
            losses.append(float(value))
            print("reference (%s) step %d: %.2f s%s" % (
                precision, k + 1, time.perf_counter() - t0,
                " (trace, lower, compile or cache load included)"
                if k == 0 else ""))
            if k == 0:
                grad_norms = {n: float(v) for n, v in seen.items()}
        change = change_norms(params, weights_fn())
        change = {n: float(v) for n, v in change.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def dump(run, who, observed, expected):
    """Keep every leaf's norms of one comparison under ``benchmark_out/``,
    for whoever has to find out why a number read as it did."""
    folder = run.path("benchmark_out", "check")
    os.makedirs(folder, exist_ok=True)
    name = "%s.%d.%s.json" % (run.cell["name"], run.seed, who)
    with open(os.path.join(folder, name), "w") as f:
        json.dump({"observed": observed, "expected": expected}, f)


def _narrow(array):
    """int64 ids as int32 (JAX without x64 would truncate them anyway)."""
    return array.astype(np.int32) if array.dtype == np.int64 else array


def worst_leaf_gap(observed, expected):
    """(gap, leaf): the largest |observed - expected| norm gap over the
    leaves, against max(expected norm of the leaf, expected median norm)."""
    floor = statistics.median(expected.values())
    worst, leaf = 0.0, None
    for name, ref in expected.items():
        got = observed[name]
        if not np.isfinite(got):
            return float("inf"), name
        gap = abs(got - ref) / max(ref, floor, 1e-30)
        if gap >= worst:
            worst, leaf = gap, name
    return worst, leaf


LARGE_LEAF = 4096


def large_leaf_mean_gap(observed, expected, sizes):
    """The mean norm gap over the leaves of at least LARGE_LEAF elements
    (weight matrices, filters, embeddings; not the scales and biases of a
    few hundred elements). Rounding that is random from element to element
    projects onto a small leaf's gradient with a random sign, so the worst
    leaf swings in first order and tells a lower precision from the stated
    one badly; over a large leaf it moves the norm in second order only, so
    this mean is steady from seed to seed and reads the precision itself."""
    floor = statistics.median(expected.values())
    gaps = []
    for name, ref in expected.items():
        if sizes[name] < LARGE_LEAF:
            continue
        got = observed[name]
        if not np.isfinite(got):
            return float("inf")
        gaps.append(abs(got - ref) / max(ref, floor, 1e-30))
    return float(np.mean(gaps))


def compare(observed, expected, limits, sizes):
    """Rows of {name, value, limit, ok}, one per number compared. ``sizes``:
    {leaf: number of elements}."""
    rows = []

    def row(name, value, limit, note=""):
        ok = bool(np.isfinite(value) and value <= limit)
        rows.append({"name": name, "value": float(value),
                     "limit": float(limit), "ok": ok, "note": note})

    for k, (got, ref) in enumerate(zip(observed["losses"],
                                       expected["losses"])):
        row("loss_step%d_rel_gap" % (k + 1),
            abs(got - ref) / abs(ref) if np.isfinite(got) else float("inf"),
            limits["loss_rel_gap"])
    gap, leaf = worst_leaf_gap(observed["grad_norms"],
                               expected["grad_norms"])
    row("first_grad_norm_worst_leaf_gap", gap, limits["grad_norm_gap"], leaf)
    row("first_grad_norm_large_leaf_mean_gap",
        large_leaf_mean_gap(observed["grad_norms"], expected["grad_norms"],
                            sizes),
        limits["grad_large_leaf_mean_gap"])
    gap, leaf = worst_leaf_gap(observed["change_norms"],
                               expected["change_norms"])
    row("param_change_norm_worst_leaf_gap", gap, limits["change_norm_gap"],
        leaf)
    return rows
