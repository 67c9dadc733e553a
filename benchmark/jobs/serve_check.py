"""How ``correct`` is decided for a served model.

Once the window has closed, the program's state is freed and
``memory_peak_bytes`` is read, a sample of the requests the window finished
is drawn from the seed, the longest among them; some hundreds of served
tokens. The configuration's plain reference (``reference/<config>.py``:
``jax.numpy``, float32, ``Precision.HIGHEST``, the full forward pass under a
causal mask, no cache, no batching) runs once over each sampled prompt
followed by the tokens the system itself served (teacher forcing), and at
every served position the gap is read by which the served token's logit
lies below the reference's best. Greedy serving puts its own best first, so
the gap is what the system's arithmetic costs in logits: 0 where both agree
on the token, a rounding's worth where two logits tie. Compared, each with
its limit from the configuration's ``limits``: the widest gap and the mean
gap over the sample's served tokens. A request that came back with another
number of tokens than asked, or never, is ``failed``, not compared.

The control (``serve_control.py``) puts the reference in fp8 in the
program's place: at each position of the same prompts and tokens it reads
the gap of the token that the fp8 logits put first.
"""

import numpy as np

from benchmark import harness


def load_reference(run):
    return harness.load_module(run.path(run.config["reference"]))


def precision(run, name):
    ops = harness.load_module(run.path(
        "benchmark", "reference", "precision.py"))
    return ops.BY_NAME[name]


def sample(finished, seed, count):
    """``count`` of the finished requests, drawn from the seed, the longest
    (prompt + answer) always among them."""
    if not finished:
        return []
    finished = sorted(finished, key=lambda r: r.index)
    longest = max(finished, key=lambda r: (r.positions, -r.index))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xC4EC]))
    picks = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(picks)]


def _rows(reference, weights, request, args, ops, pad_to):
    """The reference's logits at the positions that predict the served
    tokens: float32 [answer tokens, vocabulary], on the device."""
    tokens = np.zeros(max(pad_to, request.positions), np.int32)
    p, n = len(request.prompt), len(request.tokens)
    tokens[:p] = request.prompt
    tokens[p:p + n] = request.tokens
    return reference.logits(weights, tokens, args, ops)[p - 1:p - 1 + n]


def served_gaps(reference, weights, requests, args, ops, pad_to):
    """float32 [served tokens of the sample]: the reference's best logit
    less its logit of the token that was served."""
    import jax.numpy as jnp

    out = []
    for request in requests:
        rows = _rows(reference, weights, request, args, ops, pad_to)
        served = jnp.asarray(request.tokens, jnp.int32)
        out.append(np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
            rows, served[:, None], axis=-1)[:, 0]))
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def control_gaps(reference, weights, requests, args, exact, lower, pad_to):
    """The control's reading: the reference in ``lower`` precision stands
    in the program's place; the gap of the token IT puts first."""
    import jax.numpy as jnp

    out = []
    for request in requests:
        rows = _rows(reference, weights, request, args, exact, pad_to)
        first = jnp.argmax(_rows(reference, weights, request, args, lower,
                                 pad_to), axis=-1)
        out.append(np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
            rows, first[:, None], axis=-1)[:, 0]))
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def compare(gaps, limits):
    """Rows of (name, value, limit, ok). No served token to compare is not
    correct."""
    if len(gaps) == 0:
        return [{"name": "served_tokens_compared", "value": 0.0,
                 "limit": 1.0, "ok": False, "note": "nothing to compare"}]
    rows = []
    for name, value in (("token_gap_max", float(np.max(gaps))),
                        ("token_gap_mean", float(np.mean(gaps)))):
        limit = float(limits[name])
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(np.isfinite(value) and value <= limit),
                     "note": "over %d served tokens" % len(gaps)})
    return rows
