"""The one generator of serving traffic. A mix is a data file of parameters
(``traffic/<name>.json``); nothing here knows a mix by name.

``lengths``: ``prompt`` and ``answer``, each ``{"median", "sigma", "min",
"max"}`` of a log-normal clipped to whole tokens. ``arrivals``: ``{"kind":
"poisson", "rate_per_s"}`` (exponential gaps, open loop) or ``{"kind":
"backlog", "requests", "block"}`` (all due at the schedule's start, block
after block of the same ``block`` requests; the timed window opens when
answer number ``open_after`` has arrived). In a ``poisson`` mix ``ramp_s``
seconds of the schedule run before the timed window opens and ``tail_s``
after it closes (a traced run profiles there; a backlog has work left when
the window closes and needs none).

A mix is ONE FIXED TRACE, replayed by every run: a ``poisson`` schedule is
made of three segments, ramp, window and tail; each holds ``round(rate x
its seconds)`` requests whose (prompt length, answer length) pairs, whose
exponential gaps (scaled to fill the segment) and whose order are drawn
ONCE from the mix's ``shape_seed`` (the lengths are the distribution's
quantiles, paired at random, so that a few tens of requests already have
its median and its tails). A run's ``--seed`` draws the token ids (and the
weights) and nothing else: the scheduler is deterministic, so what a
window completes differs from run to run only by the jitter of the clock
(an order drawn from the seed changed which prompts met which chunk rung
and read 8% apart from seed to seed: PERF.md section 6). ``max_new_tokens``
is the drawn answer length and there is no end-of-sequence token, so no
request ends early.
"""

import math
import statistics

import numpy as np


def _rng(seed, tag):
    seed = int(seed)
    return np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32, tag]))


def _lengths(rng, rule, n):
    """``n`` whole token counts that stand for the clipped log-normal: its
    quantiles at (i + 1/2) / n, so that a few requests already have its
    median and its tails, in an order drawn from ``rng``."""
    at = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    drawn = np.exp(math.log(rule["median"]) + rule["sigma"] * np.array(at))
    whole = np.clip(np.rint(drawn), rule["min"], rule["max"]).astype(int)
    return whole[rng.permutation(n)] if n else whole


class Request:
    __slots__ = ("index", "at", "prompt", "max_new", "submitted", "done",
                 "tokens", "error")

    def __init__(self, index, at, prompt, max_new):
        self.index = index
        self.at = float(at)        # seconds after the schedule's start
        self.prompt = prompt       # int64 [P]
        self.max_new = int(max_new)
        self.submitted = None      # client's clock, when submit returned
        self.done = None           # client's clock, when the answer came
        self.tokens = None         # int64 [max_new] as delivered
        self.error = None

    @property
    def positions(self):
        return len(self.prompt) + self.max_new


def _segment(shape, rng, lengths, vocab, start, seconds, count, first):
    """``count`` requests due inside [start, start + seconds): lengths,
    gaps and their order from ``shape``; the run's ``rng`` draws the token
    ids."""
    prompts = _lengths(shape, lengths["prompt"], count)
    answers = _lengths(shape, lengths["answer"], count)
    gaps = shape.exponential(1.0, count)
    if count == 0:
        return []
    gaps = gaps * (seconds / gaps.sum())
    at = start + np.cumsum(gaps) - gaps[0] / 2 if seconds else np.full(
        count, float(start))
    return [Request(first + i, at[i],
                    rng.integers(0, vocab, size=int(prompts[i]),
                                 dtype=np.int64), answers[i])
            for i in range(count)]


def schedule(traffic, vocab, seed, seconds):
    """[Request] in order of arrival: ramp, window, tail."""
    arrivals, lengths = traffic["arrivals"], traffic["lengths"]
    shape = _rng(traffic["shape_seed"], 0x5E47E)
    rng = _rng(seed, 0x7EAFF1C)
    if arrivals["kind"] == "backlog":
        # block after block of the same requests: whatever stretch of the
        # queue a window serves, it serves nearly the same work
        out = []
        block = int(arrivals["block"])
        state = shape.bit_generator.state
        while len(out) < int(arrivals["requests"]):
            shape.bit_generator.state = state
            out += _segment(shape, rng, lengths, vocab, 0.0, 0.0, block,
                            len(out))
        return out[:int(arrivals["requests"])]
    if arrivals["kind"] != "poisson":
        raise KeyError("unknown arrivals kind %r" % arrivals["kind"])
    rate, out, start = float(arrivals["rate_per_s"]), [], 0.0
    for length in (float(arrivals["ramp_s"]), float(seconds),
                   float(arrivals.get("tail_s", 0.0))):
        out += _segment(shape, rng, lengths, vocab, start, length,
                        int(round(rate * length)), len(out))
        start += length
    return out
