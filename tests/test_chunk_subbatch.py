"""A chunk run computes the rows that ingest (ISSUE 38): where a rung's
height (``decode_batcher.chunk_rows``) is under the bucket, the decode loop
gathers the oldest ingesting rows into a sub-batch, hands the chunk
executable that, and scatters the lanes it wrote back into the slot table.

Everything here runs on the CPU over the tiny decoder of ``test_serving``:
which tokens come out, which rows ride and which wait, what shapes the chunk
predictor is fed, what a row nobody holds looks like afterwards. What a
chunk run costs on the chip is the benchmark's to show
(``prefill_ms_per_ktok``, ``chunk_lane_fill_pct``)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving import decode_batcher
from paddle_tpu.serving.decode_batcher import DecodeBatcher, chunk_rows

from test_serving import _build_lm_family

B, C = 4, 32            # one bucket: 4 slot rows of 32 positions
WHOLE = 1024            # every tiny rung covers B (the committed 512 does too)


@pytest.fixture(scope="module")
def family():
    """(step predictor, step spec, prefill dict, draft) on one scope."""
    return _build_lm_family(fluid.Scope())


@pytest.fixture
def budget(monkeypatch):
    """Set the token budget a chunk run: the tiny ladder (4, 8, 16) in a
    bucket of 4 rows is sub-batched only under a tiny budget."""
    def set_to(tokens):
        monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", tokens)
    return set_to


def _batcher(family, **kw):
    pred, dspec, prefill, draft = family
    kw.setdefault("ladder", (B,))
    kw.setdefault("ctx_ladder", (C,))
    kw.setdefault("prefill", prefill)
    if kw.pop("speculate", False):
        kw["speculative"] = {"draft": draft, "k": 4}
    return DecodeBatcher(pred, dspec, start=False, **kw)


def _tokens(future):
    return tuple(int(t) for t in np.asarray(future.result(0)).ravel())


def _prompt(n, first=1):
    return [(first + 3 * j) % 27 + 1 for j in range(n)]


# -- the one derivation ------------------------------------------------------

@pytest.mark.parametrize("tokens,bucket,rungs,rows", [
    (1024, 16, (64, 128, 256, 512), (16, 8, 4, 2)),     # OPT's geometry
    (1024, 8, (256,), (4,)),                            # GLM-5.2's
    (1024, 4, (4, 8, 16), (4, 4, 4)),   # a bucket the budget covers whole
    (512, 16, (64, 128, 256, 512), (8, 4, 2, 1)),
    (1024, 16, (2048, 4096), (1, 1)),   # never under one row
    (1000, 6, (64, 128, 200), (6, 4, 4)),   # a power of two, or the bucket
])
def test_a_rungs_height_is_the_budget_over_the_rung(
        monkeypatch, tokens, bucket, rungs, rows):
    monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", tokens)
    assert tuple(chunk_rows(k, bucket) for k in rungs) == rows


# -- (i) the same answers, whoever rides -------------------------------------

def _serve(family, prompts, max_new=4, **kw):
    bat = _batcher(family, **kw)
    futs = [bat.submit(p, max_new_tokens=max_new) for p in prompts]
    bat.drive()
    assert all(f.done() for f in futs)
    return [_tokens(f) for f in futs], bat.metrics()


@pytest.mark.parametrize("candidates,deferred", [
    (1, False),     # fewer than the rung's two rows: a pad sub-row
    (2, False),     # exactly as many
    (3, True),      # more: the youngest waits a chunk
    (4, True),
])
def test_answers_are_those_of_chunks_over_the_whole_bucket(
        family, budget, candidates, deferred):
    # prompts of 5: 4 tokens by chunk, rung 4, two rows under a budget of 8
    prompts = [_prompt(5, first) for first in range(candidates)]
    budget(WHOLE)
    want, whole = _serve(family, prompts)
    budget(8)
    assert chunk_rows(4, B) == 2
    got, sub = _serve(family, prompts)
    assert got == want
    # a row left behind rides the step that follows (one token) and then
    # the next chunk: every prompt token but those goes in by chunk
    assert whole["prefill_tokens"] == 4 * candidates
    assert 0 <= whole["prefill_tokens"] - sub["prefill_tokens"] \
        <= sub["prefill_deferred_rows"]
    assert (sub["prefill_deferred_rows"] > 0) == deferred
    assert whole["prefill_deferred_rows"] == 0
    # lanes are the rows computed x the rung: two, not four, a dispatch
    assert sub["prefill_lanes"] == 2 * 4 * sub["prefill_chunks"]
    assert whole["prefill_lanes"] == B * 4 * whole["prefill_chunks"]


def test_the_oldest_admissions_ride_first_and_nobody_starves(family, budget):
    budget(8)
    bat = _batcher(family)
    # slots are recycled holes: make the oldest live request sit in a HIGH row
    first = [bat.submit([5], max_new_tokens=1) for _ in range(3)]
    keep = bat.submit(_prompt(29, 2), max_new_tokens=2)     # row 3
    bat._admit()
    futs = {3: keep}
    bat._tick()                             # chunk: 16 of row 3's 28
    bat._tick()                             # step: three retire, one more
    assert all(f.done() for f in first)
    for row, n in ((0, 5), (1, 9), (2, 5)):                 # younger, rows 0-2
        futs[row] = bat.submit(_prompt(n, row), max_new_tokens=2)
    bat._admit()
    assert [s.req.order for s in bat._slots] == [4, 5, 6, 3]
    before = [s.pos for s in bat._slots]
    chunks = bat.metrics()["prefill_chunks"]
    while bat.metrics()["prefill_chunks"] == chunks:
        bat._tick()
    moved = [s.pos - p for s, p in zip(bat._slots, before)]
    # the head (row 3) has 11 left: rung 16, ONE row under a budget of 8;
    # the three younger rows wait for the next chunk, whatever their row
    assert moved == [0, 0, 0, 11]
    assert bat.metrics()["prefill_deferred_rows"] == 3
    bat.drive()
    assert all(len(_tokens(f)) == 2 for f in futs.values())


def test_a_chunk_follows_a_chunk_where_only_ingesting_rows_are_left(
        family, budget):
    """Alternation is for rows that generate: with three rows ingesting and
    none generating, a chunk tick follows a chunk tick; once the head's
    prompt is in but for its last token, a step is due and comes."""
    budget(8)
    bat = _batcher(family)
    futs = [bat.submit(_prompt(25, first), max_new_tokens=2)
            for first in range(3)]              # 24 by chunk: 16, then 8
    bat._admit()
    for tick in (1, 2):                         # one row a chunk: the head
        bat._tick()
        assert bat.metrics()["prefill_chunks"] == tick
    assert [s.pos for s in bat._slots if s is not None] == [24, 0, 0]
    assert bat.metrics()["prefill_deferred_rows"] == 2 + 2
    bat._tick()                                 # the head's last token
    assert bat.metrics()["prefill_chunks"] == 2
    assert [s.pos for s in bat._slots if s is not None] == [25, 1, 1]
    bat.drive()
    assert all(len(_tokens(f)) == 2 for f in futs)


# -- (ii) beside the prefix cache and speculation ----------------------------

def test_installed_prefix_rows_survive_a_scatter_and_harvests_read_the_table(
        family, budget):
    shared = _prompt(8, 3)

    def scenario():
        bat = _batcher(family, prefix_cache=True)
        out = []
        for last in (1, 8, 1, 13):
            # a stranger ingests beside each: its chunk scatters into a
            # table that holds the installed prefix rows
            futs = [bat.submit(shared + [last, 2, 7, 4, 9], max_new_tokens=4),
                    bat.submit(_prompt(6, last), max_new_tokens=3)]
            bat.drive()
            out.append([_tokens(f) for f in futs])
        m = bat.metrics()
        assert m["prefix_hits"] > 0 and m["prefill_chunks"] > 0
        return out, m

    budget(WHOLE)
    want, _ = scenario()
    budget(8)
    got, m = scenario()
    assert got == want
    assert m["prefill_lanes"] < B * 16 * m["prefill_chunks"]    # sub-batched


def test_a_verifying_tick_keeps_every_row(family, budget):
    prompts = [_prompt(7, 1), [1, 2], [5]]
    budget(WHOLE)
    want, _ = _serve(family, prompts, max_new=6)
    budget(4)                       # one row a rung, were drafts not verified
    bat = _batcher(family, speculate=True)
    assert [bat._chunk_height(B, k) for k in bat.prefill_ladder] == [B] * 3
    futs = [bat.submit(p, max_new_tokens=6) for p in prompts]
    bat.drive()
    m = bat.metrics()
    assert [_tokens(f) for f in futs] == want
    assert m["spec_accepted"] + m["spec_rejected"] > 0
    assert m["prefill_deferred_rows"] == 0 and not bat._rows_staged
    # and the chunk executables are the bucket's: nothing sub-batched ran
    assert all(len(sig) == 2 or sig[0] == B for sig in bat.seen_signatures)


# -- (iii) what the chunk predictor is fed -----------------------------------

class Recording:
    """The chunk predictor, with the shapes of every feed it is run over."""

    def __init__(self, predictor, spec):
        self._predictor = predictor
        self._tok = spec["token_feed"]
        self._caches = [cf["feed"] for cf in spec["cache_feeds"]]
        self.fetch_names = predictor.fetch_names
        self.fed = []

    def run(self, feed, return_numpy=True, donate_feeds=()):
        self.fed.append((tuple(feed[self._tok].shape),
                         {tuple(feed[name].shape) for name in self._caches}))
        return self._predictor.run(feed, return_numpy=return_numpy,
                                   donate_feeds=donate_feeds)


def test_a_chunk_run_is_fed_the_sub_batch_and_one_geometry_a_rung(
        family, budget):
    budget(16)                      # rungs 4, 8, 16: four rows, two, one
    pred, dspec, prefill, draft = family
    recording = Recording(prefill["predictor"], prefill["spec"])
    bat = _batcher((pred, dspec, dict(prefill, predictor=recording), draft))
    heights = {k: chunk_rows(k, B) for k in bat.prefill_ladder}
    assert heights == {4: 4, 8: 2, 16: 1}
    for n in (5, 9, 13, 4, 8, 12, 3):       # every rung, several times
        bat.submit(_prompt(n, n), max_new_tokens=2)
        if n % 2:
            bat.drive()
    bat.drive()
    assert {tok for tok, _ in recording.fed} == {
        (heights[k], k) for k in bat.prefill_ladder}
    tails = [tuple(cf["tail"]) for cf in dspec["cache_feeds"]]
    for (rows, _k), caches in recording.fed:
        assert caches == {(rows, C) + tail for tail in tails}
    assert len(bat.seen_signatures) == 1 + len(bat.prefill_ladder)
    assert all(sig[:2] == (B, C) for sig in bat.seen_signatures)
    assert bat.compiled_shape_counts() == [1 + len(bat.prefill_ladder)]
    assert sum(bat.compiled_shape_counts()) <= bat.compile_cache_bound()


def test_warmup_makes_every_executable_a_schedule_then_runs(budget):
    budget(16)
    family = _build_lm_family(fluid.Scope())    # its executors' records alone
    pred, _dspec, prefill, _ = family
    bat = _batcher(family)
    assert bat.warmup() == 1 + len(bat.prefill_ladder)
    assert sorted(bat._rows_staged) == [(B, C, 8), (B, C, 16)]
    made = dict(bat._rows_staged)
    before = [len(p._exe.compile_records)
              for p in (pred, prefill["predictor"])]
    futs = [bat.submit(_prompt(n, n), max_new_tokens=2)
            for n in (5, 9, 13, 17)]
    bat.drive()
    assert all(len(_tokens(f)) == 2 for f in futs)
    assert before == [len(p._exe.compile_records)
                      for p in (pred, prefill["predictor"])]
    assert bat._rows_staged == made             # nor a copy made again
    assert len(bat.seen_signatures) == 1 + len(bat.prefill_ladder)


def test_the_copies_are_staged_with_the_rung_they_belong_to(budget):
    budget(8)                       # every rung under the bucket: 2, 1, 1
    family = _build_lm_family(fluid.Scope())
    bat = _batcher(family)
    bat.submit(_prompt(5, 1), max_new_tokens=2)
    bat._admit()
    bat._tick()                                 # the first chunk: rung 4
    assert bat.metrics()["prefill_chunks"] == 1
    for staging in bat._ahead.values():
        staging.join(120)
        assert not staging.is_alive()
    assert sorted(bat._rows_staged) == [(B, C, k) for k in bat.prefill_ladder]
    chunk_exe = family[2]["predictor"]._exe
    assert len(chunk_exe.compile_records) == len(bat.prefill_ladder)
    bat.drive()
    bat.shutdown()


# -- (iv) a row no candidate holds -------------------------------------------

def test_pad_sub_rows_and_rows_left_out_write_nothing(family, budget):
    budget(8)                                   # rung 4: two rows
    bat = _batcher(family)
    talker = bat.submit([5, 9], max_new_tokens=12)
    bat.drive(max_steps=4)                      # row 0 generates, holds rows
    bat.submit(_prompt(5, 2), max_new_tokens=2)     # row 1: 4 by chunk
    bat._admit()
    assert [s is not None for s in bat._slots] == [True, True, False, False]
    before = {name: np.array(a) for name, a in bat._caches.items()}
    pos = bat._slots[1].pos
    was = bat.metrics()
    bat._tick()
    m = bat.metrics()
    # one candidate in a sub-batch of two: the other sub-row is a pad
    assert [m[n] - was[n] for n in (
        "prefill_chunks", "prefill_tokens", "prefill_lanes")] == [1, 4, 2 * 4]
    for name, old in before.items():
        new = np.asarray(bat._caches[name])
        for row in (0, 2, 3):       # a generating row, two free ones
            assert np.array_equal(new[row], old[row]), (name, row)
        assert np.array_equal(new[1, :pos], old[1, :pos])
        assert np.array_equal(new[1, pos + 4:], old[1, pos + 4:])
        assert not np.array_equal(new[1, pos:pos + 4], old[1, pos:pos + 4])
    bat.drive()
    assert len(_tokens(talker)) == 12


def test_lanes_that_would_pass_the_capacity_are_written_back_whole(
        family, budget):
    """A chunk whose rung reaches past the cache's last position: the
    scatter slides its window down, and the lanes the run wrote are in it."""
    prompt = _prompt(29, 4)

    def scenario():
        bat = _batcher(family)
        talker = bat.submit([3, 1], max_new_tokens=8)
        bat.drive(max_steps=3)
        long = bat.submit(prompt, max_new_tokens=3)
        bat._admit()
        bat._tick()                 # chunk: 16 lanes from position 0
        bat._tick()                 # step: the talker's, and one more lane
        at = bat._slots[1].pos
        bat._tick()                 # chunk: 11 lanes of a rung of 16
        assert (at, bat._slots[1].pos) == (17, 28)
        bat.drive()
        return _tokens(talker), _tokens(long)

    budget(WHOLE)
    want = scenario()
    budget(16)                      # rung 16: one row, and 17 + 16 > 32
    assert scenario() == want
