"""The decode loop over a step program that drafts for itself
(``spec["self_draft"]``), driven by fakes whose model is a hash of a row's
cached history and whose draft is right where the test says: whatever
stands, the delivered chain is the plain greedy chain token for token, both
caches end as plain decode's, ``max_new`` odd or even, an end-of-sequence
token inside a pair, rows that retire beside rows that go on, and
``decode_tokens`` the tokens delivered."""

import numpy as np
import pytest

from paddle_tpu.serving.decode_batcher import DecodeBatcher, greedy_chain

VOCAB = 97


def nxt(prefix):
    """The fake model's best token after ``prefix``: every earlier token
    counts, so a stale or missing cache row changes it."""
    prefix = np.asarray(prefix, np.int64)
    return int((31 * int(np.sum(prefix * (1 + np.arange(len(prefix)) % 5)))
                + 7 * len(prefix) + 3) % VOCAB)


def plain_chain(prompt, n, eos=None):
    seq, out = list(prompt), []
    while len(out) < n:
        out.append(nxt(seq))
        seq.append(out[-1])
        if eos is not None and out[-1] == eos:
            break
    return out


class FakeStep:
    """Two lanes a row. ``hist`` caches the token of each position, ``mod``
    the token AFTER it (what the prediction module is given). ``right(p)``:
    whether the draft made at position p is the model's own token."""

    fetch_names = ["yield", "hist_out", "mod_out", "next_tok", "next_pos"]

    def __init__(self, right):
        self.right = right
        self.runs = 0
        self.caches = {}        # as the last run left them

    def run(self, feed, return_numpy=False):
        self.runs += 1
        tok, pos = np.asarray(feed["tok"]), np.asarray(feed["pos"])
        hist, mod = np.array(feed["hist"]), np.array(feed["mod"])
        b, c = hist.shape[:2]
        out = np.zeros((b, 4), np.int32)
        next_tok, next_pos = np.zeros((b, 2), np.int64), np.array(pos)
        for i in range(b):
            greedy, draft = [0, 0], [0, 0]
            for j in range(2):
                p = int(pos[i, j])
                if p >= c:
                    continue
                hist[i, p, 0] = tok[i, j]
                prefix = [int(t) for t in hist[i, :p + 1, 0]]
                greedy[j] = nxt(prefix)
                mod[i, p, 0] = greedy[j]
                # the module reads its own cache: the tokens after every
                # earlier position have to be there (a lane whose draft
                # does not stand reads what nobody will keep)
                if j == 0 or tok[i, 1] == greedy[0]:
                    assert [int(t) for t in mod[i, :p, 0]] == prefix[1:]
                true = nxt(prefix + [greedy[j]])
                draft[j] = true if self.right(p) else (true + 1) % VOCAB
            stands = pos[i, 1] < c and tok[i, 1] == greedy[0]
            out[i] = [1 + stands, greedy[0], greedy[1],
                      draft[1] if stands else draft[0]]
            next_tok[i] = [greedy[1] if stands else greedy[0], out[i, 3]]
            if pos[i, 0] < c:
                next_pos[i] = pos[i, 0] + out[i, 0] + np.arange(2)
        self.caches = {"hist": hist, "mod": mod}
        return [out, hist, mod, next_tok, next_pos]


class FakeChunk:
    """K prompt tokens a row and the token after each; one draft a row."""

    fetch_names = ["draft", "hist_out", "mod_out"]

    def __init__(self, right):
        self.right = right

    def run(self, feed, return_numpy=False):
        tok, pos = np.asarray(feed["ctok"]), np.asarray(feed["cpos"])
        hist, mod = np.array(feed["hist"]), np.array(feed["mod"])
        r, c = hist.shape[:2]
        assert tok.shape[1] == pos.shape[1] + 1
        draft = np.zeros((r,), np.int32)
        for i in range(r):
            live = [j for j in range(pos.shape[1]) if pos[i, j] < c]
            for j in live:
                hist[i, pos[i, j], 0] = tok[i, j]
                mod[i, pos[i, j], 0] = tok[i, j + 1]
            if live:
                p = int(pos[i, live[-1]])
                true = nxt([int(t) for t in hist[i, :p + 1, 0]]
                           + [int(tok[i, live[-1] + 1])])
                draft[i] = true if self.right(p) else (true + 1) % VOCAB
        return [draft, hist, mod]


_CACHES = [{"feed": "hist", "fetch": "hist_out", "tail": [1],
            "dtype": "int64"},
           {"feed": "mod", "fetch": "mod_out", "tail": [1],
            "dtype": "int64"}]
STEP_SPEC = {"token_feed": "tok", "pos_feed": "pos", "vocab": VOCAB,
             "ctx_cap": 64, "cache_feeds": _CACHES,
             "self_draft": {"lanes": 2, "yield_fetch": "yield",
                            "cache_feeds": ["mod"]}}
# the same step, its spec naming the two feeds it makes for the step to
# come: the loop then runs one step ahead
AHEAD_SPEC = dict(STEP_SPEC, self_draft=dict(
    STEP_SPEC["self_draft"], next_token_fetch="next_tok",
    next_pos_fetch="next_pos"))
CHUNK_SPEC = {"token_feed": "ctok", "pos_feed": "cpos", "vocab": VOCAB,
              "ctx_cap": 64, "cache_feeds": _CACHES,
              "self_draft": {"draft_fetch": "draft", "next_token_lane": 1}}

QUALITY = {"always": lambda p: True, "never": lambda p: False,
           "mixed": lambda p: p % 3 != 0}


def _batcher(right, slots=2, ahead=False, **kw):
    step = FakeStep(right)
    return step, DecodeBatcher(
        step, AHEAD_SPEC if ahead else STEP_SPEC, ladder=(slots,),
        ctx_ladder=(64,), start=False,
        prefill={"predictor": FakeChunk(right), "spec": CHUNK_SPEC,
                 "ladder": (4, 8)}, **kw)


@pytest.mark.parametrize("ahead", [False, True], ids=["read-first", "ahead"])
@pytest.mark.parametrize("max_new", [1, 2, 5, 6])
@pytest.mark.parametrize("quality", sorted(QUALITY))
def test_the_chain_is_the_plain_greedy_chain(quality, max_new, ahead):
    step, batcher = _batcher(QUALITY[quality], ahead=ahead)
    prompt = list(np.random.default_rng(2).integers(0, VOCAB, size=11))
    future = batcher.submit(prompt, max_new_tokens=max_new)
    batcher.drive()
    want = plain_chain(prompt, max_new)
    assert list(future.result()) == want
    m = batcher.metrics()
    assert m["decode_tokens"] == max_new
    # a step dispatched ahead for a row that then ends is read by nobody
    read = m["spec_steps"]
    assert read <= step.runs <= read + ahead
    assert m["decode_steps_ahead_total"] == (max(0, read - 1) if ahead else 0)
    # the chunk's draft, then one a step, but where one token was left
    seq = prompt + want
    if quality == "always":
        assert read == (max_new + 1) // 2
        assert m["spec_accepted"] == max_new // 2 <= m["spec_drafted"]
    elif quality == "never":
        assert read == max_new and m["spec_accepted"] == 0
        assert m["spec_drafted"] >= max_new - 1
    else:
        assert (max_new + 1) // 2 <= read <= max_new
        assert m["spec_accepted"] == max_new - read
    # both caches are plain decode's: the sequence, and the token after
    # each position; the last token is never fed back
    n = len(seq) - 1
    assert list(step.caches["hist"][0, :n, 0]) == seq[:n]
    assert list(step.caches["mod"][0, :n, 0]) == seq[1:n + 1]


@pytest.mark.parametrize("ahead", [False, True], ids=["read-first", "ahead"])
@pytest.mark.parametrize("quality", sorted(QUALITY))
def test_an_end_of_sequence_inside_a_pair_ends_the_row(quality, ahead):
    prompt = list(np.random.default_rng(3).integers(0, VOCAB, size=9))
    chain = plain_chain(prompt, 8)
    for at in (0, 1, 2, 3, 4):
        eos = chain[at]
        _, batcher = _batcher(QUALITY[quality], ahead=ahead)
        future = batcher.submit(prompt, max_new_tokens=8, eos_id=eos)
        batcher.drive()
        want = plain_chain(prompt, 8, eos)
        assert list(future.result()) == want and want[-1] == eos
        assert batcher.metrics()["decode_tokens"] == len(want)


@pytest.mark.parametrize("ahead", [False, True], ids=["read-first", "ahead"])
@pytest.mark.parametrize("quality", sorted(QUALITY))
def test_rows_retire_and_are_taken_while_others_go_on(quality, ahead):
    """Five requests of unlike lengths over two slots: a row that retires in
    the middle of its neighbour's pairs, and a request admitted into the
    slot it left, each ingested by chunks while the other row verifies."""
    step, batcher = _batcher(QUALITY[quality], ahead=ahead)
    rng = np.random.default_rng(4)
    jobs = [(list(rng.integers(0, VOCAB, size=n)), m)
            for n, m in ((5, 3), (17, 9), (2, 4), (1, 7), (12, 2))]
    futures = [batcher.submit(p, max_new_tokens=m) for p, m in jobs]
    # every step quantum dispatches a step: where the loop cannot tell that
    # the step after the one in flight will follow it (a row may end and
    # somebody waits for its slot) it reads that one in the quantum that
    # dispatched it, so no quantum is left a read alone (a reader of the
    # median ``decode.step`` span finds an ``executor.run`` in it)
    quantum, dispatched = batcher._step_once, []

    def counted(last=False):
        before = step.runs
        quantum(last)
        dispatched.append(step.runs - before)

    batcher._step_once = counted
    batcher.drive()
    assert dispatched and min(dispatched) >= 1
    for (prompt, m), future in zip(jobs, futures):
        assert list(future.result()) == plain_chain(prompt, m)
    metrics = batcher.metrics()
    assert metrics["decode_tokens"] == sum(m for _, m in jobs)
    assert metrics["spec_steps"] <= step.runs
    assert (metrics["decode_steps_ahead_total"] > 0) == ahead
    if not ahead:
        assert metrics["spec_steps"] == step.runs
    if quality == "always" and not ahead:
        assert metrics["spec_accepted"] == metrics["spec_drafted"] > 0
    if quality == "never":
        assert metrics["spec_accepted"] == 0 < metrics["spec_drafted"]


def test_a_prompt_of_one_token_has_no_draft_to_verify():
    """No chunk ran, so nothing drafted the first step's second lane: it is
    a pad lane, the step yields one token and drafts the next."""
    step, batcher = _batcher(QUALITY["always"])
    future = batcher.submit([5], max_new_tokens=5)
    batcher.drive()
    assert list(future.result()) == plain_chain([5], 5)
    m = batcher.metrics()
    assert step.runs == 3 and m["spec_drafted"] == m["spec_accepted"] == 2
    assert m["decode_steps_ahead_total"] == 0     # the spec names no feeds


def test_the_accept_rule_is_one():
    """``speculative=`` and a spec's self-draft emit by the same rule."""
    # one committed lane, three drafts, the second wrong
    assert greedy_chain([7, 1, 9, 3], 1, [1, 2, 3, 4], 10, None) == [1, 2]
    assert greedy_chain([7, 1, 2, 3], 1, [1, 2, 3, 4], 10, None) == [
        1, 2, 3, 4]
    assert greedy_chain([7, 1, 2, 3], 1, [1, 2, 3, 4], 3, None) == [1, 2, 3]
    assert greedy_chain([7, 1, 2, 3], 1, [1, 2, 3, 4], 10, 2) == [1, 2]
    assert greedy_chain([7, 1], 1, [1, 2], 1, None) == [1]
    assert greedy_chain([7], 1, [1, 2], 10, None) == [1]
    # three committed lanes: the chain starts after the last of them
    assert greedy_chain([7, 8, 9, 4], 3, [0, 0, 4, 5], 10, None) == [4, 5]


def test_a_step_that_drafts_is_turned_on_by_the_spec_alone():
    step = FakeStep(QUALITY["always"])
    with pytest.raises(ValueError, match="chunk program"):
        DecodeBatcher(step, STEP_SPEC, ladder=(2,), ctx_ladder=(64,),
                      start=False)
    with pytest.raises(ValueError, match="states no self_draft"):
        DecodeBatcher(step, STEP_SPEC, ladder=(2,), ctx_ladder=(64,),
                      start=False, prefill={
                          "predictor": FakeChunk(QUALITY["always"]),
                          "spec": {k: v for k, v in CHUNK_SPEC.items()
                                   if k != "self_draft"}, "ladder": (4,)})
    with pytest.raises(ValueError, match="one draft a row"):
        DecodeBatcher(step, dict(STEP_SPEC, self_draft=dict(
            STEP_SPEC["self_draft"], lanes=3)), ladder=(2,),
            ctx_ladder=(64,), start=False)
