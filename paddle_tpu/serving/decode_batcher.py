"""Continuous batching for autoregressive decode: a slot-recycled
scheduler over a KV-cached one-token step program.

The one-shot engine (``engine.py``) serves whole requests: a batch rides
until its LONGEST member finishes, so one 512-token generation stalls
every 8-token request batched with it. This scheduler makes the decode
STEP the scheduling quantum instead (the established continuous-batching
design the reference, whose serving story ends at
``AnalysisPredictor::Clone``, has no analog for):

  * a **slot table** of ``bucket_batch`` rows, each row one in-flight
    request with its own fill level ``pos`` into fixed-capacity per-slot
    cache tensors ([B, C, *tail] each, carried between steps as
    device-resident fetch->feed state — never a host round trip, and
    HANDED OVER to every step and chunk run: the batcher names them as
    ``donate_feeds`` of ``predictor.run``, so the executable writes a
    token's row into the buffer it was given and hands that buffer back
    as the fetch, where an executable that may not touch its feed copies
    the whole array first; the batcher keeps only what comes back, and a
    predictor whose ``run`` takes no ``donate_feeds`` is run as before).
    The spec's ``cache_feeds`` name them one by one with their own tails
    and types: a key and a value of one width a layer, or ONE latent row
    a layer, with an index-key cache beside it on the layers that have an
    indexer and none on the others; the scheduler never pairs them. A
    cache feed may state a ``capacity`` of its own: a RING of that many
    positions a row whatever the context rung (a window layer's keys and
    values; the program writes position p at slot ``p % capacity``), which
    the scheduler allocates, warms, stages, re-buckets, gathers and
    scatters at that capacity beside the caches that hold the rung. Or it
    may state a ``stride``: a cache that FOLLOWS the rung, ``rung /
    stride`` entries a row (one entry for every ``stride`` positions, written
    at ``p // stride``: a summary of the positions, which the program
    derives from its other caches), allocated, re-bucketed, gathered and
    scattered at that length (a chunk run's lanes ``[start, start + K)``
    land on the entries ``start // stride`` on). What cannot hold with
    either is refused at construction: a prefix cache (a prefix's rows are
    gone once the ring wraps, and an entry would need every derived cache
    at its own length) and speculation (a rejected draft's writes into a
    ring or into a derived cache cannot be rewound);
  * one compiled step per ``(bucket_batch, bucket_ctx)`` on the pow2
    ladders (``buckets.py``), so the XLA compile cache stays bounded at
    ``len(ladder) * len(ctx_ladder)`` executables;
  * **slot recycling**: new requests are admitted into free slots BETWEEN
    steps and finished sequences retire immediately — a long generation
    never blocks short co-riders, it just keeps its one slot;
  * **re-bucketing** when occupancy crosses a ladder boundary: the slot
    table compacts/grows and caches are copied row-wise into the new
    geometry (rare, host-side, O(B*C*D));
  * prompt tokens are ingested through the same step function (one forced
    token per step) — by default no separate prefill executable, so the
    compile cache bound holds and a long prompt shares steps with
    everyone else.

Three optional fast paths ride on top (ISSUE 20), all off unless
configured, and a fourth that a decode spec turns on:

  * **prefix cache** (``prefix_cache=``): a hash-trie over token-id
    prefixes (``prefix_cache.PrefixCache``) maps shared prompt prefixes
    to the KV rows the slot table already computed for them; ``submit``
    matches the longest cached prefix, admission CLONES the rows into
    the new slot (one device-side copy) and the slot starts at
    ``pos = prefix_len`` — a request whose prefix is cached skips that
    many step dispatches of TTFT. Entries are harvested when a prompt
    finishes ingesting, LRU-evicted under byte/entry budgets, and
    ref-counted against pending admissions; since live slots hold
    CLONES, eviction can never corrupt an in-flight request.
  * **chunked prefill** (``prefill=``): a K-token chunk program
    (``transformer_lm_chunk``) ingests K prompt tokens per dispatch on
    its own pow2 prefill ladder, interleaved chunk-by-chunk with decode
    steps (when some live rows generate and so cannot ride a chunk, the
    scheduler alternates chunk/step ticks) so a long prompt neither pays
    step-per-token TTFT nor stalls its co-riders. A chunk run computes
    the rows that INGEST, not the slot table: each rung ``k`` has ONE
    height, ``chunk_rows(k, b)`` (the power of two whose lanes stay
    inside ``CHUNK_TOKEN_BUDGET``, at most the bucket), its executable is
    made for ``[rows, k]`` tokens and ``[rows, c, *tail]`` caches, and
    where that is under the bucket the loop gathers the oldest ingesting
    rows' caches into a sub-batch on the device
    (``serve_rows_gather``), hands the chunk run those, and scatters the
    lanes it wrote back into the table in place
    (``serve_rows_scatter``, the table handed over); rows the sub-batch
    has no room for keep their place and ride the next chunk tick,
    oldest admission first. Where the height is the bucket the chunk
    runs over the table itself, and a batcher that verifies drafts keeps
    every rung at the bucket (a verifying tick reads every live row's
    logits). The compile cache gains one executable per (batch rung,
    ctx rung, prefill rung) — proved by
    ``analysis.resources.decode_cache_verdict`` via
    :meth:`DecodeBatcher.compile_cache_bound` — and the two small copies
    beside each sub-batched one.
  * **speculative decode** (``speculative=``): a small draft LM proposes
    k-1 tokens per generating row; ONE pass of the chunk program scores
    all k positions (the weight-sharing family makes the verifier free)
    and each row accepts greedily — a draft token is emitted only while
    it equals the target model's own argmax at that position, then the
    verifier's next argmax is emitted as the bonus token. Rejected
    drafts' cache writes are simply rewound (rows past a slot's fill
    level are unreachable by the attention mask — the same property
    slot recycling rests on). Greedy accept therefore emits exactly the
    target model's greedy chain: output is identical to plain decode
    (pinned bitwise on CPU by ``tests/test_serving.py``), regardless of
    how bad the draft is — draft quality only moves throughput.

  * **a step program that drafts for itself** (no option: the decode spec
    states it, ``spec["self_draft"]``): the step takes two lanes a row, the
    committed token and the draft of the next at positions ``p``, ``p +
    1``, runs the target model over both, chooses the greedy token after
    each and judges the draft INSIDE the executable, runs its own proposer
    (a multi-token-prediction module with a cache of its own among the
    spec's ``cache_feeds``) over the lanes, and returns ``[b, 4]`` int32 a
    row: how many tokens it yields (1 or 2), the two tokens, the next
    draft. The loop advances a row by what came back; ``max_new`` and an
    eos id cut a pair short; ``decode_tokens`` counts tokens delivered,
    never lanes. The chunk program ingests prompts as ever, the proposer's
    layer with them (its token feed is ``[rows, k + 1]``: a lane's own
    token and the one after it), and fetches the draft a row's first step
    verifies; a row that still ingests rides no step (a forced lane would
    write the proposer's cache with a guess). A lane that did not stand
    leaves a row in the caches at the position the row's next step writes
    first: the property speculation's rewind rests on. ONE accept rule,
    :func:`greedy_chain`, serves this and ``speculative=``, so here too the
    output is the plain greedy chain whatever is drafted. A spec with a
    ring refuses it as it refuses ``speculative=``, and a spec that drafts
    refuses a second proposer. Where the spec names ``next_token_fetch`` /
    ``next_pos_fetch`` (the step to come's two feeds, made on the device)
    the loop runs one step ahead as below; the one more thing it learns
    late is a yield of two: a row with two tokens of room rides the step
    ahead where nobody waits for its slot (its lane there is dropped if it
    ended), and is read first where somebody does; and a run's end is
    settled one step early on the same terms as below, a row counted as
    near its end while it MAY be (by the tokens its unread steps may yet
    yield), so that no quantum is left a read alone.

**Exact-parity guarantee.** Every op in a step program is strictly
per-row (``cached_attention`` masks each row to its own fill level;
``kv_cache_write`` writes only the row's own slot; matmul/layernorm
reduce over feature axes only). A dead or stranger row therefore cannot
perturb a live row: at a fixed (bucket_batch, bucket_ctx) geometry,
batched-with-strangers output is BITWISE-identical to solo decode —
``tests/test_serving.py`` pins this for greedy (here) and beam (the
one-shot path). Across different bucket geometries the math is identical
per row but runs in different executables, so parity there is
floating-point-deterministic, not contractual. A chunk's sub-batch is
another geometry in that sense: a row's lanes see the same cache row and
the same positions as they would in the slot table, computed by the
executable of ``[rows, k]`` and not of ``[b, k]``.

Sampling is greedy argmax, per row and deterministic, and it is made
INSIDE the step executable: the loop asks its step predictor for one more
fetch, the ``argmax`` of the logits fetch (``ProgramPredictor.
fetch_argmax``, appended to the program before its first compile, so a
geometry still has ONE step executable and a step ONE dispatch), and reads
``[b]`` int32 ids where it would copy ``[b, vocabulary]`` float32 logits
to the host; ``jnp.argmax`` and ``np.argmax`` both take the first maximum
of the same float32 values, so the tokens are the same bit for bit. Eos
and length control flow stay on the host. A predictor that cannot be asked
(an exported computation, a wrapper, a test fake) is served from its
logits, ``np.argmax`` a live row, as every predictor was before; the loop
decides by what it finds (``fetch_argmax``, and a ``run`` that takes the
caches' hand-over: one that does not is a wrapper, which may rewrite the
logits it passes on), no option. A chunk that samples (speculation) reads
its logits on the host.

**The loop runs one step ahead.** The next step's one data dependence on
this one, beside the carried caches, is the sampled id; every other input
(positions, which rows are live, which end by ``max_new``) the host knows
before the step has run. So after dispatching step n the loop dispatches
step n+1 at once, step n's id array (on the device, unread) its token
feed and the positions one on, and only then reads step n's ids, delivers
the tokens and retires what is done: the device runs n+1 while the host
does that. Depth one, never more; and only where the quantum to come is
again a plain step over the same slot table (:meth:`DecodeBatcher.
_rows_after`): no row ingests (a chunk or a forced prompt token is the
host's), no row awaits the prefix cache's harvest, speculation is off, no
re-bucketing is due, and no request waits that a slot free after step n
could take (rows that end by ``max_new`` at step n are known now); then
the loop reads first and admits, as it always did. While a step is in
flight nobody is admitted (``_admit`` returns at once), so a request that
arrives in the middle of a run waits for the step running and the one
queued, and no more: the quantum that finds it waiting only reads. The end
of a run that can be foreseen is settled one step early, from the same
knowledge: a step dispatched ahead that will have no follower is read in
the quantum that dispatched it, after the step before it. The one thing
the host learns late is a row that ends on an end-of-sequence id: it
rode step n+1 as a live lane, its output there is dropped, and its cache
write landed at its own row's next position (modulo its own row's ring),
which a later occupant of the slot writes before its attention reaches it:
the property slot recycling rests on. ``drive(max_steps)``, ``shutdown``
and a step that raises leave no step unread.

**The loop accounts for its own quantum** (``obs.trace.span``: the tracer
and, under a ``jax.profiler`` trace, ``paddle_tpu.<name>`` on its host
plane; the falsy no-op with neither). Between two quanta ``decode.idle``
(the loop's wait while no slot is live and nothing is queued),
``decode.admit`` and ``decode.plan`` (no plan before a step that is in
flight already); a quantum is ``decode.step`` over ``decode.feed``,
``executor.run``, ``decode.fetch`` (the wait for the device and the ids'
way to the host) and ``decode.sample``, or ``prefill.chunk`` /
``spec.verify`` over ``decode.feed`` and ``executor.run`` (a chunk's span
ends at its dispatch; one that samples also fetches). ONE ``decode.step``
span a step, ending with the read of exactly one step: in a run of steps a
span's feed and ``executor.run`` are step n+1's and its fetch and sample
are step n's; the first span of a run holds two dispatches; and the run's
last step, read in the quantum that dispatched it, has a span of its own
that opens before that quantum's and closes after it (it holds that span,
then its own fetch and sample). So every span lies over a dispatch, but
one kind: a run cut short by what the host could not foresee (an arrival
there is a slot for) ends with a span of a fetch and a sample alone. The
spans' period is still the loop's period a step. No span waits for the
device on its own account. README, Observability, lists their tags and
the counters beside them.
"""

import contextlib
import functools
import inspect
import threading
import time
from collections import deque, namedtuple
from concurrent.futures import Future

import numpy as np

from ..obs import trace
from .admission import AdmissionController, DeadlineExceededError
from .buckets import bucket_for, pow2_ladder
from .engine import EngineShutdownError
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache

__all__ = ["DecodeBatcher", "DecodeRequest", "DraftLM", "save_decode_spec",
           "load_decode_spec", "default_ctx_ladder",
           "default_prefill_ladder", "chunk_rows"]

DECODE_SPEC_FILE = "decode_spec.json"


def default_ctx_ladder(spec):
    """The ctx-capacity rung ladder a decode spec gets when the caller
    passes none: pow2 rungs up to the spec's cache capacity, floored at
    16. THE single derivation — ``DecodeBatcher.__init__`` and
    ``ServingEngine``'s build-time compile-cache verdict both call it,
    so the proved executable bound can never desynchronize from the
    ladder the batcher actually compiles."""
    cap = int(spec.get("ctx_cap", 256) or 256)
    return tuple(r for r in pow2_ladder(cap) if r >= 16) or (cap,)


def default_prefill_ladder(spec):
    """The chunk-length rung ladder a prefill/verify chunk program gets
    when the caller passes none: pow2 rungs from 4 up to half the cache
    capacity (a chunk near the full capacity would serve exactly one
    prompt shape — not worth an executable). Shared by
    ``DecodeBatcher.__init__`` and the engine's build-time verdict, same
    single-derivation rule as :func:`default_ctx_ladder`."""
    cap = int(spec.get("ctx_cap", 256) or 256)
    top = min(cap, max(4, cap // 2))
    return tuple(r for r in pow2_ladder(top) if r >= 4) or (min(4, cap),)


# The token lanes one chunk run computes, at most: rows x rung; one value for
# every geometry. Settled by a sweep of 256 / 512 / 1024 / 2048 on the chip in
# the three serving cells (PERF.md section 6, PR 38): the one value under
# which every cell gains. A backlog of short prompts, where one row ingests
# at a time, wants it smaller still; long prompts several rows at a time
# want 1024, because a decode step runs between two chunk ticks whatever
# they ingest.
CHUNK_TOKEN_BUDGET = 512


def chunk_rows(k, b):
    """The rows a chunk run of rung ``k`` computes in a bucket of ``b`` slot
    rows: the largest power of two whose lanes stay inside
    ``CHUNK_TOKEN_BUDGET``, at least one row and at most the bucket. ONE
    height a rung, so the chunk executables stay one a ``(b, c, k)``: the
    plan, the feeds, the staging and the warm-up all ask here. Where it
    gives ``b`` the chunk runs over the slot table itself, as it did before
    there were sub-batches."""
    rows = max(1, CHUNK_TOKEN_BUDGET // int(k))
    return min(1 << (rows.bit_length() - 1), int(b))


@functools.lru_cache(maxsize=None)
def _rows_helpers(lanes, whole=frozenset(), strided=frozenset()):
    """The two jitted copies round a sub-batched chunk run (the jit names
    are what a device trace shows), each a loop over the ``n`` sub-rows
    that hold a slot row, so a pad sub-row costs nothing:
    ``serve_rows_gather(table, idx, n)`` takes the slot table's caches
    ``{name: [b, c, *tail]}`` to the sub-batch's ``[len(idx), c, *tail]``,
    sub-row ``j < n`` a copy of table row ``idx[j]`` and the pad sub-rows
    zeros (every lane of theirs is a pad lane, so nothing of them is
    kept); ``serve_rows_scatter(table, sub, idx, start, n)``, the TABLE
    donated, writes the lanes ``[start[j], start[j] + lanes)`` of sub-row
    ``j < n`` back into row ``idx[j]`` in place: the lanes its chunk
    wrote, slid down where they would pass the capacity (a lane the run
    left alone holds what the gather read, so writing it back changes
    nothing). Whole rows written back measured 2.5-10.3 ms for OPT-1.3B's
    48 caches where the lanes measure 2.0-2.6 (PERF.md section 6, PR 38).
    The caches named in ``whole`` are rings: a chunk's lanes land at their
    positions modulo the ring, not at ``[start, start + lanes)``, and a
    ring row is small, so the whole row goes back. ``strided``: (name,
    stride) of the caches that hold one entry for every ``stride``
    positions; the lanes land on the entries ``start // stride .. (start +
    lanes - 1) // stride``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def serve_rows_gather(table, idx, n):
        def copy_row(j, sub):
            return {name: lax.dynamic_update_slice_in_dim(
                sub[name], lax.dynamic_slice_in_dim(a, idx[j], 1, axis=0),
                j, axis=0) for name, a in table.items()}

        return lax.fori_loop(0, n, copy_row, {
            name: jnp.zeros(idx.shape + a.shape[1:], a.dtype)
            for name, a in table.items()})

    strides = dict(strided)

    def serve_rows_scatter(table, sub, idx, start, n):
        def write_row(j, table):
            out = {}
            for name, a in table.items():
                stride = strides.get(name, 1)
                # lanes that start anywhere touch one entry more than
                # their share
                span = lanes if stride == 1 else -(-lanes // stride) + 1
                width = a.shape[1] if name in whole \
                    else min(span, a.shape[1])
                at = jnp.clip(start[j] // stride, 0, a.shape[1] - width)
                rest = (0,) * (a.ndim - 2)
                out[name] = lax.dynamic_update_slice(
                    a, lax.dynamic_slice(sub[name], (j, at) + rest,
                                         (1, width) + a.shape[2:]),
                    (idx[j], at) + rest)
            return out

        return lax.fori_loop(0, n, write_row, table)

    return (jax.jit(serve_rows_gather),
            jax.jit(serve_rows_scatter, donate_argnums=(0,)))


class _Carrying:
    """A step or chunk predictor with the caches it carries: their feed
    names in the order of the fetches that carry them on, which is the
    order they are handed over in, so that jit pairs each cache with its
    own fetch. ``hands_over``: whether ``predictor.run`` has the
    ``donate_feeds`` argument (``ProgramPredictor`` and ``Predictor`` do;
    an exported-computation predictor, a wrapper or a test fake that
    lacks it is fed as ever, and its cache writes copy)."""

    def __init__(self, predictor, carried):
        """``carried``: (feed name, index of its fetch) a cache."""
        self.predictor = predictor
        self.order = tuple(name for name, _idx in
                           sorted(carried, key=lambda c: c[1]))
        try:
            self.hands_over = "donate_feeds" in inspect.signature(
                predictor.run).parameters
        except (TypeError, ValueError):
            self.hands_over = False

    def stage(self, feed, caches):
        """Make the executable that :meth:`run` with such arguments would
        use, and run nothing: ``caches`` may hold shapes in the arrays'
        places. A predictor that takes no hand-over or has no ``stage`` is
        left to its first run; so is one whose staging raises (the run will
        raise it again, where the loop answers for it)."""
        stage = getattr(self.predictor, "stage", None)
        if stage is None or not self.hands_over:
            return
        try:
            stage({**feed, **caches}, donate_feeds=self.order)
        except Exception:  # noqa: BLE001: the run itself will raise it
            pass

    def run(self, feed):
        """One run over ``feed``, the quantum's own arrays with the carried
        caches merged in. Where the predictor takes that, the caches are
        handed over for good: the executable may then write into them in
        place, and after the call, failed or not, they must not be read
        again — only what the run returns (outputs in fetch order)."""
        if not self.hands_over:
            return self.predictor.run(feed, return_numpy=False)
        return self.predictor.run(feed, return_numpy=False,
                                  donate_feeds=self.order)


class DraftLM:
    """Greedy draft proposer over a small FULL ``transformer_lm``
    program: no KV cache of its own — each draft token is one pass of
    the full causal program over the row's (window of) token history,
    taking the argmax at the last real position. Deliberately simple:
    the speculative accept rule guarantees output parity with plain
    decode for ANY proposer, so the draft model only has to be cheap
    and usually-right, not exact.

    ``predictor``: ``run``/``fetch_names`` over a full-program build
    (``transformer_lm``; fetch the ``logits`` extra). ``seq_len``: the
    program's sequence length — longer histories are drafted from their
    last ``seq_len`` tokens (a sliding window; quality detail only).
    ``ladder``: pow2 batch rungs the draft batch is padded to, bounding
    the draft program's own compile cache."""

    def __init__(self, predictor, logits_fetch, seq_len, ids_feed="ids",
                 lbl_feed="lbl", ladder=(1, 2, 4, 8)):
        self._pred = predictor
        self._logits_idx = list(predictor.fetch_names).index(logits_fetch)
        self.seq_len = int(seq_len)
        self.ids_feed = ids_feed
        self.lbl_feed = lbl_feed
        self.ladder = tuple(sorted(set(ladder)))

    def propose(self, histories, n):
        """``n`` greedy continuations for each token history. Returns a
        list of n-token lists, one per history."""
        hists = [list(h) for h in histories]
        out = [[] for _ in histories]
        for _ in range(int(n)):
            b = bucket_for(len(hists), self.ladder) \
                if len(hists) <= max(self.ladder) else len(hists)
            ids = np.zeros((b, self.seq_len), np.int64)
            lens = []
            for j, h in enumerate(hists):
                t = h[-self.seq_len:]
                ids[j, :len(t)] = t
                lens.append(len(t))
            feed = {self.ids_feed: ids}
            if self.lbl_feed:
                feed[self.lbl_feed] = ids
            outs = self._pred.run(feed, return_numpy=False)
            logits = np.asarray(outs[self._logits_idx])
            for j, fill in enumerate(lens):
                t = int(np.argmax(logits[j, fill - 1]))
                out[j].append(t)
                hists[j].append(t)
        return out


def save_decode_spec(dirname, spec):
    """Write a step builder's decode-spec dict next to a
    ``save_inference_model`` export, so ``ServingEngine(dir, decode=True)``
    can serve continuous-batching decode straight from the directory."""
    import json
    import os

    path = os.path.join(dirname, DECODE_SPEC_FILE)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return path


def load_decode_spec(dirname):
    import json
    import os

    with open(os.path.join(dirname, DECODE_SPEC_FILE)) as f:
        return json.load(f)


class DecodeRequest:
    """One decode request: ``prompt`` (1-D int token ids, non-empty),
    ``max_new_tokens``, optional ``eos_id`` (stop token, also emitted),
    the caller's future (resolves to the generated ids as int64 ndarray),
    and the admission timestamps the TTFT/TPOT metrics read."""

    __slots__ = ("prompt", "max_new", "eos_id", "future", "enqueue_t",
                 "deadline", "n_ctx", "prefix", "order")

    def __init__(self, prompt, max_new, eos_id, future, enqueue_t,
                 deadline=None, prefix=None):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.future = future
        self.enqueue_t = enqueue_t
        self.deadline = deadline
        # pinned PrefixEntry matched at submit (cloned + released at
        # admission), or None
        self.prefix = prefix
        # its place among the admissions (set when a slot takes it): a
        # sub-batched chunk takes the oldest ingesting rows first
        self.order = 0
        # cache capacity this request needs: every prompt token is written
        # once, then at most max_new-1 generated tokens are fed back (the
        # last sampled token never re-enters the cache), so the highest
        # index written is prompt+max_new-2
        self.n_ctx = len(self.prompt) + self.max_new - 1

    def __repr__(self):
        return ("DecodeRequest(prompt=%d toks, max_new=%d)"
                % (len(self.prompt), self.max_new))


class _Slot:
    """One occupied slot-table row."""

    __slots__ = ("req", "pos", "k", "out", "next_token", "first_tok_t",
                 "harvested", "draft")

    def __init__(self, req):
        self.req = req
        # a matched prefix starts the slot past its cloned rows: the
        # first m tokens are already in the cache, so ingestion resumes
        # at prompt[m] (m <= len(prompt)-1 — the last token always feeds
        # through the model to produce first-generation logits)
        m = req.prefix.length if req.prefix is not None else 0
        self.pos = m            # next cache index == tokens ingested
        self.k = m + 1          # prompt cursor: prompt[m] feeds first
        self.out = []           # generated ids
        self.next_token = req.prompt[m]
        self.first_tok_t = None
        self.harvested = False  # this prompt's rows offered to the cache
        # a self-drafting loop: the draft of the token AFTER ``next_token``,
        # an int, or ``(array on the device, row)`` not read yet (a chunk
        # run's), or None where nothing has drafted it
        self.draft = None

    @property
    def forcing(self):
        """Still ingesting prompt tokens (logits ignored)."""
        return self.k < len(self.req.prompt)


# A step dispatched and not read yet: what the run returned (``outs``, device
# arrays in fetch order), the rows that rode it as live lanes, each ``(slot
# row, its _Slot, the position it was fed at)``, and whether it was
# dispatched ``ahead`` of the read of the step before.
# ``drafts``: in a self-drafting loop, {slot row: the draft its lane 1 was fed,
# or None where the lane was a pad lane}.
_Flight = namedtuple("_Flight", "outs rows ahead drafts", defaults=(None,))


def greedy_chain(fed, forced, greedy, room, eos_id):
    """THE accept rule, of ``speculative=`` (a proposer over histories, the
    chunk program the verifier) and of a spec's ``self_draft`` (a proposer
    inside the step) alike. ``fed``: the tokens of a row's lanes, the first
    ``forced`` committed and the rest drafts; ``greedy[j]``: the model's own
    best token after lane j. Returns the tokens the row emits: the model's
    token after the last committed lane, then after each draft for as long
    as that draft IS the token just emitted, at most ``room`` of them and
    none past ``eos_id``. So what is emitted is the model's own greedy
    chain whatever was drafted; a draft only decides how far one run gets
    along it."""
    j = forced - 1
    emitted = [int(greedy[j])]
    while (j + 1 < len(fed) and len(emitted) < room
           and (eos_id is None or emitted[-1] != eos_id)
           and int(fed[j + 1]) == emitted[-1]):
        j += 1
        emitted.append(int(greedy[j]))
    return emitted


class DecodeBatcher:
    """The continuous batcher. Same client surface as ``ServingEngine``
    (``submit``/``predict``/``metrics``/``warmup``/``shutdown``) so the
    engine can put it behind one API.

    ``predictor``: anything with ``run(feed, return_numpy=False) -> list``
    in fetch order and ``fetch_names`` — a ``Predictor`` over a saved
    step-program dir, an in-process ``ProgramPredictor``, or a test fake.
    ``spec``: the decode-spec dict a step builder returns
    (``models.transformer.transformer_lm_step``): token/pos feed names,
    logits fetch, cache feed/fetch pairs each with its own tail shape
    and dtype (any number a layer) and optionally a ``capacity`` (a ring of
    that many positions a row) or a ``stride`` (``rung / stride`` entries a
    row, one for every ``stride`` positions; neither: the context rung), and
    optionally
    ``counter_fetch`` /
    ``counters``: one small int vector the step program counts of itself
    and the names of its entries, added after every step to the metrics'
    ``program_<name>`` counters.

    ``start=False`` skips the loop thread: tests then call
    :meth:`drive` to run the scheduler synchronously — fully
    deterministic, zero sleeps (the injectable-``clock`` contract the
    rest of the serving tier follows)."""

    def __init__(self, predictor, spec, ladder=None, ctx_ladder=None,
                 max_batch_size=8, max_queue_depth=256,
                 default_timeout_s=None, default_max_new_tokens=64,
                 eos_id=None, clock=None, metrics=None, start=True,
                 prefix_cache=None, prefill=None, speculative=None):
        self._spec = dict(spec)
        self._tok_feed = self._spec["token_feed"]
        self._pos_feed = self._spec["pos_feed"]
        fetch_names = list(predictor.fetch_names)
        # a spec may state that its step program drafts for itself
        # (``self_draft``): two lanes a row, the committed token and the
        # draft of the next; the greedy tokens, the accept rule and the next
        # draft inside the executable; ONE small fetch, ``yield_fetch``
        # [b, 4] int32 (tokens yielded, the two tokens, the next draft). Such
        # a program hands no logits over. The loop turns it on from the
        # spec alone
        self._self = None
        stated = self._spec.get("self_draft")
        if stated:
            if int(stated.get("lanes", 2)) != 2:
                raise ValueError("self_draft with %r lanes: the loop "
                                 "verifies one draft a row" % stated["lanes"])
            self._self = {"yield_idx": fetch_names.index(
                stated["yield_fetch"])}
            # where the program also makes the step to come's two feeds
            # (each row's last token that stands with the next draft, and
            # the positions after those that stand), the loop can feed them
            # unread and stay one step ahead
            if stated.get("next_token_fetch") and stated.get(
                    "next_pos_fetch"):
                self._self["carried"] = (
                    fetch_names.index(stated["next_token_fetch"]),
                    fetch_names.index(stated["next_pos_fetch"]))
        self._logits_idx = (None if self._self else fetch_names.index(
            self._spec["logits_fetch"]))
        # optional: ONE small int vector the step program counts of itself
        # (``counter_fetch``), its entries named by ``counters``; read
        # after a step's logits and added to the engine's metrics
        self._counter_names = tuple(self._spec.get("counters") or ())
        self._counter_idx = (
            fetch_names.index(self._spec["counter_fetch"])
            if self._counter_names else None)
        # (feed, fetch index, tail, dtype, capacity, stride): capacity None
        # where the cache follows the context rung, a ring's own where it
        # states one; stride 1 where it holds the rung, the positions an
        # entry stands for where it states one
        self._cache_feeds = []
        for cf in self._spec["cache_feeds"]:
            cap, stride = cf.get("capacity"), cf.get("stride")
            for what, stated in (("capacity", cap), ("stride", stride)):
                if stated is not None and int(stated) < 1:
                    raise ValueError("cache feed %r states a %s of %r"
                                     % (cf["feed"], what, stated))
            if cap is not None and stride is not None:
                raise ValueError("cache feed %r states a capacity and a "
                                 "stride: a ring does not follow the rung"
                                 % cf["feed"])
            self._cache_feeds.append(
                (cf["feed"], fetch_names.index(cf["fetch"]),
                 tuple(cf["tail"]), np.dtype(cf.get("dtype", "float32")),
                 None if cap is None else int(cap), int(stride or 1)))
        self._rings = frozenset(cf[0] for cf in self._cache_feeds
                                if cf[4] is not None)
        self._strided = frozenset((cf[0], cf[5]) for cf in self._cache_feeds
                                  if cf[5] > 1)
        self._step = _Carrying(predictor,
                               [cf[:2] for cf in self._cache_feeds])
        # the greedy choice inside the step executable: a predictor that
        # can be asked (``ProgramPredictor.fetch_argmax``) returns ``[b]``
        # ids as one more fetch, and the loop reads those; one that cannot
        # (an exported computation, a test fake), or whose ``run`` takes no
        # hand-over (a wrapper round it, which may as well rewrite the
        # logits it passes on: ``tests/benchmark/test_serve_cell.py`` breaks
        # a step so), is served from its logits
        self._ids_idx = None
        ask = getattr(predictor, "fetch_argmax", None)
        if ask is not None and self._step.hands_over and not self._self:
            ids_fetch = ask(self._spec["logits_fetch"])
            self._ids_idx = list(predictor.fetch_names).index(ids_fetch)
        self._flight = None  # the step dispatched and not read yet
        # a self-drafting loop, where somebody set a list here: every
        # verifying step appends ``(request, position of lane 0, the draft
        # lane 1 was fed or None, the step's [4] row)``, so that the drafts
        # the steps made can be held against a reference after the run
        # (``benchmark/serve_drafts.py``); the served tokens do not depend
        # on them, so nothing else can see them
        self.draft_log = None
        self.ladder = tuple(sorted(set(
            ladder if ladder is not None else pow2_ladder(max_batch_size))))
        if ctx_ladder is None:
            ctx_ladder = default_ctx_ladder(self._spec)
        self.ctx_ladder = tuple(sorted(set(int(c) for c in ctx_ladder)))
        for feed, stride in sorted(self._strided):
            odd = [c for c in self.ctx_ladder if c % stride]
            if odd:
                raise ValueError(
                    "cache %r holds one entry for every %d positions: the "
                    "context rung %d is no multiple of that" % (
                        feed, stride, odd[0]))
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.default_timeout_s = default_timeout_s
        self.eos_id = eos_id
        self._clock = clock or time.monotonic
        self._admission = AdmissionController(max_queue_depth)
        if metrics is not None:
            # shared instance (the engine's): the OWNER binds aggregate
            # gauges across every batcher — binding here would leave the
            # gauges reading whichever replica bound last
            self.metrics_ = metrics
        else:
            self.metrics_ = ServingMetrics()
            self.metrics_.bind_gauges(lambda: len(self._pending),
                                      lambda: self._admission.in_flight)

        # -- prefix cache (optional): True / kwargs dict builds an owned
        # instance; a PrefixCache instance is shared (the engine's)
        self.prefix_cache = None
        # NOT a truthiness test: an EMPTY PrefixCache is len()==0/falsy
        if prefix_cache is not None and prefix_cache is not False:
            self._refuse_with_rings(
                "prefix_cache=", "a prefix's rows are gone from a ring "
                "once it wraps, so they cannot be cloned into another slot")
            if isinstance(prefix_cache, PrefixCache):
                self.prefix_cache = prefix_cache
            else:
                kw = (dict(prefix_cache) if isinstance(prefix_cache, dict)
                      else {})
                kw.setdefault("metrics", self.metrics_)
                self.prefix_cache = PrefixCache(**kw)
                if metrics is None:
                    self.metrics_.bind_prefix_bytes(
                        lambda: self.prefix_cache.nbytes)

        # -- chunked prefill / speculative verify (optional): the chunk
        # program must share the step program's cache feed names so the
        # carried cache dict feeds both
        self._prefill = None
        self.prefill_ladder = ()
        # what a pad lane of a chunk carries for a position: the context
        # rung, so that its cache writes drop; a chunk program with a ring
        # must tell a pad lane from a live one (modulo the ring it would
        # land on a live slot) and states the one position it is given
        self._pad_pos = None
        if prefill is not None:
            p = dict(prefill)
            cpred = p["predictor"]
            cspec = dict(p["spec"])
            if cspec.get("pad_pos") is not None:
                self._pad_pos = int(cspec["pad_pos"])
                if self._pad_pos < max(self.ctx_ladder):
                    raise ValueError(
                        "the chunk program takes position %d for a pad "
                        "lane, inside the context rung %d" % (
                            self._pad_pos, max(self.ctx_ladder)))
            elif self._rings:
                raise ValueError(
                    "cache %r is a ring and the chunk program's spec "
                    "states no pad_pos: it could not tell a pad lane from "
                    "a live one" % min(self._rings))
            cfetch = list(cpred.fetch_names)
            step_feeds = {cf["feed"] for cf in self._spec["cache_feeds"]}
            cmap = []
            for cf in cspec["cache_feeds"]:
                if cf["feed"] not in step_feeds:
                    raise ValueError(
                        "chunk cache feed %r has no step-program "
                        "counterpart — the chunk program must share the "
                        "step's cache feed names" % cf["feed"])
                cmap.append((cf["feed"], cfetch.index(cf["fetch"])))
            if len(cmap) != len(step_feeds):
                raise ValueError(
                    "chunk program covers %d of the step program's %d "
                    "cache feeds" % (len(cmap), len(step_feeds)))
            pl = p.get("ladder")
            if pl is None:
                pl = default_prefill_ladder(self._spec)
            self.prefill_ladder = tuple(sorted(set(int(k) for k in pl)))
            self._prefill = {
                "pred": _Carrying(cpred, cmap), "tok": cspec["token_feed"],
                "pos": cspec["pos_feed"],
                # a chunk program that only ingests (no speculation reads
                # its logits) may leave the head, and the fetch, out
                "logits_idx": (cfetch.index(cspec["logits_fetch"])
                               if cspec.get("logits_fetch") else None),
                "cache_map": cmap}
            drafts = cspec.get("self_draft")
            if self._self is not None:
                if not drafts:
                    raise ValueError(
                        "the step program drafts for itself and the chunk "
                        "program's spec states no self_draft: the module's "
                        "cache would not hold the prompt")
                self._self["chunk_draft_idx"] = cfetch.index(
                    drafts["draft_fetch"])
                # token lanes its feed holds past the rung: each lane's
                # own token and, for the proposer, the one after it
                self._self["chunk_extra"] = int(
                    drafts.get("next_token_lane", 0))
        if self._self is not None:
            self._refuse_with_rings(
                "self_draft", "a draft that does not stand leaves a row in "
                "a ring that cannot be rewound")
            if self._prefill is None:
                raise ValueError("a self-drafting step program needs the "
                                 "chunk program (pass prefill= as well): "
                                 "prompts are ingested through it, the "
                                 "module's layer with them")
            if speculative is not None:
                raise ValueError("speculative= with a decode spec that "
                                 "states self_draft: one proposer a loop")
        self._alt_chunk = False
        self._ahead = {}  # signature -> the thread staging its executable
        self._rows_staged = {}  # chunk signature -> its two copies, compiled
        self._admissions = 0

        # -- speculative decode (optional, rides the chunk program)
        self._draft = None
        self._spec_k = 0
        if speculative is not None:
            self._refuse_with_rings(
                "speculative=", "a rejected draft's writes into a ring "
                "overwrite positions that cannot be rewound")
            if self._prefill is None:
                raise ValueError("speculative decode needs the chunk "
                                 "program (pass prefill= as well)")
            if self._prefill["logits_idx"] is None:
                raise ValueError("speculative decode reads the chunk "
                                 "program's logits; its spec names no "
                                 "logits_fetch")
            s = dict(speculative)
            self._draft = s["draft"]
            k = int(s.get("k", 4))
            if k < 2:
                raise ValueError("speculative k must be >= 2 "
                                 "(k-1 drafts + the committed token)")
            self._spec_k = k

        self._pending = deque()
        self._slots = []          # list[_Slot | None], len == bucket_batch
        self._caches = {}         # feed name -> [B, C, *tail] array
        self._cache_bytes = 0     # of them all: moves with the geometry
        self._bucket = (0, 0)     # (bucket_batch, bucket_ctx)
        self.seen_signatures = set()
        self._cv = threading.Condition()
        self._closed = False
        self._aborted = False
        self._thread = None
        if start:
            self._thread = threading.Thread(
                target=self._loop, name="paddle-tpu-decode", daemon=True)
            self._thread.start()

    def _refuse_with_rings(self, option, why):
        """``option`` needs caches that hold the context position by
        position: refuse it where a cache is a ring (``why``) or is derived
        from the others at a stride."""
        for feed, _idx, _tail, _dtype, cap, stride in self._cache_feeds:
            if cap is not None:
                raise ValueError(
                    "%s cannot be used with this decode spec: cache %r is "
                    "a ring of %d positions, and %s" % (option, feed, cap,
                                                        why))
            if stride > 1:
                raise ValueError(
                    "%s cannot be used with this decode spec: cache %r "
                    "holds one entry for every %d positions, derived from "
                    "the program's other caches: it can neither be cut at "
                    "a prefix's length nor rewound past a write" % (
                        option, feed, stride))

    @staticmethod
    def _entries(cap, stride, c):
        """The entries a row of a cache holds in a bucket of context ``c``:
        a ring's own capacity, else the rung at the cache's stride."""
        return cap or c // stride

    # -- client surface -----------------------------------------------------
    def now(self):
        return self._clock()

    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               timeout_s=None):
        """Enqueue one decode request; returns a Future resolving to the
        generated token ids (int64 ndarray, eos included when hit).
        Raises ``BucketError`` when prompt+max_new exceeds the top ctx
        rung, ``ServerOverloadedError`` when the bounded queue is full."""
        if self._closed:
            raise RuntimeError("DecodeBatcher is shut down")
        prompt = np.asarray(prompt).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must hold at least one token")
        max_new = (int(max_new_tokens) if max_new_tokens is not None
                   else self.default_max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos = eos_id if eos_id is not None else self.eos_id
        # matches DecodeRequest.n_ctx: the last sampled token never
        # re-enters the cache
        n_ctx = int(prompt.size) + max_new - 1
        bucket_for(n_ctx, self.ctx_ladder)  # validates at the door
        timeout_s = (timeout_s if timeout_s is not None
                     else self.default_timeout_s)
        now = self._clock()
        deadline = now + timeout_s if timeout_s is not None else None
        self._admission.acquire(1)
        prefix = None
        if self.prefix_cache is not None and prompt.size > 1:
            # match capped at len-1: the last prompt token must feed
            # through the step to produce first-generation logits
            prefix = self.prefix_cache.lookup(prompt,
                                              limit=int(prompt.size) - 1)
        req = DecodeRequest(prompt, max_new, eos, Future(), now,
                            deadline=deadline, prefix=prefix)
        with self._cv:
            if self._closed:
                self._admission.release(1)
                self._release_prefix(req)
                raise RuntimeError("DecodeBatcher is shut down")
            self._pending.append(req)
            self._cv.notify_all()
        return req.future

    def predict(self, prompt, max_new_tokens=None, eos_id=None,
                timeout_s=None):
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_id=eos_id,
                           timeout_s=timeout_s).result(timeout_s)

    def metrics(self):
        return self.metrics_.snapshot()

    def metrics_report(self):
        return self.metrics_.report()

    def compiled_shape_counts(self):
        """Distinct step ``(bucket_batch, bucket_ctx)`` and chunk
        ``(bucket_batch, bucket_ctx, chunk_rung)`` geometries dispatched
        — bounded at :meth:`compile_cache_bound` by construction."""
        return [len(self.seen_signatures)]

    def compile_records(self):
        """The compile records (``Executor.compile_records``) of the
        executables the step predictor and the chunk predictor made, in
        that order; a predictor that keeps no executor adds none. Each
        says whether the hand-over of the caches engages in it:
        ``memory["alias_bytes"]`` no less than ``donated_feed_bytes``."""
        carrying = [self._step]
        if self._prefill is not None:
            carrying.append(self._prefill["pred"])
        return [record for c in carrying for record in getattr(getattr(
            c.predictor, "_exe", None), "compile_records", ())]

    def compile_cache_bound(self):
        """The PROVED executable-count bound (ISSUE 15): the static
        compile-cache verdict from the decode spec — dispatched
        geometries (:meth:`compiled_shape_counts`) can never exceed it.
        With a chunk program attached the bound covers the prefill
        ladder too: ``len(ladder) * len(ctx_ladder) *
        (1 + len(prefill_ladder))``."""
        from ..analysis.resources import decode_cache_verdict

        bound, _result = decode_cache_verdict(
            self._spec, self.ladder, self.ctx_ladder,
            prefill_ladder=self.prefill_ladder)
        return bound

    def warmup(self):
        """Pre-compile every (batch rung, ctx rung) step geometry — and,
        when a chunk program rides along, every (batch, ctx, chunk rung)
        chunk geometry at its sub-batch height, with the two copies round
        a sub-batched run — with a zero-token synthetic dispatch, so live
        traffic never compiles. Returns the number of geometries
        warmed."""
        warmed = 0
        for b in self.ladder:
            for c in self.ctx_ladder:
                self._step.run({**self._synth_feed(b),
                                **self._synth_caches(b, c)})
                self.seen_signatures.add((b, c))
                warmed += 1
                if self._prefill is not None:
                    for k in self.prefill_ladder:
                        self._prefill["pred"].run(
                            {**self._synth_chunk_feed(b, c, k),
                             **self._synth_caches(
                                 self._chunk_height(b, k), c)})
                        self._rows_copies((b, c, k))
                        self.seen_signatures.add((b, c, k))
                        warmed += 1
        return warmed

    def drive(self, max_steps=None):
        """Run the scheduler loop synchronously on the CALLING thread
        until idle (or ``max_steps`` quanta: a quantum is a chunk run or a
        step read, two steps where a run of steps ends). Only valid with
        ``start=False`` — the deterministic test/bench mode. Returns the
        number of quanta executed. No step is left in flight."""
        if self._thread is not None:
            raise RuntimeError("drive() requires start=False "
                               "(the loop thread owns the slot table)")
        steps = 0
        try:
            while max_steps is None or steps < max_steps:
                self._admit()
                if not any(s is not None for s in self._slots):
                    break
                # the last quantum asked for dispatches nothing ahead:
                # every step dispatched is read before this returns
                steps += 1
                self._tick(last=steps == max_steps)
        except BaseException as e:
            self._poison(e)
            raise
        return steps

    def shutdown(self, drain=True, timeout_s=None):
        """Stop intake. ``drain=True`` serves everything already
        submitted — queued requests included — to completion;
        ``drain=False`` aborts: in-flight generation and queued requests
        both fail with :class:`EngineShutdownError`."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._aborted = not drain
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout_s if timeout_s is not None else 30.0)
        elif drain:
            self.drive()
        else:
            self._abort_live()
        for staging in list(self._ahead.values()):
            # a compile left running at interpreter exit is a crash
            staging.join(timeout_s if timeout_s is not None else 30.0)
        self._fail_pending()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)

    # -- scheduler ----------------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while (not self._closed and not self._pending
                       and not any(s is not None for s in self._slots)):
                    # no request to serve: the device idles because nothing
                    # was asked of it, which the span and the counter say
                    with trace.span("decode.idle"):
                        since = self._clock()
                        self._cv.wait()
                        self.metrics_.observe_idle(self._clock() - since)
                if self._closed:
                    if self._aborted:
                        break
                    if (not any(s is not None for s in self._slots)
                            and not self._pending):
                        break
            try:
                self._admit()
                if any(s is not None for s in self._slots):
                    self._tick()
                elif self._closed:
                    break
            except BaseException as e:  # noqa: BLE001 — fail loudly, once
                self._poison(e)
                if not isinstance(e, Exception):
                    return
        if self._aborted:
            self._abort_live()

    def _poison(self, exc):
        """The step function itself threw (a replica fault, not a request
        fault): fail everything in flight — a decode loop cannot retry
        mid-sequence without replaying the whole cache, and the caches
        were handed over to the run that failed, so they are gone. The
        slot table is dropped with them: whatever is submitted next is
        served from fresh caches."""
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._resolve_exc(slot.req, exc)
                self._slots[i] = None
        self._drop_table()
        self._fail_pending(exc)

    def _drop_table(self):
        """Forget the slot table and its caches (every slot is free): the
        next admission re-buckets from nothing, into new zero caches. A
        step in flight over the table is waited for and dropped with it."""
        flight, self._flight = self._flight, None
        if flight is not None:
            try:
                np.asarray(flight.outs[self._self["yield_idx"] if self._self
                                       else self._ids_idx])
            except Exception:  # noqa: BLE001: its rows have failed already
                pass
        self._slots = []
        self._caches = {}
        self._cache_bytes = 0
        self._bucket = (0, 0)

    def _fail_pending(self, exc=None):
        with self._cv:
            pending = list(self._pending)
            self._pending.clear()
        for req in pending:
            self._resolve_exc(req, exc or EngineShutdownError(
                "DecodeBatcher shut down before this request started"))
            self.metrics_.observe_failed()

    def _abort_live(self):
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._resolve_exc(slot.req, EngineShutdownError(
                    "DecodeBatcher aborted mid-generation"))
                self.metrics_.observe_failed()
                self._slots[i] = None
        self._drop_table()

    def _release_prefix(self, req):
        """Drop a request's pinned prefix entry (cloned, failed, or
        expired — the pin must never outlive the request)."""
        if self.prefix_cache is not None and req.prefix is not None:
            self.prefix_cache.release(req.prefix.entry)
            req.prefix = None

    def _resolve_exc(self, req, exc):
        self._release_prefix(req)
        try:
            req.future.set_exception(exc)
        except Exception:
            pass
        self._admission.release(1)

    # admission + re-bucketing — runs BETWEEN steps only (slot recycling)
    def _admit(self):
        with trace.span("decode.admit") as sp:
            if self._flight is not None:
                # a step is in flight over this slot table: whoever waits
                # is admitted once it has been read (a request there is
                # room for ends the run: nothing is dispatched ahead of it)
                if sp:
                    sp.set(admitted=0, pending=len(self._pending),
                           rebucketed=0)
                return
            now = self._clock()
            admitted = []
            waited = 0.0
            with self._cv:
                live = sum(1 for s in self._slots if s is not None)
                room = max(self.ladder) - live
                while self._pending and room > 0:
                    req = self._pending.popleft()
                    if req.deadline is not None and now > req.deadline:
                        self._resolve_exc(req, DeadlineExceededError(
                            "request waited %.1f ms, deadline was %.1f ms"
                            % ((now - req.enqueue_t) * 1e3,
                               (req.deadline - req.enqueue_t) * 1e3)))
                        self.metrics_.observe_expired()
                        continue
                    req.order = self._admissions
                    self._admissions += 1
                    admitted.append(req)
                    waited += now - req.enqueue_t
                    room -= 1
                pending = len(self._pending)
            copied = None
            if admitted:
                self.metrics_.observe_admitted(len(admitted), waited)
            if admitted or self._bucket != self._target_bucket([]):
                copied = self._rebucket(admitted)
            if sp:
                moved = {} if copied is None else {"copied_bytes": copied}
                sp.set(admitted=len(admitted), pending=pending,
                       rebucketed=int(copied is not None), **moved)

    def _target_bucket(self, admitting):
        live = [s.req for s in self._slots if s is not None]
        reqs = live + list(admitting)
        if not reqs:
            return (0, 0)
        b = bucket_for(len(reqs), self.ladder)
        c = bucket_for(max(r.n_ctx for r in reqs), self.ctx_ladder)
        return (b, c)

    def _rebucket(self, admitting):
        """Place admissions and re-shape the caches when the (batch, ctx)
        bucket moved.

        Same geometry: admitted requests drop into free HOLES — zero
        cache traffic, because a recycled row needs no cleaning (its new
        occupant starts at pos 0 and a row's attention mask never reaches
        past its own fill level, so the previous occupant's leftovers are
        unreachable). This is what keeps steady-state slot recycling off
        the host.

        Geometry moved (occupancy crossed a ladder rung, or a longer
        request raised the ctx rung): live rows compact into fresh
        zero arrays — the one host-side copy re-bucketing costs. Returns
        the bytes of live rows that copy moved, or None where the geometry
        stayed."""
        new_b, new_c = self._target_bucket(admitting)
        if (new_b, new_c) == self._bucket:
            if admitting:
                free = [i for i, s in enumerate(self._slots) if s is None]
                for req, i in zip(admitting, free):
                    self._slots[i] = _Slot(req)
                    self._install_prefix(i, req)
            return None
        old_c = self._bucket[1]
        live = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        new_slots = [s for _, s in live]
        for req in admitting:
            new_slots.append(_Slot(req))
        new_slots += [None] * (new_b - len(new_slots))
        copied = 0
        for feed, _idx, tail, dtype, cap, stride in self._cache_feeds:
            old = self._caches.get(feed)
            # a ring keeps its capacity whatever the rung, and goes whole
            copy_c = self._entries(cap, stride, min(old_c, new_c))
            new = np.zeros((new_b, self._entries(cap, stride, new_c)) + tail,
                           dtype)
            if old is not None and live:
                old = np.asarray(old)
                for j, (i, _s) in enumerate(live):
                    new[j, :copy_c] = old[i, :copy_c]
                copied += len(live) * new[0, :copy_c].nbytes
            self._caches[feed] = new
        self._cache_bytes = sum(a.nbytes for a in self._caches.values())
        self._slots = new_slots
        self._bucket = (new_b, new_c)
        for j, req in enumerate(admitting, start=len(live)):
            self._install_prefix(j, req)
        return copied

    def _install_prefix(self, i, req):
        """Clone a matched prefix's leading rows into slot row ``i`` and
        release the pin. CLONE, never alias: after this the slot owns
        its rows, so evicting the entry can never corrupt the slot."""
        match = req.prefix
        if match is None:
            return
        m = match.length
        for feed, *_ in self._cache_feeds:
            rows = match.entry.rows.get(feed)
            if rows is None:
                continue
            rows = np.asarray(rows)[:m]
            cache = self._caches[feed]
            if isinstance(cache, np.ndarray):
                cache[i, :m] = rows
            else:  # device-resident jax array: one device-side copy
                self._caches[feed] = cache.at[i, :m].set(rows)
        self._release_prefix(req)

    def _synth_feed(self, b):
        lanes = (b, 2) if self._self else (b,)
        return {self._tok_feed: np.zeros(lanes, np.int64),
                self._pos_feed: np.zeros(lanes, np.int32)}

    def _synth_caches(self, b, c):
        return {name: np.zeros(a.shape, a.dtype)
                for name, a in self._cache_shapes(b, c).items()}

    def _tick(self, last=False):
        """One scheduler quantum: a chunk dispatch (prefill and/or
        speculative verify) when the chunk program has work, else one
        decode step. When some live rows can't ride the chunk (they are
        generating and speculation is off), chunk and step ticks
        ALTERNATE so a long prompt is ingested chunk-by-chunk without
        stalling its co-riders. Alternation is for rows that generate: an
        ingesting row the sub-batch had no room for rides the next chunk,
        and where no row generates that chunk is the next tick. Where a
        step is in flight (:meth:`_step_once` dispatched it ahead) the
        quantum is that step's: no row ingested when it was dispatched, and
        nobody was admitted since. ``last``: the caller stops after this
        quantum, so no step is dispatched ahead of it."""
        if self._prefill is None or self._flight is not None:
            self._step_once(last)
            return
        with trace.span("decode.plan") as sp:
            plan = self._chunk_plan()
            if sp:
                sp.set(rows=len(plan[0]) if plan else 0,
                       verifying=bool(plan and plan[2]))
        if plan is None:
            self._alt_chunk = False
            self._step_once(last)
            return
        rows, has_uncovered, verifying, deferred = plan
        if has_uncovered and self._alt_chunk:
            self._alt_chunk = False
            self._step_once(last)
            return
        self._alt_chunk = True
        with trace.span("spec.verify" if verifying
                        else "prefill.chunk") as sp:
            self._chunk_once(rows, deferred, sp)

    def _stage_ahead(self, now):
        """A chunk run of signature ``now`` is about to be dispatched: have
        helper threads make (or load) meanwhile every other executable of
        this geometry that has not run yet, the step's and the other chunk
        rungs', so that the quanta which follow find theirs staged. Staging
        takes seconds and most of them outside the interpreter's lock (a
        handed-over chunk executable loads in 2-3 s); the threads share
        nothing but the weights, which all only read, and an executor
        whose variants they make one each."""
        b, c = self._bucket
        # speculation samples from the chunk's logits: no step follows
        ahead = [] if self._spec_k else [(b, c)]
        ahead += [(b, c, k) for k in self.prefill_ladder]
        ahead = [sig for sig in ahead
                 if sig != now and sig not in self.seen_signatures
                 and sig not in self._ahead]
        if not ahead:
            return
        with trace.span("decode.plan") as sp:
            for sig in ahead:
                self._ahead[sig] = threading.Thread(
                    target=self._stage, args=(sig,),
                    name="paddle-tpu-decode-stage", daemon=True)
                self._ahead[sig].start()
            if sp:
                sp.set(staging=len(ahead))

    def _cache_shapes(self, rows, c):
        """{cache feed: the shape and type of its ``rows`` x ``c`` array,
        ``rows`` x its own capacity where it is a ring, ``rows`` x ``c /
        stride`` where it states a stride}."""
        import jax

        return {name: jax.ShapeDtypeStruct(
            (rows, self._entries(cap, stride, c)) + tail, dtype)
            for name, _idx, tail, dtype, cap, stride in self._cache_feeds}

    def _stage(self, sig):
        """Make (or load) the executable of ``sig`` from shapes, nothing
        run: the step's at the slot table's shapes, a chunk rung's at its
        sub-batch's, and with it the two copies round its runs."""
        b, c = sig[:2]
        if len(sig) == 2:
            self._step.stage(self._synth_feed(b), self._cache_shapes(b, c))
            return
        self._prefill["pred"].stage(
            self._synth_chunk_feed(*sig),
            self._cache_shapes(self._chunk_height(b, sig[2]), c))
        try:
            self._rows_copies(sig)
        except Exception:  # noqa: BLE001: the run itself will raise it
            pass

    def _chunk_height(self, b, k):
        """The rows the chunk executable of rung ``k`` is made for in a
        bucket of ``b``: :func:`chunk_rows`; the whole bucket where drafts
        are verified, since a verifying tick reads every live row's
        logits and one height a rung is all the bound allows."""
        return b if self._spec_k else chunk_rows(k, b)

    def _rows_copies(self, sig):
        """(gather, scatter) compiled for the chunk signature ``sig``, or
        None where its chunk runs over the slot table itself. Compiled
        from shapes (a staging thread does it beside the rung's own
        executable), once a signature."""
        b, c, k = sig
        r = self._chunk_height(b, k)
        if r >= b:
            return None
        made = self._rows_staged.get(sig)
        if made is None:
            import jax

            gather, scatter = _rows_helpers(min(k, c), self._rings,
                                            self._strided)
            table, sub = self._cache_shapes(b, c), self._cache_shapes(r, c)
            idx = jax.ShapeDtypeStruct((r,), np.dtype("int32"))
            n = jax.ShapeDtypeStruct((), np.dtype("int32"))
            made = self._rows_staged[sig] = (
                gather.lower(table, idx, n).compile(),
                scatter.lower(table, sub, idx, idx, n).compile())
        return made

    def _await_staged(self, sig):
        """The executable of ``sig`` is about to run: if a helper thread is
        staging it, let it finish (the run would stage it a second time)."""
        staging = self._ahead.pop(sig, None)
        if staging is not None:
            with trace.span("decode.plan"):
                staging.join()

    def _chunk_plan(self):
        """This tick's chunk rows as ``(rows, has_uncovered, verifying,
        deferred)`` — or None when no live row wants the chunk program.
        Each row is ``(i, slot, tokens, n_forced)``: lane j of the chunk
        feeds ``tokens[j]`` at cache index ``slot.pos + j``; the first
        ``n_forced`` tokens are committed (prompt or already-emitted),
        the rest are speculative drafts judged against the chunk's own
        logits.

        Without speculation the chunk is the rung of the OLDEST ingesting
        row's pending prompt, and as many ingesting rows ride as that
        rung's sub-batch is high (:func:`chunk_rows`), oldest admission
        first, each ingesting up to the rung; ``deferred`` counts those
        left for the next chunk tick. No row waits behind a younger one,
        and the head always rides, so nobody starves."""
        spec = self._spec_k > 0
        top = self.prefill_ladder[-1]
        ingest = []     # (i, slot, want) — rows with prompt left
        verify = []     # (i, slot) — generating rows (spec mode)
        uncovered = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            pending = len(slot.req.prompt) - slot.pos
            if pending >= 2 or (spec and pending == 1):
                # plain prefill leaves the LAST prompt token for the
                # step program (sampling stays on the step path —
                # bitwise parity with plain decode); spec mode ingests
                # through it and samples from the chunk's own logits
                want = pending if spec else pending - 1
                ingest.append((i, slot, min(want, top)))
            elif spec:
                verify.append((i, slot))
            else:
                uncovered += 1
        if not ingest and not verify:
            return None
        deferred = 0
        if not spec:
            ingest.sort(key=lambda row: row[1].req.order)
            k = bucket_for(ingest[0][2], self.prefill_ladder)
            height = self._chunk_height(self._bucket[0], k)
            deferred = max(0, len(ingest) - height)
            ingest = [(i, slot, min(want, k))
                      for i, slot, want in ingest[:height]]
        rows = []
        for i, slot, want in ingest:
            toks = slot.req.prompt[slot.pos:slot.pos + want]
            rows.append((i, slot, toks, len(toks)))
        if verify:
            needs = []
            for i, slot in verify:
                real = min(self._spec_k,
                           slot.req.max_new - len(slot.out), top)
                needs.append(max(0, real - 1))
            drafts = self._draft_for([s for _i, s in verify], max(needs))
            for (i, slot), d, n in zip(verify, drafts, needs):
                rows.append((i, slot, [slot.next_token] + d[:n], 1))
        return rows, uncovered > 0, bool(verify), deferred

    def _draft_for(self, slots, n):
        """``n`` draft continuations per generating slot, from the small
        draft LM over each slot's committed history. Any proposal is
        SAFE — the accept rule only ever emits the target model's own
        greedy chain — so draft failures degrade to 1-token progress
        rather than failing the tick."""
        if n <= 0:
            return [[] for _ in slots]
        hists = [s.req.prompt + s.out for s in slots]
        try:
            return self._draft.propose(hists, n)
        except Exception:
            return [[] for _ in slots]

    def _chunk_once(self, rows, deferred, sp):
        """Dispatch one chunk: K-token lanes per covered row, pad lanes
        carry the pad sentinel ``pos == bucket_ctx`` (their cache writes
        drop via the op's out-of-range mode and their logits are
        ignored; the chunk spec's ``pad_pos`` in its place where it states
        one). Where the rung's height (:meth:`_chunk_height`) is
        under the bucket, the run is over a SUB-BATCH: one jitted gather
        copies the covered rows' caches out of the slot table, the chunk
        executable is handed those, and one jitted scatter, the table
        handed over, writes the lanes the run wrote back in place; all
        three are dispatched and none is waited for. A pad sub-row
        carries the row index ``b``, past the table, and the copies' loops
        end before it: it reads and writes nothing. Commits forced
        tokens, then emits each verify row's greedy chain: drafts are accepted while they equal the chunk's
        own argmax, the first disagreement is replaced by the argmax
        itself (always >= 1 token of progress), and rejected lanes are
        REWOUND by pointer arithmetic — rows past a slot's fill level
        are unreachable by the attention mask, the same property slot
        recycling rests on."""
        pf = self._prefill
        b, c = self._bucket
        k = bucket_for(max(len(t) for _i, _s, t, _f in rows),
                       self.prefill_ladder)
        sig = (b, c, k)
        r = self._chunk_height(b, k)
        self._stage_ahead(sig)
        self._await_staged(sig)
        copies = self._rows_copies(sig)
        with trace.span("decode.feed"):
            tok = np.zeros((r, k + self._chunk_extra), np.int64)
            cpos = np.full((r, k), self._pad(c), np.int32)
            # sub-row j holds table row at[j]; the pads' lies past the
            # table, and neither copy's loop reaches them
            at = np.full((r,), b, np.int32)
            start = np.zeros((r,), np.int32)
            ordered = sorted(rows, key=lambda row: row[0])
            sub_of = {}
            for j, (i, slot, tokens, _f) in enumerate(ordered):
                sub = sub_of[i] = i if copies is None else j
                n = len(tokens)
                at[sub], start[sub] = i, slot.pos
                tok[sub, :n] = tokens
                if self._chunk_extra:
                    # the token after the row's last lane: a chunk stops
                    # short of the prompt's last token, so there is one
                    tok[sub, n] = slot.req.prompt[slot.pos + n]
                cpos[sub, :n] = np.arange(slot.pos, slot.pos + n,
                                          dtype=np.int32)
            table, caches, held = None, self._caches, np.int32(len(rows))
            if copies is not None:
                table = caches
                if isinstance(next(iter(table.values())), np.ndarray):
                    import jax

                    # a fresh table is the host's zeros: placed once, not
                    # by each of the two copies
                    table = jax.device_put(table)
                caches = copies[0](table, at, held)
            # the carried caches are handed over: from here on only what
            # the run returns may be read, and that is what the table keeps
            feed = {pf["tok"]: tok, pf["pos"]: cpos, **caches}
        outs = pf["pred"].run(feed)
        self.seen_signatures.add(sig)
        caches = {name: outs[idx] for name, idx in pf["cache_map"]}
        if copies is not None:
            caches = copies[1](table, caches, at, start, held)
        self._caches = caches
        self.metrics_.observe_cache_donated(
            self._cache_bytes * r // b if pf["pred"].hands_over else 0)
        greedy = None
        now = self._clock()
        live = sum(1 for s in self._slots if s is not None)
        generated = 0
        accepted = rejected = 0
        chunk_rows = 0
        chunk_toks = 0
        for i, slot, tokens, n_forced in rows:
            real = len(tokens)
            base = slot.pos
            L = len(slot.req.prompt)
            ingested = max(0, min(L - base, n_forced))
            if ingested:
                chunk_rows += 1
                chunk_toks += ingested
            if n_forced == real and base + real < L:
                # pure prompt ingestion, prompt not finished
                slot.pos = base + real
                slot.k = slot.pos + 1
                slot.next_token = slot.req.prompt[slot.pos]
                if self._self and slot.pos == L - 1:
                    # the module's head on this row's last lane: the draft
                    # its first step verifies, read when that step is fed
                    slot.draft = (outs[self._self["chunk_draft_idx"]],
                                  sub_of[i])
                continue
            # the chunk covered through the last prompt token (spec
            # prefill) or this is a verify row: emit the greedy chain
            if greedy is None:
                # the one place a chunk waits for the device: it samples
                with trace.span("decode.fetch") as fsp:
                    logits = np.asarray(outs[pf["logits_idx"]])
                    if fsp:
                        fsp.set(bytes=int(logits.nbytes))
                greedy = np.argmax(logits, axis=-1)
            req = slot.req
            emitted = greedy_chain(tokens, n_forced, greedy[i],
                                   req.max_new - len(slot.out), req.eos_id)
            j = n_forced - 2 + len(emitted)
            if n_forced < real:
                accepted += j - (n_forced - 1)
                rejected += (real - n_forced) - (j - (n_forced - 1))
            slot.pos = base + j + 1
            for t in emitted:
                slot.out.append(t)
                generated += 1
                if slot.first_tok_t is None:
                    slot.first_tok_t = now
                    self.metrics_.observe_ttft(now - req.enqueue_t)
            if not slot.harvested and slot.pos >= L:
                self._maybe_harvest(i, slot)
            done = (len(slot.out) >= req.max_new
                    or (req.eos_id is not None
                        and slot.out[-1] == req.eos_id))
            if done:
                self._retire(i, slot, now)
            else:
                slot.next_token = slot.out[-1]
        if chunk_rows:
            self.metrics_.observe_prefill_chunk(chunk_rows, chunk_toks,
                                                r * k, deferred)
        if accepted or rejected:
            self.metrics_.observe_spec(accepted, rejected)
        self.metrics_.observe_decode_step(live, b, generated)
        if sp:
            sp.set(live=live, bucket=b, ctx=c, chunk=k,
                   generated=generated, accepted=accepted,
                   rejected=rejected, rows=chunk_rows, tokens=chunk_toks,
                   lanes=r * k, sub_rows=r)

    def _maybe_harvest(self, i, slot):
        """First full ingestion of this prompt: offer its KV rows [0:L]
        to the prefix cache (one host copy per request, once; the cache
        keeps them only if the exact prompt isn't already an entry)."""
        slot.harvested = True
        if self.prefix_cache is None:
            return
        key = slot.req.prompt
        if len(key) < 2 or key in self.prefix_cache:
            return
        rows = {}
        for feed, *_ in self._cache_feeds:
            rows[feed] = np.array(np.asarray(self._caches[feed])[i,
                                                                 :len(key)])
        self.prefix_cache.insert(key, rows)

    def _synth_chunk_feed(self, b, c, k):
        """A chunk feed of pad lanes only, at the rung's own height."""
        pf = self._prefill
        r = self._chunk_height(b, k)
        return {pf["tok"]: np.zeros((r, k + self._chunk_extra), np.int64),
                pf["pos"]: np.full((r, k), self._pad(c), np.int32)}

    @property
    def _chunk_extra(self):
        """Token lanes a chunk feed holds past its rung: one where the
        programs draft for themselves (lane j's own token and, for the
        prediction module, lane j + 1's)."""
        return self._self["chunk_extra"] if self._self else 0

    def _pad(self, c):
        """The position a pad lane carries in a bucket of context ``c``."""
        return c if self._pad_pos is None else self._pad_pos

    def _step_once(self, last=False):
        """One step quantum. It reads the step in flight, dispatched ahead
        by the quantum before, or else dispatches one over the live slots
        and reads that; and where the quantum to come is again a plain step
        over this slot table (:meth:`_rows_after`), that step is dispatched
        BEFORE the read, the ids of the step about to be read its tokens:
        the device runs it while the host reads, delivers and retires.
        Depth one, never more.

        The run's end is settled one step early, from the same knowledge:
        where the step dispatched ahead here will have no follower (a row
        of its own ends by ``max_new`` with somebody waiting for the slot,
        all of them end, a re-bucketing falls due), it is read here too,
        after the step before it, under a ``decode.step`` span of its own
        that opens before this quantum's (it holds this quantum's
        dispatches and ends with the read of exactly that step): ONE span a
        step, each ending with the read of one step, and each over a
        dispatch but one kind: a step left in flight whose follower is
        called off by what happened since (a request arrived that there is
        a slot for, a row ended on its eos id with somebody waiting) is
        read by a quantum that dispatches nothing. ``last`` (:meth:`drive`
        with a budget) dispatches nothing ahead."""
        self._await_staged(self._bucket)
        flight, self._flight = self._flight, None
        # a self-drafting step takes no forced prompt token: a row that
        # still ingests waits for its chunk (the module's cache holds what
        # the chunk program wrote, and a forced lane would write another)
        rows = flight.rows if flight is not None else [
            (i, slot, slot.pos) for i, slot in enumerate(self._slots)
            if slot is not None and not (self._self and slot.forcing)]
        ahead = None if last else self._rows_after(rows, 1)
        ends = ahead is not None and self._rows_after(ahead, 2) is None
        with (trace.span("decode.step") if ends
              else contextlib.nullcontext()) as outer:
            with trace.span("decode.step") as sp:
                if flight is None:
                    flight = self._dispatch(rows)
                if ahead is not None:
                    self._flight = self._dispatch(
                        ahead, tuple(flight.outs[i]
                                     for i in self._self["carried"])
                        if self._self else flight.outs[self._ids_idx])
                self._land(flight, sp)
            if ends:
                flight, self._flight = self._flight, None
                if flight is not None:
                    self._land(flight, outer)

    def _rows_after(self, rows, k):
        """The rows of the step after one over ``rows``, each one position
        on, if the quantum after that step is again a plain step over the
        same slot table; else None. ``rows``: ``(slot row, its _Slot, the
        position fed)`` of a step whose token will be each slot's ``k``-th
        from what its ``out`` holds now (1: the step not read yet; 2: the
        one after it). Decided from the loop's own state, all of it known
        before the ids are. Rows known to end at that step by ``max_new``
        ride no further (dead lanes, like the table's holes). The one
        thing the host learns late is a row that ends on an end-of-sequence
        id: it rides the step ahead as a live lane and its output there is
        dropped (:meth:`_land`); its cache write lands at its own row's
        next position (modulo its own row's ring), which a later occupant
        of the slot writes before its attention reaches it."""
        if self._self:
            if "carried" not in self._self or any(
                    s is not None and s.forcing for s in self._slots):
                return None  # the host's to feed; or a chunk comes next
        elif self._ids_idx is None or self._spec_k:
            return None  # the next tokens are the host's to make
        harvests = self.prefix_cache is not None
        after = []
        for i, slot, p in rows:
            if self._slots[i] is not slot:
                continue  # ended on its eos id a step ago: a dead lane
            if slot.forcing or (harvests and not slot.harvested):
                # its next token is the prompt's, not the device's; or the
                # prefix cache is to read rows the step ahead is handed
                return None
            if self._self:
                # a verifying step yields one token or two, and which the
                # host learns a step late: of the ``k`` steps not read yet
                # the row has at least ``most`` tokens of room left before
                # the last of them and at most ``least`` (all it has, for
                # k = 1). With one token of room it ends there for certain;
                # with two it ends iff its draft stands (it then rides one
                # step for nothing, dropped when read, unless somebody
                # waits for its slot: then read first); and where it will
                # stand is the device's to carry
                room = slot.req.max_new - len(slot.out)
                most, least = room - (k - 1), room - 2 * (k - 1)
                if most <= 1:
                    continue
                if least <= 2 and self._pending:
                    return None
                after.append((i, slot, None))
                continue
            if len(slot.out) + k < slot.req.max_new:
                after.append((i, slot, p + 1))
        if not after:
            return None
        if self._pending and len(after) < max(self.ladder):
            return None  # read first, admit; the chunk's quantum follows
        if self._bucket != (
                bucket_for(len(after), self.ladder),
                bucket_for(max(slot.req.n_ctx for _i, slot, _p in after),
                           self.ctx_ladder)):
            return None  # a re-bucketing is due
        return after

    def _dispatch(self, rows, ids=None):
        """Dispatch one step over ``rows`` and return it as a
        :class:`_Flight`; nothing is waited for. Each row is fed the token
        the host holds for it, or, with ``ids`` (the id array of the step
        before, unread and on the device), that array is the token feed
        itself, of the shape and type the executable was made for."""
        b, c = self._bucket
        if self._self:
            return self._dispatch_drafting(rows, ids)
        with trace.span("decode.feed"):
            pos = np.zeros((b,), np.int32)
            toks = ids
            if ids is None:
                toks = np.zeros((b,), np.int64)
                for i, slot, _p in rows:
                    toks[i] = slot.next_token
            for i, _slot, p in rows:
                pos[i] = p
            # carried state: the caches are handed over to the step (it
            # writes its rows into the buffers it is given), and the
            # fetched arrays, device-resident and never on the host, are
            # the table's caches from here on; the arrays fed are deleted
            # and not read again
            feed = {self._tok_feed: toks, self._pos_feed: pos,
                    **self._caches}
        outs = self._step.run(feed)
        self.seen_signatures.add((b, c))
        self._caches = {name: outs[idx]
                        for name, idx, *_ in self._cache_feeds}
        self.metrics_.observe_cache_donated(
            self._cache_bytes if self._step.hands_over else 0)
        return _Flight(outs, rows, ids is not None)

    def _land(self, flight, sp):
        """Read a dispatched step's result and keep the slots' books: the
        wait for the device, the ids' (or, from a predictor that gives
        none, the logits') way to the host, then each row's token
        delivered and what is done retired. A row that is no longer its
        slot's (it ended on its eos id while this step was in flight) is
        dropped, counted neither live nor generated."""
        b, c = self._bucket
        if self._self:
            self._land_drafting(flight, sp)
            return
        # the wait for the device: the run only dispatched the step
        with trace.span("decode.fetch") as fsp:
            by_id = self._ids_idx is not None
            read = np.asarray(flight.outs[
                self._ids_idx if by_id else self._logits_idx])
            if self._counter_idx is not None:
                self.metrics_.observe_program_counters(
                    self._counter_names,
                    np.asarray(flight.outs[self._counter_idx]).ravel())
            if fsp:
                fsp.set(bytes=int(read.nbytes))
        with trace.span("decode.sample") as ssp:
            now = self._clock()
            live = 0
            generated = 0
            retired = 0
            for i, slot, p in flight.rows:
                if self._slots[i] is not slot:
                    continue
                live += 1
                slot.pos = p + 1
                if slot.forcing:
                    slot.next_token = slot.req.prompt[slot.k]
                    slot.k += 1
                    continue
                if not slot.harvested and slot.pos >= len(slot.req.prompt):
                    self._maybe_harvest(i, slot)
                nxt = int(read[i] if by_id else np.argmax(read[i]))
                generated += 1
                slot.out.append(nxt)
                if slot.first_tok_t is None:
                    slot.first_tok_t = now
                    self.metrics_.observe_ttft(now - slot.req.enqueue_t)
                done = (len(slot.out) >= slot.req.max_new
                        or (slot.req.eos_id is not None
                            and nxt == slot.req.eos_id))
                if done:
                    self._retire(i, slot, now)
                    retired += 1
                else:
                    slot.next_token = nxt
            ahead = self._flight
            if ahead is not None and not any(
                    self._slots[i] is slot for i, slot, _p in ahead.rows):
                # every row of the step ahead has ended meanwhile: nothing
                # of it is anyone's, and no quantum would come to read it
                self._flight = None
            self.metrics_.observe_decode_step(live, b, generated,
                                              ahead=flight.ahead)
            if ssp:
                ssp.set(generated=generated, retired=retired)
        if sp:
            # slot occupancy rides on every step span (ISSUE 17)
            sp.set(live=live, bucket=b, ctx=c, generated=generated,
                   ahead=int(flight.ahead))

    def _dispatch_drafting(self, rows, carried=None):
        """Dispatch one verifying step over ``rows``: lane 0 of a row the
        token the host holds for it at its position, lane 1 the draft of
        the next one position on. A row nobody has drafted for, or with room
        for one token more, rides with lane 1 a pad lane (and so does every
        lane of a row that does not ride): it writes nothing, and no draft
        is judged. With ``carried`` (the two feeds the step before made for
        this one, unread and on the device) those are the feeds themselves:
        every row that rode that step rides this one where it will stand,
        its draft with it, and what has ended meanwhile is dropped when this
        step is read."""
        b, c = self._bucket
        with trace.span("decode.feed"):
            drafts = None
            if carried is not None:
                toks, pos = carried
            else:
                toks = np.zeros((b, 2), np.int64)
                pos = np.full((b, 2), self._pad(c), np.int32)
                drafts = {}
                for i, slot, p in rows:
                    toks[i, 0], pos[i, 0] = slot.next_token, p
                    draft = slot.draft
                    if isinstance(draft, tuple):    # a chunk run's, unread
                        draft = int(np.asarray(draft[0])[draft[1]])
                    if slot.req.max_new - len(slot.out) < 2:
                        draft = None
                    drafts[i] = draft
                    if draft is not None:
                        toks[i, 1], pos[i, 1] = draft, p + 1
            feed = {self._tok_feed: toks, self._pos_feed: pos,
                    **self._caches}
        outs = self._step.run(feed)
        self.seen_signatures.add((b, c))
        self._caches = {name: outs[idx]
                        for name, idx, *_ in self._cache_feeds}
        self.metrics_.observe_cache_donated(
            self._cache_bytes if self._step.hands_over else 0)
        return _Flight(outs, rows, carried is not None, drafts)

    def _land_drafting(self, flight, sp):
        """Read a verifying step's ``[b, 4]`` (tokens yielded, the two
        tokens, the next draft) and keep the books: each row emits what
        :func:`greedy_chain` gives of its two lanes, one token or two, and
        moves on by as many positions; a lane that did not stand left a
        row in the caches at the position the row's next step writes first.
        ``decode_tokens`` counts the tokens delivered, never the lanes."""
        b, c = self._bucket
        with trace.span("decode.fetch") as fsp:
            read = np.asarray(flight.outs[self._self["yield_idx"]])
            if self._counter_idx is not None:
                self.metrics_.observe_program_counters(
                    self._counter_names,
                    np.asarray(flight.outs[self._counter_idx]).ravel())
            if fsp:
                fsp.set(bytes=int(read.nbytes))
        with trace.span("decode.sample") as ssp:
            now = self._clock()
            live = generated = retired = drafted = accepted = 0
            for i, slot, p in flight.rows:
                if self._slots[i] is not slot:
                    continue  # it ended while this step was in flight
                live += 1
                req = slot.req
                if flight.drafts is None:
                    # fed by the step before: where the row stood when that
                    # one was read, and the draft it made
                    p, draft = slot.pos, slot.draft
                else:
                    draft = flight.drafts[i]
                if not slot.harvested and p + 1 >= len(req.prompt):
                    slot.pos = p + 1
                    self._maybe_harvest(i, slot)
                if self.draft_log is not None:
                    self.draft_log.append((req, p, draft, tuple(read[i])))
                fed = [slot.next_token] + ([] if draft is None else [draft])
                emitted = greedy_chain(fed, 1, read[i, 1:3],
                                       req.max_new - len(slot.out),
                                       req.eos_id)
                if draft is not None:
                    drafted += 1
                    accepted += len(emitted) - 1
                slot.pos = p + len(emitted)
                for t in emitted:
                    slot.out.append(t)
                    generated += 1
                    if slot.first_tok_t is None:
                        slot.first_tok_t = now
                        self.metrics_.observe_ttft(now - req.enqueue_t)
                done = (len(slot.out) >= req.max_new
                        or (req.eos_id is not None
                            and slot.out[-1] == req.eos_id))
                if done:
                    self._retire(i, slot, now)
                    retired += 1
                else:
                    # the device judged as the host did unless the host cut
                    # the chain short, and then the row is done
                    slot.next_token = slot.out[-1]
                    slot.draft = int(read[i, 3])
            ahead = self._flight
            if ahead is not None and not any(
                    self._slots[i] is slot for i, slot, _p in ahead.rows):
                # every row of the step ahead has ended meanwhile: nothing
                # of it is anyone's, and no quantum would come to read it
                self._flight = None
            self.metrics_.observe_spec(accepted, drafted - accepted)
            self.metrics_.observe_decode_step(live, b, generated,
                                              ahead=flight.ahead)
            if ssp:
                ssp.set(generated=generated, retired=retired)
        if sp:
            sp.set(live=live, bucket=b, ctx=c, generated=generated,
                   ahead=int(flight.ahead), drafted=drafted,
                   accepted=accepted)

    def _retire(self, i, slot, now):
        """Finished sequence: resolve, free the slot IMMEDIATELY (the
        next ``_admit`` recycles it — no drain barrier)."""
        self._slots[i] = None
        if len(slot.out) > 1:
            self.metrics_.observe_tpot(
                (now - slot.first_tok_t) / (len(slot.out) - 1))
        try:
            slot.req.future.set_result(np.asarray(slot.out, np.int64))
        except Exception:
            pass
        self.metrics_.observe_completed(now - slot.req.enqueue_t)
        self._admission.release(1)
