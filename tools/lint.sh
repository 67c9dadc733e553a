#!/usr/bin/env bash
# Repo lint gate (wired into the test suite via tests/test_lint.py).
#
# Two sections:
#   1. `ruff check` with the enforced floor configured in pyproject.toml
#      [tool.ruff.lint] (syntax errors, unused/undefined names, broken
#      comparisons, redefinitions). When ruff is not in the image
#      (nothing may be pip-installed here), degrade to a pure-stdlib
#      syntax gate so the check still refuses unparseable code.
#   2. the static-analysis zoo sweep (`python -m paddle_tpu.analysis
#      --zoo`, which since ISSUE 15 also runs the COST pass over every
#      zoo program) — the verifier's regression corpus must stay at zero
#      findings and every cost rule must run without crashing.
#   3. the router chaos smoke (`tools/chaos_router.py --smoke`, ISSUE
#      16): one real worker process behind the socket front door, a
#      small burst, zero silent losses — the multi-process serving path
#      must stay standing before anything ships.
#   4. the trace-view smoke (`tools/trace_view.py --smoke`, ISSUE 17):
#      a deterministic fake-clock capture through the summarizer —
#      critical path + cross-process stitch check must agree with the
#      obs/trace span format.
#   5. the streaming chaos smoke (`tools/chaos_stream.py --smoke`, ISSUE
#      18): an in-process train-to-serve loop, the newest published
#      version corrupted on disk — the publisher must fall back to the
#      previous intact version mid-burst with zero failed requests and
#      a flight dump that proves it.
#   6. the fleet chaos smoke (`tools/chaos_fleet.py --smoke`, ISSUE 19):
#      deterministic fake-clock drills for the multi-host loop — a
#      mid-file death resumed exactly-once from its cursor, a lease
#      takeover past the TTL, and a two-phase fleet swap that
#      quarantines (then heals) a commit-faulted straggler.
#   7. the decode chaos smoke (`tools/chaos_decode.py --smoke`, ISSUE
#      20): two lm-decode workers with the prefix-KV cache hot, a
#      mid-decode SIGKILL — zero silent losses, every completed reply
#      bitwise-equal to the cold pass, and no stale prefix after the
#      respawn.
set -euo pipefail
cd "$(dirname "$0")/.."

TARGETS=(paddle_tpu tests tools chip_smoke.py)
PY="${PYTHON:-$(command -v python3 || command -v python)}"

if command -v ruff >/dev/null 2>&1; then
    ruff check "${TARGETS[@]}"
elif "$PY" -c "import ruff" >/dev/null 2>&1; then
    "$PY" -m ruff check "${TARGETS[@]}"
else
    echo "lint.sh: ruff unavailable; falling back to compileall syntax gate" >&2
    "$PY" -m compileall -q -f "${TARGETS[@]}"
fi

JAX_PLATFORMS=cpu "$PY" -m paddle_tpu.analysis --zoo -q

JAX_PLATFORMS=cpu "$PY" tools/chaos_router.py --smoke

JAX_PLATFORMS=cpu "$PY" tools/trace_view.py --smoke

JAX_PLATFORMS=cpu "$PY" tools/chaos_stream.py --smoke

JAX_PLATFORMS=cpu "$PY" tools/chaos_fleet.py --smoke

JAX_PLATFORMS=cpu "$PY" tools/chaos_decode.py --smoke

echo "lint.sh: ok"
