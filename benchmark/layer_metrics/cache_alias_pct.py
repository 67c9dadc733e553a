"""Decode loop: of the cache bytes the loop hands over to its executables
(``donated_feed_bytes`` in each compile record, PR 36), the share the
compiler aliased to an output (``memory["alias_bytes"]``), over every
executable the step and chunk predictors made. 100: every cache is written
in place; under 100: some executable copies a cache it was handed. Program
counter."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.cache_alias_pct(ctx.get("compile_records"))
