"""Kernels: the chunked delta-rule core's share of its roofline. The least
time the chip could take for the operations and bytes the core requires in
one step (``ops_count_qwen3_next.gated_delta_core_step``; the larger of
operations over the bf16 peak and bytes over the HBM peak) over ``gdn_ms``.
The triangular halves, the carried states and recomputation are not
counted, so the share cannot pass 100%. Since PR 27 the core is the two
Pallas kernels ``gated_delta.fwd`` / ``.bwd`` on the chip (the ``jnp`` chunk
scan on the CPU and under a mesh); ``gdn_ms`` holds both."""

import os

from benchmark import harness


def read(ctx):
    run, trainer = ctx["run"], ctx["trainer"]
    measured = ctx["trace"].ms_a_step_under(("gated_delta_rule",))
    if not measured:
        return None
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_qwen3_next.py")).gated_delta_core_step
    peaks = run.peaks()
    rows_per_chip = trainer.sizes["batch"] // len(run.devices)
    flops, nbytes = count(trainer.builder_args, rows_per_chip)
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
