"""XLA-lowered ops: the chunked state-space core's share of its roofline.
The least time the chip could take for the operations and bytes the core
requires in one step (``ops_count_nemotron3_super.ssd_core_step``; the
larger of operations over the bf16 peak and bytes over the HBM peak) over
``ssd_ms``. The triangular halves, the products between chunks, the carried
states and recomputation are not counted, so the share cannot pass 100%. It
is the XLA-lowered chunked form's share while the program has no kernel for
it."""

import os

from benchmark import harness


def read(ctx):
    run, trainer = ctx["run"], ctx["trainer"]
    measured = ctx["trace"].ms_a_step_under(("mamba2_ssd",))
    if not measured:
        return None
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_nemotron3_super.py")).ssd_core_step
    peaks = run.peaks()
    rows_per_chip = trainer.sizes["batch"] // len(run.devices)
    flops, nbytes = count(trainer.builder_args, rows_per_chip)
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
