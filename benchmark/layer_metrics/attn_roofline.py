"""Kernels: the attention core's share of its roofline. The least time the
chip could take for the operations and bytes the core requires in one step
(``ops_count.attention_core_step``; the larger of operations over the bf16
peak and bytes over the HBM peak) over ``attn_ms``. At these shapes the
operations bound it. Recomputation inside the kernels is not counted, so
the share cannot pass 100%."""

from benchmark import ops_count


def read(ctx):
    run, trainer = ctx["run"], ctx["trainer"]
    measured = ctx["trace"].ms_a_step_under(("flash_attention",))
    if not measured:
        return None
    peaks = run.peaks()
    rows_per_chip = trainer.sizes["batch"] // len(run.devices)
    flops, nbytes = ops_count.attention_core_step(trainer.builder_args,
                                                  rows_per_chip)
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
