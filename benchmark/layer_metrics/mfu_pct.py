"""XLA-lowered ops: model FLOP/s utilization. The benchmark's own count of
the operations the forward and backward passes require per sample (the
configuration's ``ops_count``, ``file.py:function`` under ``benchmark/``)
times the run's ``train_samples_per_s``, over the chips times the published
bf16 peak (``peaks.json``). Recomputed operations do not count. Not a
kernel's roofline share and blind to idle time."""

import os

from benchmark import harness


def read(ctx):
    run, trainer = ctx["run"], ctx["trainer"]
    if run.devices[0].platform != "tpu":
        return None
    file, function = run.config["ops_count"].split(":")
    count = getattr(harness.load_module(os.path.join(harness.HERE, file)),
                    function)
    flops = count(trainer.builder_args)
    peak = run.peaks()["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops * ctx["rate"] / peak
