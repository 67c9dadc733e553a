"""Gates: how many Pallas kernels the compiled step calls: instructions of
the step's compiled HLO whose ``custom_call_target`` is
``tpu_custom_call``. A count that repeats exactly; 0 under a mesh, where the
gates refuse. (The decisions the gated ops record at trace time,
``op.attrs["_kernel_choice"]``, miss the convolutions that the fusion pass
rewrites: they read 0 for ResNet-50's 98 calls, my chip run, PR 23.)"""


def read(ctx):
    hlo_text = ctx.get("hlo_text")
    if hlo_text is None:
        return None
    return hlo_text.count('custom_call_target="tpu_custom_call"')
