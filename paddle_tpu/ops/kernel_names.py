"""One name on every Pallas kernel, where a trace can read it.

``<family>.<part>``: the family as its gate names it (``dense_vmem``,
``packed_stream``, ``head_split_stream``, ``fused_conv``, ...), the part
``fwd``, ``bwd``, ``apply``, ... . The kernel's ``name=`` becomes the name
of the compiled step's HLO instruction (``%dense_vmem.fwd.56``), which is
what a TPU device event is called; the enclosing ``jax.named_scope`` puts
the same text into the instruction's ``op_name`` path, so that forward
can be told from backward and one family from another by either."""

import jax


def named_pallas_call(name, kernel, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)``, called under
    ``jax.named_scope(name)``."""
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with jax.named_scope(name):
            return call(*args)

    return run
