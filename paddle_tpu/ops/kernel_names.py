"""One name on every Pallas kernel, where a trace can read it, and one
trace of every kernel signature.

``<family>.<part>``: the family as its gate names it (``dense_vmem``,
``packed_stream``, ``head_split_stream``, ``fused_conv``, ...), the part
``fwd``, ``bwd``, ``apply``, ... . The kernel's ``name=`` becomes the name
of the compiled step's HLO instruction (``%dense_vmem.fwd.56``), which is
what a TPU device event is called; the enclosing ``jax.named_scope`` puts
the same text into the instruction's ``op_name`` path, so that forward
can be told from backward and one family from another by either.

Pallas traces a kernel's body anew at every ``pallas_call`` binding, and a
body that unrolls heads and batch rows in Python is hundreds of equations.
A step calls the same kernel at many sites (one attention signature a
layer), so the function that builds and binds a kernel is wrapped by
:func:`traced_once`: JAX's own trace cache then holds its jaxpr by avals
and static arguments, and a further site of the same signature gets the
equations without running the Python again."""

import contextlib
import functools
import threading

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


# ---------------------------------------------------------------------------
# kernel bodies traced, and sites that reused one, during one trace
# ---------------------------------------------------------------------------

_gathering = threading.local()


@contextlib.contextmanager
def collect_traces():
    """Gather ``{name: {"sites": calls, "traced": bodies traced}}`` of
    every :func:`traced_once` function called on this thread inside the
    block."""
    before = getattr(_gathering, "counts", None)
    _gathering.counts = counts = {}
    try:
        yield counts
    finally:
        _gathering.counts = before


def _count(name, what):
    counts = getattr(_gathering, "counts", None)
    if counts is not None:
        counts.setdefault(name, {"sites": 0, "traced": 0})[what] += 1


def tally_traces(counts):
    """``{name: {"traced": bodies traced, "reused": sites that took a
    body traced before}}`` of gathered counts."""
    return {name: {"traced": row["traced"],
                   "reused": row["sites"] - row["traced"]}
            for name, row in sorted(counts.items())}


def traced_once(name, static_argnames):
    """Decorator for the function that builds and binds the kernel
    ``name``: a function of its arrays (``None`` for an absent one), with
    everything else it depends on among ``static_argnames`` — also what
    it would otherwise read from the process at trace time, such as a
    module's ``_INTERPRET``, or a stale body is reused. Its Python runs
    once per (avals, statics) in a process; every call inlines the cached
    equations at the call site under the caller's name stack, so the
    enclosing program is the one the unwrapped function would have given.
    Both are counted for :func:`collect_traces`."""
    def decorate(impl):
        @functools.wraps(impl)
        def body(*args, **kwargs):
            _count(name, "traced")
            return impl(*args, **kwargs)

        cached = jax.jit(body, static_argnames=static_argnames, inline=True)

        @functools.wraps(impl)
        def site(*args, **kwargs):
            _count(name, "sites")
            return cached(*args, **kwargs)

        return site

    return decorate
