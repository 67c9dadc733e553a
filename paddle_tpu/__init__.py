"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid 1.3 (reference at /root/reference; blueprint in
SURVEY.md).

Public surface mirrors ``paddle.fluid``:

    import paddle_tpu as fluid
    x = fluid.layers.data("x", shape=[784])
    y = fluid.layers.fc(x, size=10, act="softmax")
    ...
    exe = fluid.Executor(fluid.XLAPlace(0))
    exe.run(fluid.default_startup_program())
    loss_val, = exe.run(feed={...}, fetch_list=[loss])

Execution model: programs are symbolic op graphs compiled by whole-program
``jax.jit`` into single XLA computations with donated state (see
``core/executor.py``); parallelism is mesh sharding (see ``parallel/``).
"""

from . import compile_cache  # noqa: F401
from .core import framework
from .core.framework import (  # noqa: F401
    Program, Variable, Parameter,
    default_main_program, default_startup_program, program_guard,
    name_scope)
from .core.executor import (  # noqa: F401
    Executor, Scope, global_scope, scope_guard,
    XLAPlace, TPUPlace, CPUPlace, CUDAPlace)
from .core.compiler import (  # noqa: F401
    CompiledProgram, BuildStrategy, ExecutionStrategy)
from .core.param_attr import ParamAttr  # noqa: F401
from .core import initializer  # noqa: F401
from .core import unique_name  # noqa: F401

from . import layers  # noqa: F401
from . import optimizer  # noqa: F401
from . import backward  # noqa: F401
from .backward import append_backward, calc_gradient, gradients  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import metrics  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import checkpoint  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from .async_executor import AsyncExecutor  # noqa: F401
from . import streaming  # noqa: F401
from . import contrib  # noqa: F401
from .data.data_feed import DataFeedDesc  # noqa: F401
from . import dygraph  # noqa: F401
from . import data  # noqa: F401
from .data.feeder import DataFeeder  # noqa: F401
from . import profiler  # noqa: F401
from . import obs  # noqa: F401
from . import debugger  # noqa: F401
from . import analysis  # noqa: F401
from . import dlpack  # noqa: F401
from . import parallel  # noqa: F401
from .version import __version__  # noqa: F401

compile_cache.place()

# convenience re-exports matching fluid's top level
from .clip import set_gradient_clip  # noqa: F401


def memory_optimize(input_program=None, skip_opt_set=None, print_log=False,
                    level=0, skip_grads=False):
    """Ref ``python/paddle/fluid/transpiler/memory_optimization_transpiler.py``
    (var reuse by liveness). The XLA build gets buffer sharing/reuse from
    the compiler already; the knob that still matters on TPU is
    rematerialization, so this flips the program's backward to recompute
    forward activations in the backward pass (``jax.checkpoint``), trading
    FLOPs for peak HBM exactly like the reference trades copies for reuse."""
    from .core import framework as _fw

    prog = input_program or _fw.default_main_program()
    hit = False
    for op in prog.global_block().ops:
        if op.type == "autodiff":
            op.attrs["remat"] = True
            hit = True
    if hit:
        prog._version += 1
    elif print_log:
        print("memory_optimize: no backward in program; XLA buffer "
              "assignment already reuses forward buffers")
    return prog


def release_memory(input_program=None, skip_opt_set=None):
    """Ref ``release_memory`` (insert delete_var ops): subsumed — buffer
    donation + XLA liveness free buffers at their last use. Kept for API
    parity; returns the program unchanged."""
    from .core import framework as _fw

    return input_program or _fw.default_main_program()
