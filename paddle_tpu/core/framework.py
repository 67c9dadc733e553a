"""Symbolic program graph: Program / Block / Variable / Operator.

Capability parity with the reference's program-based user API
(``python/paddle/fluid/framework.py``: ``Variable:242``, ``Operator:571``,
``Block:1020``, ``Program:2284``) — but lowered differently: instead of
serializing to a ProgramDesc protobuf interpreted op-by-op by a C++ Executor
(``paddle/fluid/framework/executor.cc:186``), a ``paddle_tpu`` Program is a
lightweight op list that the Executor traces into ONE jitted XLA computation
(whole-program fusion; state is a functional pytree with buffer donation).

TPU-first design notes:
  * no ProgramDesc/protobuf IR — the jaxpr/HLO *is* the IR; this class only
    records user intent (ops + attrs) for tracing & introspection.
  * Variables carry static shapes with -1 for the batch dim (XLA needs static
    shapes at compile time; the executor specializes on fed shapes).
  * Parameters may carry a sharding spec (tuple of mesh axis names or None)
    consumed by CompiledProgram/pjit — this replaces the reference's
    multi-device graph passes (``multi_devices_graph_pass.cc``).
"""

import contextlib
import os
import sys

import numpy as np

from . import unique_name

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "default_main_program",
    "default_startup_program",
    "switch_main_program",
    "switch_startup_program",
    "program_guard",
    "name_scope",
    "trace_scope",
    "convert_np_dtype",
    "grad_var_name",
    "in_dygraph_mode",
]

_SUPPORTED_DTYPES = {
    "float16": np.float16,
    "bfloat16": "bfloat16",  # resolved lazily through ml_dtypes via jnp
    "float32": np.float32,
    "float64": np.float64,
    "int8": np.int8,
    "int16": np.int16,
    "int32": np.int32,
    "int64": np.int64,
    "uint8": np.uint8,
    "bool": np.bool_,
}


def convert_np_dtype(dtype):
    """Normalize a dtype spec (str / np.dtype / jnp dtype) to a np.dtype.

    bfloat16 is supported via ml_dtypes (jax's numpy dtype extension).
    """
    if dtype is None:
        return np.dtype("float32")
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        if dtype not in _SUPPORTED_DTYPES:
            raise ValueError("unsupported dtype: %s" % dtype)
        return np.dtype(_SUPPORTED_DTYPES[dtype])
    return np.dtype(dtype)


def grad_var_name(name):
    """Gradient variable naming convention (ref: framework ``@GRAD`` suffix)."""
    return name + "@GRAD"


# ---------------------------------------------------------------------------
# Op provenance. Every appended op records the USER code line that created it
# (the reference stores an op_callstack attr on each OpDesc for the same
# reason — ``operator.cc`` prints it on enforce failures). Frames inside the
# framework's own graph-building machinery (core/, layers/, the optimizer /
# backward / clip wrappers) are skipped, so a diagnostic for an op appended
# by ``opt.minimize(loss)`` points at the minimize() call, not at
# layer_helper internals. Frame-pointer walk only — no traceback object, no
# linecache reads — so the capture is cheap enough to stay always-on.
# ---------------------------------------------------------------------------

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FRAMEWORK_PREFIXES = (os.path.join(_PKG_DIR, "core"),
                       os.path.join(_PKG_DIR, "layers"))
_FRAMEWORK_FILES = frozenset(
    os.path.join(_PKG_DIR, f) for f in
    ("backward.py", "optimizer.py", "clip.py", "regularizer.py", "amp.py"))


def _is_framework_frame(filename):
    return (filename in _FRAMEWORK_FILES
            or filename.startswith(_FRAMEWORK_PREFIXES))


def _user_callsite(skip=2):
    """(filename, lineno, function) of the innermost non-framework frame."""
    try:
        f = sys._getframe(skip)
    except ValueError:  # shallower stack than expected (C embedding)
        return None
    first = None
    while f is not None:
        fn = f.f_code.co_filename
        if first is None:
            first = (fn, f.f_lineno, f.f_code.co_name)
        if not _is_framework_frame(fn):
            return (fn, f.f_lineno, f.f_code.co_name)
        f = f.f_back
    return first  # pure-framework stack (internal tests): best effort


def in_dygraph_mode():
    from .. import dygraph

    return dygraph.base._in_dygraph_mode()


class Variable:
    """A symbolic tensor in a Block.

    Mirrors the user-visible contract of the reference's ``Variable``
    (name/shape/dtype/persistable/stop_gradient/lod_level); ``lod_level`` is
    kept for API parity — ragged sequence data is represented with explicit
    length/segment-id companion tensors on TPU (static shapes), not LoD.
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype="float32",
        persistable=False,
        stop_gradient=False,
        lod_level=0,
        is_data=False,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_np_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.lod_level = lod_level
        self.is_data = is_data
        self.op = None  # producing op, set by append_op

    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def astype(self, dtype):
        from ..layers import tensor as tensor_layers

        return tensor_layers.cast(self, dtype)

    # ------ introspection parity helpers ------
    def to_string(self, throw_on_error=False, with_details=False):
        return "Variable(name=%s, shape=%s, dtype=%s, persistable=%s)" % (
            self.name,
            self.shape,
            self.dtype,
            self.persistable,
        )

    __repr__ = __str__ = lambda self: self.to_string()

    # arithmetic sugar (the reference monkey-patches these via
    # ``layers/math_op_patch.py``)
    def _binary(self, other, fn, reverse=False):
        from ..layers import math_op_patch

        return math_op_patch.binary(self, other, fn, reverse)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rtruediv__(self, other):
        return self._binary(other, "elementwise_div", reverse=True)

    def __pow__(self, other):
        return self._binary(other, "elementwise_pow")

    def __neg__(self):
        from ..layers import nn

        return nn.scale(self, scale=-1.0)

    def __lt__(self, other):
        return self._binary(other, "less_than")

    def __le__(self, other):
        return self._binary(other, "less_equal")

    def __gt__(self, other):
        return self._binary(other, "greater_than")

    def __ge__(self, other):
        return self._binary(other, "greater_equal")


class Parameter(Variable):
    """A trainable persistable Variable (ref ``framework.py:2917``).

    Extra attributes consumed by the optimizer / parallel layers:
      * trainable, optimize_attr (learning_rate multiplier), regularizer,
        gradient_clip_attr — parity with the reference.
      * sharding: optional tuple of mesh-axis names (len == rank) used by
        CompiledProgram/pjit to lay the parameter out on the device mesh —
        the TPU-native replacement for pserver param slicing
        (``distribute_transpiler.py:84``).
    """

    def __init__(self, block, shape, dtype, **kwargs):
        if shape is None or any(int(s) <= 0 for s in shape):
            raise ValueError("Parameter shape must be fully-defined and positive, got %s" % (shape,))
        super().__init__(block, shape=shape, dtype=dtype, persistable=True, **{
            k: v for k, v in kwargs.items()
            if k in ("name", "stop_gradient", "lod_level", "is_data")
        })
        self.trainable = kwargs.get("trainable", True)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)
        self.sharding = kwargs.get("sharding", None)
        self.initializer = kwargs.get("initializer", None)
        self.is_distributed = kwargs.get("is_distributed", False)


class Operator:
    """A symbolic op: type + named input/output slots + attrs.

    Execution semantics live in ``core.op_registry`` (each type maps to a pure
    jax function). Mirrors the reference ``Operator`` (``framework.py:571``)
    without the OpDesc protobuf layer.
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs = {}
        self.outputs = {}
        self.attrs = dict(attrs) if attrs else {}
        self.callsite = None  # (file, line, function) set by Block.append_op
        # a None value (or entry) means "slot absent" — several layer
        # builders pass optional slots through unconditionally, and every
        # consumer (impls via op.input(), dataflow via input_arg_names)
        # treats a missing slot and None identically
        if inputs:
            for slot, vs in inputs.items():
                vs = list(vs) if isinstance(vs, (list, tuple)) else [vs]
                vs = [v for v in vs if v is not None]
                if vs:
                    self.inputs[slot] = vs
        if outputs:
            for slot, vs in outputs.items():
                vs = list(vs) if isinstance(vs, (list, tuple)) else [vs]
                vs = [v for v in vs if v is not None]
                if vs:
                    self.outputs[slot] = vs

    def input(self, slot):
        vs = self.inputs.get(slot, [])
        return vs[0] if vs else None

    def input_list(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        vs = self.outputs.get(slot, [])
        return vs[0] if vs else None

    def output_list(self, slot):
        return self.outputs.get(slot, [])

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def where(self):
        """Human-readable creation site for diagnostics, e.g.
        ``train.py:42 (in build_model)``; '<unknown>' when not captured."""
        if not self.callsite:
            return "<unknown>"
        fn, line, func = self.callsite
        return "%s:%d (in %s)" % (os.path.basename(fn), line, func)

    @property
    def input_arg_names(self):
        return [v.name for vs in self.inputs.values() for v in vs]

    @property
    def output_arg_names(self):
        return [v.name for vs in self.outputs.values() for v in vs]

    def __repr__(self):
        return "{%s: (%s) -> (%s)}" % (
            self.type,
            ", ".join(self.input_arg_names),
            ", ".join(self.output_arg_names),
        )


class Block:
    """An ordered list of ops + a var symbol table (ref ``framework.py:1020``).

    Sub-blocks exist for control-flow parity (While/Cond record their bodies
    as sub-blocks, executed through lax.while_loop/cond)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def var(self, name):
        """Look up a var by name, walking parent blocks (ref scope lookup)."""
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        raise KeyError("Variable %s not found in block %d or ancestors" % (name, self.idx))

    def has_var(self, name):
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    def create_var(self, **kwargs):
        name = kwargs.get("name") or unique_name.generate("_generated_var")
        kwargs["name"] = name
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[name] = v
        return v

    def create_parameter(self, **kwargs):
        name = kwargs.get("name") or unique_name.generate("param")
        kwargs["name"] = name
        p = Parameter(self, kwargs.pop("shape"), kwargs.pop("dtype", "float32"), **kwargs)
        self.vars[name] = p
        self.program._params[name] = p
        return p

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        op.callsite = _user_callsite()
        self.ops.append(op)
        for vs in op.outputs.values():
            for v in vs:
                v.op = op
        self.program._version += 1
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        op.callsite = _user_callsite()
        self.ops.insert(0, op)
        self.program._version += 1
        return op

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def __repr__(self):
        return "Block(idx=%d, ops=[%s])" % (
            self.idx,
            ", ".join(op.type for op in self.ops),
        )


class Program:
    """A user-built symbolic program (ref ``framework.py:2284``).

    The Executor compiles a (program, feed-signature, fetch-list) triple into
    a single jitted function over the persistable-state pytree."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0  # bumped on mutation; part of the executor cache key
        self._params = {}
        self._is_test = False
        # set by optimizer.minimize: ops needing special replay handling
        self._backward_ops = []
        # set by CompiledProgram / DistStrategy
        self._mesh = None
        self._lr_schedulers = []
        self._seed_counter = 0

    # ---- block management ----
    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    # ---- introspection ----
    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    def all_parameters(self):
        return [p for p in self._params.values()]

    def to_string(self, throw_on_error=False, with_details=False):
        lines = []
        for b in self.blocks:
            lines.append("-- block %d (parent %d) --" % (b.idx, b.parent_idx))
            for v in b.vars.values():
                lines.append("  var %s : %s %s%s" % (
                    v.name, v.shape, v.dtype,
                    " [param]" if isinstance(v, Parameter) else ""))
            for op in b.ops:
                lines.append("  op %r" % (op,))
        return "\n".join(lines)

    __repr__ = __str__ = lambda self: self.to_string()

    # ---- cloning (ref Program.clone; for_test flips is_test attrs) ----
    def clone(self, for_test=False):
        """Structural copy. ``for_test=True`` sets is_test on dropout /
        batch_norm-style ops (ref ``Program.clone(for_test=True)``) and strips
        optimizer/backward ops."""
        p = Program()
        p.random_seed = self.random_seed
        var_map = {}

        # clone blocks/vars
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            p.blocks.append(nb)
            for name, v in b.vars.items():
                if isinstance(v, Parameter):
                    nv = Parameter(
                        nb, v.shape, v.dtype, name=name,
                        trainable=v.trainable, optimize_attr=v.optimize_attr,
                        regularizer=v.regularizer,
                        gradient_clip_attr=v.gradient_clip_attr,
                        sharding=v.sharding, initializer=v.initializer,
                        is_distributed=v.is_distributed,
                    )
                    p._params[name] = nv
                else:
                    nv = Variable(
                        nb, name=name, shape=v.shape, dtype=v.dtype,
                        persistable=v.persistable, stop_gradient=v.stop_gradient,
                        lod_level=v.lod_level, is_data=v.is_data)
                    # mesh/ZeRO annotations must survive cloning
                    if getattr(v, "sharding", None) is not None:
                        nv.sharding = v.sharding
                    if getattr(v, "is_optimizer_state", False):
                        nv.is_optimizer_state = True
                nb.vars[name] = nv
                var_map[(b.idx, name)] = nv

        def map_vars(block_idx, vs):
            return [var_map[(block_idx, v.name)] for v in vs]

        _TEST_SKIP = {"autodiff"}
        for b, nb in zip(self.blocks, p.blocks):
            for op in b.ops:
                if for_test and (op.type in _TEST_SKIP or op.attr("is_optimizer_op")):
                    continue
                attrs = dict(op.attrs)
                if for_test and "is_test" in attrs:
                    attrs["is_test"] = True
                if for_test and op.type == "dropout":
                    attrs["is_test"] = True
                nop = Operator(
                    nb, op.type,
                    {s: map_vars(b.idx, vs) for s, vs in op.inputs.items()},
                    {s: map_vars(b.idx, vs) for s, vs in op.outputs.items()},
                    attrs)
                nop.callsite = op.callsite  # provenance survives cloning
                nb.ops.append(nop)
        p._is_test = for_test
        p._version = self._version
        p.current_block_idx = 0
        return p

    def prune(self, targets):
        """Keep only ops needed to compute ``targets`` (ref ``Program.prune``,
        C++ ``prune.h``). Used by save_inference_model."""
        if not isinstance(targets, (list, tuple)):
            targets = [targets]
        needed = {t.name if isinstance(t, Variable) else t for t in targets}
        # persistables are STATE (resolved from the scope), not products:
        # without this, pruning to an inference target chases params back
        # through the optimizer ops and drags the whole backward along.
        # The user's explicit targets stay producible even when persistable
        # (e.g. fetching an EMA/global var the program computes).
        persistable = {v.name for v in self.list_vars()
                       if v.persistable} - set(needed)
        ops = self.global_block().ops
        kept_idx = set()
        for i in range(len(ops) - 1, -1, -1):
            if set(ops[i].output_arg_names) & (needed - persistable):
                kept_idx.add(i)
                needed |= set(ops[i].input_arg_names)
        # clone preserves op order 1:1, so filter by position — two
        # identical-signature ops (e.g. two dropouts of the same var) must
        # not alias each other
        p = self.clone()
        nb = p.global_block()
        nb.ops = [o for i, o in enumerate(nb.ops) if i in kept_idx]
        p._version += 1
        return p


# ---------------------------------------------------------------------------
# default program singletons + guards (ref framework.py:3001-3069)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program():
    return _main_program_


def default_startup_program():
    return _startup_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


_name_scope_stack = []


@contextlib.contextmanager
def trace_scope(name):
    """The ops appended to the current main program's global block inside
    this context run under ``jax.named_scope(name)``, round each op's own
    scope of its type: a module of several ops then has one name in a
    device trace (``mtp.block/routed_experts/...``)."""
    block = default_main_program().global_block()
    start = len(block.ops)
    try:
        yield
    finally:
        for op in block.ops[start:]:
            op.attrs["_trace_scope"] = name


@contextlib.contextmanager
def name_scope(prefix=None):
    """Profiling/introspection name scope (ref ``framework.py`` name_scope;
    maps to jax.named_scope at trace time)."""
    _name_scope_stack.append(prefix or "")
    try:
        yield
    finally:
        _name_scope_stack.pop()
