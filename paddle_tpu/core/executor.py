"""Executor: compiles a (Program, feed-signature, fetch-list) into ONE jitted
XLA computation and runs it.

Reference contract: ``fluid.Executor(place).run(program, feed, fetch_list)``
(``python/paddle/fluid/executor.py:262,554`` dispatching to the C++
interpreter ``paddle/fluid/framework/executor.cc:186``). The TPU-native
execution model replaces the op-by-op interpreter loop + per-op kernel
launches + garbage collector with:

  * trace all ops of the program into a single jax function
    ``(state, feed, rng) -> (fetches, new_state, rng')``;
  * ``jax.jit`` it with the persistable-state pytree DONATED — XLA's buffer
    assignment gives in-place parameter updates (the role of the reference's
    inplace/memory-optimize passes and eager-deletion GC); feeds the caller
    hands over for good (``run(donate_feeds=...)``: a decode loop's carried
    caches) go in as a fourth, donated argument and are updated in place the
    same way;
  * a program cache keyed like the reference's (``executor.py:224``) but
    including feed shapes/dtypes, since XLA specializes on static shapes.

Randomness is a threaded functional PRNG key stored in the scope under
``@RNG@`` (vs. the reference's per-device curand states).
"""

import os
import threading
import time
import warnings

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np

from . import framework
from .framework import Variable
from .op_registry import run_op, RNG_KEY, RNG0_KEY, ENV0_KEY
from ..ops.gates import placed
from ..obs import trace as obs_trace
# the span primitive's second sink (jax.profiler.TraceAnnotation) is
# installed by the module that owns the profiler surface
from .. import profiler  # noqa: F401

__all__ = ["Executor", "Scope", "global_scope", "scope_guard",
           "XLAPlace", "TPUPlace", "CPUPlace", "CUDAPlace"]


# ---------------------------------------------------------------------------
# Places. The reference dispatches kernels by place (CPUPlace/CUDAPlace,
# ``platform/place.h``); here a place selects the jax backend/device. XLAPlace
# is the first-class TPU place from the north star.
# ---------------------------------------------------------------------------

class _Place:
    backend = None  # None: JAX's default backend and default placement

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def jax_devices(self):
        """Every device of this place's backend (the default backend for
        XLAPlace / CUDAPlace). A place that names a backend never answers
        with devices of another one: no such backend is an error."""
        if self.backend is None:
            return jax.devices()
        try:
            return jax.devices(self.backend)
        except RuntimeError as e:
            raise RuntimeError(
                "%r: JAX finds no %s device in this process (%s)"
                % (self, self.backend, e)) from None

    def jax_device(self):
        devs = self.jax_devices()
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError("%r: this process has %d such device(s)"
                               % (self, len(devs)))
        return devs[self.device_id]


def as_jax_devices(places):
    """jax devices of a list that may name them as fluid places."""
    return [p.jax_device() if isinstance(p, _Place) else p for p in places]


class XLAPlace(_Place):
    """The default accelerator place (TPU when available)."""


class TPUPlace(_Place):
    """One TPU chip. Raises where JAX finds no TPU — an Executor on a
    TPUPlace never runs on the CPU."""
    backend = "tpu"

    def __init__(self, device_id=0):
        super().__init__(device_id)
        self.jax_device()


class CPUPlace(_Place):
    """The host CPU, also where a chip is attached."""
    backend = "cpu"


class CUDAPlace(_Place):
    """API-compat alias: maps to the default accelerator (no CUDA on TPU
    builds; kept so reference scripts port without edits)."""


# ---------------------------------------------------------------------------
# Scope: name -> device array store (ref ``framework/scope.h:48``). Flat —
# local-scope hierarchy is unnecessary because execution is functional.
# ---------------------------------------------------------------------------

class Scope:
    def __init__(self):
        self._vars = {}

    def find_var(self, name):
        return self._vars.get(name)

    def var_names(self):
        return list(self._vars.keys())

    def get(self, name):
        return self._vars[name]

    def set(self, name, value):
        self._vars[name] = value

    def drop(self, name):
        self._vars.pop(name, None)

    def __contains__(self, name):
        return name in self._vars

    def numpy(self, name):
        return np.asarray(self._vars[name])


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        _scope_stack.append(self.scope)

    def __exit__(self, *a):
        _scope_stack.pop()


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def _as_array(value, var=None):
    if isinstance(value, jax.ShapeDtypeStruct):
        # a shape in an array's place (``Executor.stage``): the dtype the
        # program declares, as an array's would be coerced to
        if var is not None and var.dtype is not None:
            want = jax.dtypes.canonicalize_dtype(np.dtype(var.dtype))
            if value.dtype != want:
                value = jax.ShapeDtypeStruct(value.shape, want)
        return value
    if isinstance(value, jax.Array):
        # already-staged device array (e.g. a py_reader prefetch slot or a
        # caller's jax.device_put): no host round-trip; coerce dtype
        # device-side like the numpy path below does host-side
        if (var is not None and var.dtype is not None
                and not jnp.issubdtype(value.dtype, jax.dtypes.prng_key)):
            want = jax.dtypes.canonicalize_dtype(np.dtype(var.dtype))
            if value.dtype != want:
                value = value.astype(want)
        return value
    arr = np.asarray(value)
    if var is not None and var.dtype is not None and arr.dtype != var.dtype:
        arr = arr.astype(var.dtype)
    return arr


def _make_rng_key(seed, platform):
    """Threaded PRNG key. On TPU the counter-based ``rbg`` generator: it
    maps onto the hardware RNG instruction and is far cheaper than threefry
    for the per-step dropout masks (threefry lowers to long scalar-ish
    bit-mix chains that steal MXU-adjacent cycles). Elsewhere stock jax
    threefry keys."""
    if platform == "tpu":
        return jax.random.key(seed, impl="rbg")
    return jax.random.PRNGKey(seed)


def build_step_fn(program, fetch_names, persist_names, pp_cfg=None,
                  grad_scale=None, infer_only=False):
    """Trace a program's global block into one pure function
    ``(state, feed, rng) -> (fetches, new_state, rng')`` — the unit the
    Executor jits and ``__graft_entry__`` exposes.
    ``pp_cfg`` routes the autodiff replay through the pipeline engine
    (see ``parallel/pipeline.py``). ``infer_only`` narrows ``new_state``
    to persistables some op actually writes: an inference program then
    returns NO state, so running it without donation (see
    ``Executor.run(donate_state=False)``) neither invalidates nor copies
    the shared weights."""
    from .epilogue_fusion import fuse_ops

    ops = list(program.global_block().ops)
    if pp_cfg is None:
        # conv->BN(+add)->relu epilogue fusion (the build_strategy.cc
        # analog), applied to the traced op list — the user's program is
        # not mutated, and the autodiff replay lists are rewritten too so
        # the backward recomputation sees the fused ops. Skipped under
        # pipeline parallelism: stage boundaries are named vars that an
        # absorbed intermediate could erase.
        ops, _ = fuse_ops(ops, protected=set(fetch_names))
    persist_set = set(persist_names)
    if infer_only:
        produced = set()
        for op in ops:
            produced.update(op.output_arg_names)
        persist_set &= produced
    amp = bool(getattr(program, "_amp_bf16", False))

    def step(state, feed, rng):
        from .op_registry import AMP, PP_KEY

        env = {}
        env.update(state)
        env.update(feed)
        env[RNG_KEY] = rng
        env[RNG0_KEY] = rng
        if pp_cfg is not None:
            env[PP_KEY] = pp_cfg
        if grad_scale is not None:
            from .op_registry import GRAD_SCALE_KEY

            env[GRAD_SCALE_KEY] = grad_scale
        # Step-start snapshot: the autodiff replay re-runs the forward from
        # here (not from the post-forward env), so in-place ops — e.g. the LR
        # schedule's step-counter increment — apply exactly once per step.
        env[ENV0_KEY] = dict(env)
        prev_amp = AMP.enabled
        AMP.enabled = amp  # trace-time flag: fwd + autodiff replay
        try:
            for op in ops:
                run_op(env, op)
        finally:
            AMP.enabled = prev_amp
        fetches = tuple(env[n] for n in fetch_names)
        new_state = {n: env[n] for n in persist_set if n in env}
        return fetches, new_state, env[RNG_KEY]

    return step


def _xla_compiler_options():
    """PADDLE_TPU_XLA_OPTIONS="k=v,k=v" -> jit(compiler_options=...): the
    gflags-style escape hatch for per-compile XLA/libtpu tuning knobs
    (e.g. xla_tpu_scoped_vmem_limit_kib), mirroring the reference's
    FLAGS_* passthrough to its executors."""
    import os

    raw = os.environ.get("PADDLE_TPU_XLA_OPTIONS", "").strip()
    if not raw:
        return {}
    opts = {}
    for item in raw.split(","):
        if "=" in item:
            k, v = item.split("=", 1)
            opts[k.strip()] = v.strip()
    return {"compiler_options": opts} if opts else {}


# Whether JAX's persistent compilation cache served an executable is only
# told through ``jax.monitoring``; counted here, read round a compile.
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_cache_events = {_CACHE_HIT: 0, _CACHE_MISS: 0}


def _on_jax_event(event, **kwargs):
    if event in _cache_events:
        _cache_events[event] += 1


jax.monitoring.register_event_listener(_on_jax_event)


class _Variant:
    """One compiled variant of a program: the jitted step and its input
    layout (made when the variant is first seen), and what staging it
    leaves behind — the ``jax.stages`` objects ``run`` calls and
    ``lowered_hlo_text`` reads."""

    __slots__ = ("jfn", "in_shardings", "about", "handed", "lowered",
                 "compiled")

    def __init__(self, jfn, in_shardings, about, handed=()):
        self.jfn = jfn
        self.in_shardings = in_shardings
        self.about = about  # what the compile record says of the variant
        self.handed = handed  # names of the feeds the caller hands over
        self.lowered = None
        self.compiled = None

    def arguments(self, state, feed, rng):
        """The step's arguments: ``(state, feed, rng)``, and where the
        caller hands feeds over, those as a fourth (donated) tuple of their
        own, in the caller's order, and out of ``feed``."""
        if not self.handed:
            return state, feed, rng
        handed = tuple(feed[n] for n in self.handed)
        rest = dict(feed)
        for n in self.handed:
            del rest[n]
        return state, rest, rng, handed


def _nbytes(a):
    """Bytes of an array, or of the array a ``jax.ShapeDtypeStruct`` stands
    for."""
    return int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize


def _host_bytes(arrays):
    """Bytes of the numpy values among ``arrays``: what a put moves from
    the host."""
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))


class Executor:
    def __init__(self, place=None):
        self.place = place if place is not None else XLAPlace(0)
        # the device a backend-naming place pins the step to, resolved
        # once so a backend JAX lacks raises HERE, not at the first run;
        # None (XLAPlace/CUDAPlace) leaves placement to JAX, which follows
        # committed arguments — how predictor clones pin themselves
        self._device = (self.place.jax_device() if self.place.backend
                        else None)
        self._cache = {}
        # program variants already verified -> strictness (1 = warn-mode,
        # 2 = raising). A warn-mode pass must NOT suppress a later strict
        # verify=True of the same variant.
        self._verified = {}
        self._reads = None  # (program key, names its ops read)
        self._last = None  # the variant of the last run (lowered_hlo_text)
        # One record a compiled variant, always on (it happens once a
        # variant): seconds of tracing, lowering and backend compile,
        # whether the persistent cache served the executable, the
        # executable's memory_analysis() and the decision of every gated
        # kernel site met during the trace. Plain data; read it.
        self.compile_records = []
        # plain counters beside it: calls of run, calls that found their
        # variant compiled and calls that staged one, and state arrays a
        # call had to move to the step's layout (0 in a steady loop)
        self.runs = 0
        self.variant_hits = 0
        self.variant_misses = 0
        self.state_relayouts = 0
        # ``stage`` may be called from other threads than the one that runs
        self._counting = threading.Lock()

    # -- public API ---------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, feed_var_name="feed",
            fetch_var_name="fetch", check_nan_inf=None, donate_state=True,
            verify=None, donate_feeds=()):
        """``donate_state=False`` compiles the step WITHOUT donating the
        state pytree (and, off-mesh, without echoing unwritten state back
        out). Donation invalidates the input weight arrays mid-call — fine
        for a single-threaded training loop that re-sets the scope right
        after, but a use-after-free race when predictor clones serve the
        same scope from concurrent threads (``inference.py``/``serving``).

        ``donate_feeds`` names the feeds the caller hands over for good:
        arrays nobody else holds and the caller will not read again (a
        decode loop's carried caches, which come back as fetches). They go
        to the step as a fourth argument of their own, which is donated, so
        XLA may write a fetch into a handed-over feed's buffer (an in-place
        ``kv_cache_write``) where it would otherwise copy the whole array
        first. After the call a handed-over ``jax.Array`` is deleted,
        whether or not the call succeeded in full: keep what comes back.
        Name them in the order of the fetches that are to take their
        buffers: jit pairs the donated arguments of one shape and type with
        the outputs of that shape and type in order, and a cache paired
        with another cache's fetch costs the copy the hand-over was to
        save. The names, in that order, are part of the variant's key; with
        none given the step is the three-argument one it always was. A
        step over a mesh takes no hand-over (its shardings are not
        guessed): it raises.

        ``verify=True`` (or env ``PADDLE_TPU_VERIFY=1``) runs the static
        program verifier (``paddle_tpu.analysis``) once per compiled
        variant, BEFORE lowering: use-before-def, unordered double writes,
        static shape/dtype propagation, dead-op lint, and — when the state
        is donated — the fetch/donation alias check. Errors raise
        :class:`analysis.VerificationError` naming the op and the user
        line that created it; ``verify="warn"`` (or
        ``PADDLE_TPU_VERIFY=warn``) downgrades errors to warnings;
        ``verify="strict"`` (or ``PADDLE_TPU_VERIFY=strict``) additionally
        runs the RESOURCE lints (``analysis.resources``: Pallas VMEM-gate
        refusals, dynamic-shape recompile hazards) — advisory findings
        surfaced as warnings, correctness errors still raising."""
        from .compiler import CompiledProgram

        if program is None:
            program = framework.default_main_program()
        if check_nan_inf is None:
            from .op_registry import env_flag

            check_nan_inf = env_flag("FLAGS_check_nan_inf")
        if check_nan_inf:
            if isinstance(program, CompiledProgram):
                warnings.warn("check_nan_inf runs op-by-op and only "
                              "supports plain Programs; the CompiledProgram "
                              "runs unchecked on the jit path")
            else:
                return self._run_checked(program, feed or {},
                                         fetch_list or [], scope,
                                         return_numpy)
        # The host phases of a call, children of ``executor.run`` (the
        # span the serving trace stitches on): recorded in the obs tracer
        # and, as ``paddle_tpu.executor.*``, on whatever jax.profiler trace
        # is being taken. Nothing here waits for the device except the
        # np.asarray of ``return_numpy=True``, under ``writeback``.
        ordinal = self.runs
        self.runs += 1
        with obs_trace.span("executor.run") as run_sp:
            with obs_trace.span("executor.prepare"):
                entry, hit, scope, state_in_names, feed_arrays, span = \
                    self._prepare(program, feed, fetch_list, scope,
                                  use_program_cache, donate_state, verify,
                                  tuple(donate_feeds))
            if run_sp:
                run_sp.set(ordinal=ordinal, variant_hit=hit)
            with obs_trace.span("executor.feed_put") as sp:
                put = _host_bytes(feed_arrays.values()) if sp else 0
                state, feed_arrays, rng, moved = self._feed_put(
                    entry, scope, state_in_names, feed_arrays, span)
                self.state_relayouts += moved
                if sp:
                    sp.set(bytes=put, state_relayouts=moved)
            args = entry.arguments(state, feed_arrays, rng)
            if entry.compiled is None:
                self._stage(entry, args, ordinal)
            self._last = entry
            with obs_trace.span("executor.dispatch"):
                try:
                    fetches, new_state, rng_out = entry.compiled(*args)
                except (TypeError, ValueError):
                    # an argument no longer has the shape, type or
                    # placement the variant was staged for (a state array
                    # replaced in the scope): what jit answers with a
                    # retrace. The check precedes execution, so nothing
                    # was donated; stage again for what is there, or raise
                    # what that raises.
                    self._stage(entry, args, ordinal, restaged=True)
                    fetches, new_state, rng_out = entry.compiled(*args)
            with obs_trace.span("executor.writeback") as sp:
                scope.set(RNG_KEY, rng_out)
                for n, v in new_state.items():
                    scope.set(n, v)
                if sp:
                    sp.set(fetch="numpy" if return_numpy else "device")
                if return_numpy:
                    return [np.asarray(f) for f in fetches]
                return list(fetches)

    def stage(self, program=None, feed=None, fetch_list=None, scope=None,
              donate_state=True, donate_feeds=()):
        """Make the executable that ``run`` with these arguments would use
        (trace, lower, compile or load from the persistent cache: seconds)
        and run nothing: the ``run`` that follows finds its variant staged.
        A feed's value may be a ``jax.ShapeDtypeStruct`` in the array's
        place, so that a caller can stage ahead of the arrays, and from
        other threads than the one that runs: a variant a thread (a decode
        loop has the step and every other chunk rung of a geometry staged
        while its first chunk is staged and run; it waits for a variant's
        thread before it runs that variant). Not for a step over a mesh,
        whose feeds are laid out before they are staged."""
        if program is None:
            program = framework.default_main_program()
        entry, _hit, scope, state_in_names, feed_arrays, span = \
            self._prepare(program, feed, fetch_list, scope, True,
                          donate_state, None, tuple(donate_feeds))
        if span is not None:
            raise NotImplementedError("Executor.stage: a step over a mesh "
                                      "is staged by its first run")
        if entry.compiled is None:
            shapes = {n: a for n, a in feed_arrays.items()
                      if isinstance(a, jax.ShapeDtypeStruct)}
            if self._device is not None:
                # as ``_feed_put`` commits arrays to the place's device
                on = jax.sharding.SingleDeviceSharding(self._device)
                shapes = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=on)
                          for n, a in shapes.items()}
            # off a mesh no state array is ever moved
            state, arrays, rng, _moved = self._feed_put(
                entry, scope, state_in_names,
                {n: a for n, a in feed_arrays.items() if n not in shapes},
                span)
            self._stage(entry, entry.arguments(state, {**arrays, **shapes},
                                               rng), self.runs)

    def _prepare(self, program, feed, fetch_list, scope, use_program_cache,
                 donate_state, verify, donate_feeds=()):
        """``executor.prepare``: unwrap the CompiledProgram, normalise the
        feeds, seed the rng, name the state, look the variant up (making
        its jitted step and layout when it is new, staging nothing) and
        verify. Returns (variant, found compiled, scope, state names, feed
        arrays, the mesh the step spans or None)."""
        from .compiler import CompiledProgram

        mesh = None
        dp_axis = None
        sp_axis = None
        seq_feeds = None
        pp = None
        zero_state = False
        grad_scale = None
        if isinstance(program, CompiledProgram):
            from .compiler import BuildStrategy

            mesh = program._resolve_mesh(self.place)
            dp_axis = program._dp_axis
            sp_axis = program._sp_axis
            seq_feeds = program._seq_feeds
            bs = program._build_strategy
            zero_state = (bs is not None and bs.reduce_strategy ==
                          BuildStrategy.ReduceStrategy.Reduce)
            if bs is not None:
                gss = BuildStrategy.GradientScaleStrategy
                if bs.gradient_scale_strategy == gss.One:
                    # ref details/build_strategy.h kGradientScaleOne: sum
                    # of per-device local-mean grads instead of the global
                    # mean — with GSPMD the whole-batch mean comes out of
                    # autodiff, so One multiplies the loss cotangent by
                    # the dp world size
                    n_dp = (dict(zip(mesh.axis_names, mesh.devices.shape))
                            .get(dp_axis, 1) if mesh is not None else 1)
                    grad_scale = float(n_dp)
                elif bs.gradient_scale_strategy == gss.Customized:
                    # ref kGradientScaleCustomized: the user feeds the loss
                    # cotangent as "<loss>@GRAD" (checked at autodiff time)
                    grad_scale = "customized"
            if program._pp_axis is not None:
                pp = (program._pp_axis, program._pp_boundaries,
                      program._pp_nmicro)
            program = program._program
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list]
        # where this step runs: over the CompiledProgram's mesh, else over
        # the mesh_scope() block a plain Program with sharded ops is run
        # inside of (they shard_map over it), else on the place's device
        from ..parallel.mesh import scoped_mesh

        span = mesh if mesh is not None else scoped_mesh()
        placement = (span.devices.flat[0].platform if span is not None
                     else self._platform(), span is not None)

        # normalize feed values
        feed_arrays = {}
        for name, value in feed.items():
            var = None
            if program.global_block().has_var(name):
                var = program.global_block().var(name)
            feed_arrays[name] = _as_array(value, var)
        if donate_feeds:
            missing = [n for n in donate_feeds if n not in feed_arrays]
            if missing:
                raise KeyError("donate_feeds names %s, which this call does "
                               "not feed" % missing)
            if span is not None:
                raise NotImplementedError(
                    "donate_feeds=%s: a step over a mesh takes no "
                    "handed-over feed (which sharding a donated feed and "
                    "the fetch that takes its buffer share is not guessed); "
                    "run it on one device or feed without handing over"
                    % list(donate_feeds))

        # seed rng on first use; random_seed=0 means nondeterministic
        # (reference Program.random_seed semantics)
        if RNG_KEY not in scope:
            if program.random_seed:
                seed = program.random_seed
            else:
                import secrets
                seed = secrets.randbits(31)
            scope.set(RNG_KEY, _make_rng_key(seed, placement[0]))

        persist_names = sorted({v.name for v in program.list_vars()
                                if v.persistable})
        state_in_names = tuple(n for n in persist_names if n in scope)
        if not donate_state:
            # a persistable the program only WRITES (a counter an op leaves
            # in the scope) is no input: as one it would enter the variant's
            # key with its first write-back and stage the step a second time
            read = self._read_names(program)
            state_in_names = tuple(n for n in state_in_names
                                   if n in read or n in fetch_names)

        # multi-host mesh (jax.distributed): each process feeds its LOCAL
        # batch shard (the reference's per-trainer reader semantics) and the
        # executor assembles global arrays. State must be identical across
        # processes (set program.random_seed) — it's treated as replicated
        # unless annotated.
        multiproc = mesh is not None and any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat)
        if multiproc:
            in_sh, _ = self._mesh_shardings(
                program, tuple(sorted(feed_arrays)), tuple(fetch_names),
                state_in_names, persist_names, mesh, dp_axis, sp_axis,
                seq_feeds, zero_state)
            state_sh, feed_sh, repl_sh = in_sh

            def globalize(sharding, arr):
                if isinstance(arr, jax.Array) and arr.sharding == sharding:
                    return arr
                if isinstance(arr, jax.Array) and jnp.issubdtype(
                        arr.dtype, jax.dtypes.prng_key):
                    # typed PRNG keys (rbg) can't round-trip through numpy;
                    # globalize the raw key bits and re-wrap
                    impl = jax.random.key_impl(arr)
                    data = jax.make_array_from_process_local_data(
                        repl_sh, np.asarray(jax.random.key_data(arr)))
                    return jax.random.wrap_key_data(data, impl=impl)
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(arr))

            feed_arrays = {n: globalize(feed_sh[n], a)
                           for n, a in feed_arrays.items()}
            for n in state_in_names:
                scope.set(n, globalize(state_sh[n], scope.get(n)))
            scope.set(RNG_KEY, globalize(repl_sh, scope.get(RNG_KEY)))

        # a host array is named by the type jit gives it (int64 is int32
        # without x64), so that the same feed as a device array, such as a
        # fetch of the step before, finds the same variant
        feed_sig = tuple(sorted(
            (n, a.shape, str(jax.dtypes.canonicalize_dtype(a.dtype)
                             if isinstance(a, np.ndarray) else a.dtype))
            for n, a in feed_arrays.items()))
        key = (id(program), program._version, feed_sig, tuple(fetch_names),
               state_in_names, id(scope), mesh, dp_axis, sp_axis, seq_feeds,
               pp, zero_state, grad_scale, donate_state, placement)
        if donate_feeds:
            key += (donate_feeds,)
        entry = self._cache.get(key) if use_program_cache else None
        if verify is None:
            mode = os.environ.get("PADDLE_TPU_VERIFY", "").strip().lower()
            if mode in ("warn", "strict"):
                verify = mode
            else:
                verify = mode in ("1", "true", "yes", "on", "raise")
        # once per program variant AT this strictness, cache hit or not —
        # an explicit verify=True after the variant compiled (or after a
        # warn-mode pass) must still verify
        strictness = 0 if not verify else {
            "warn": 1, "strict": 3}.get(verify, 2)
        if strictness > self._verified.get(key, 0):
            from ..analysis import verify_program

            verify_program(
                program, feed_names=sorted(feed_arrays),
                fetch_names=fetch_names, state_names=persist_names,
                donate_state=donate_state, warn=(verify == "warn"),
                donate_feeds=donate_feeds)
            if strictness >= 3:
                from ..analysis.resources import check_resources

                batch = None
                for a in feed_arrays.values():
                    if getattr(a, "ndim", 0) >= 1:
                        batch = int(a.shape[0])
                        break
                for d in check_resources(program, batch=batch).diagnostics:
                    warnings.warn("program verification: %s" % d)
            self._verified[key] = strictness
        hit = entry is not None
        with self._counting:
            if hit:
                self.variant_hits += 1
            else:
                self.variant_misses += 1
        if not hit:
            entry = self._compile(program, tuple(sorted(feed_arrays)),
                                  fetch_names, state_in_names, persist_names,
                                  mesh, dp_axis, sp_axis, seq_feeds, pp,
                                  zero_state, grad_scale, donate_state,
                                  placement, donate_feeds)
            if use_program_cache:
                self._cache[key] = entry
        return entry, hit, scope, state_in_names, feed_arrays, span

    def _feed_put(self, entry, scope, state_in_names, feed_arrays, span):
        """``executor.feed_put``: every device_put and re-layout of feeds,
        rng and state. Returns (state, feeds, rng, state arrays moved)."""
        state = {n: scope.get(n) for n in state_in_names}
        rng = scope.get(RNG_KEY)
        in_shardings = entry.in_shardings
        moved = 0

        def lay_out(a, sh):
            nonlocal moved
            if not isinstance(a, jax.Array) or a.sharding == sh:
                return a
            moved += 1
            return jax.device_put(a, sh)

        if in_shardings is not None:
            # a scope initialised by a one-device startup run holds arrays
            # committed to that device: lay them out as the mesh step
            # wants them (arrays already in place pass through untouched)
            state, rng = jax.tree.map(
                lay_out, (state, rng), (in_shardings[0], in_shardings[2]))
            # and the host's batch goes to the chips here, not inside the
            # call of the step
            feed_arrays = jax.device_put(feed_arrays, in_shardings[1])
        elif span is not None:
            # plain Program inside mesh_scope(): its shard_maps span the
            # mesh, so arrays a one-device startup run committed elsewhere
            # are replicated over it first
            from jax.sharding import NamedSharding, PartitionSpec

            everywhere = NamedSharding(span, PartitionSpec())

            def replicate(a):
                nonlocal moved
                if not isinstance(a, jax.Array) \
                        or a.sharding.device_set == everywhere.device_set:
                    return a
                moved += 1
                return jax.device_put(a, everywhere)

            state, rng = jax.tree.map(replicate, (state, rng))
        elif self._device is not None:
            # the place decides where the step runs: jit follows its
            # committed arguments, so committing the feeds and the rng key
            # puts the step — and with it every state array it writes
            # back — on the place's device. State committed elsewhere is
            # an error from jit, never a silent move.
            feed_arrays = jax.device_put(feed_arrays, self._device)
            rng = jax.device_put(rng, self._device)
        return state, feed_arrays, rng, moved

    def _read_names(self, program):
        """Names some op of the program reads (in any block), kept a program
        version."""
        key = (id(program), program._version)
        if self._reads is None or self._reads[0] != key:
            names = set()
            for block in program.blocks:
                for op in block.ops:
                    names.update(op.input_arg_names)
            self._reads = (key, names)
        return self._reads[1]

    def _platform(self):
        """Platform an un-meshed step runs on: the place's device, else
        (XLAPlace / CUDAPlace) JAX's default backend."""
        if self._device is not None:
            return self._device.platform
        return jax.default_backend()

    def lowered_hlo_text(self, optimized=True):
        """Optimized HLO text of the step this executor LAST ran —
        the compiled-module inspection surface for multi-chip sharding
        assertions (``parallel/sharding_check.py``; ref analog:
        ``multi_devices_graph_check_pass.cc`` asserting SSA-graph
        structure). It is the text of the executable that ran, kept from
        its one staging: nothing is traced, lowered or compiled again.
        Call after ``run``. ``optimized=False`` gives the StableHLO the
        step lowered to, before XLA compiled it."""
        if self._last is None:
            raise RuntimeError("no prior run() to inspect")
        return (self._last.compiled if optimized
                else self._last.lowered).as_text()

    def close(self):
        """Parity with ``Executor::Close`` (``executor.cc:139``): release the
        compiled-program cache."""
        self._cache.clear()
        self._verified.clear()
        self._last = None

    # -- debug run-mode -----------------------------------------------------
    def _run_checked(self, program, feed, fetch_list, scope, return_numpy):
        """FLAGS_check_nan_inf parity (ref ``operators/isfinite_op.cc`` +
        the framework's CheckOpHasNanOrInf debug hook): run the program
        op-by-op WITHOUT jit, checking every float output after each op and
        raising with the op type + var name of the first bad value. Slow by
        design — a debugging mode."""
        from .op_registry import AMP

        if scope is None:
            scope = global_scope()
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list]
        gb = program.global_block()
        env = {}
        persist_names = sorted({v.name for v in program.list_vars()
                                if v.persistable})
        for n in persist_names:
            if n in scope:
                env[n] = scope.get(n)
        for name, value in feed.items():
            var = gb.var(name) if gb.has_var(name) else None
            env[name] = jnp.asarray(_as_array(value, var))
        if RNG_KEY not in scope:
            if program.random_seed:
                seed = program.random_seed
            else:  # random_seed=0 = nondeterministic, same as run()
                import secrets
                seed = secrets.randbits(31)
            scope.set(RNG_KEY, _make_rng_key(seed, self._platform()))
        env[RNG_KEY] = scope.get(RNG_KEY)
        env[RNG0_KEY] = env[RNG_KEY]
        env[ENV0_KEY] = dict(env)
        prev_amp = AMP.enabled
        AMP.enabled = bool(getattr(program, "_amp_bf16", False))
        try:
            for op in gb.ops:
                before = {n: env.get(n) for n in op.output_arg_names}
                with placed(self._platform()), \
                        jax.default_device(self._device):
                    run_op(env, op)
                for n in op.output_arg_names:
                    v = env.get(n)
                    if v is None or v is before.get(n):
                        continue
                    if not (hasattr(v, "dtype")
                            and jnp.issubdtype(v.dtype, jnp.floating)):
                        continue
                    # bf16 numpy views have dtype.kind 'V'; upcast so the
                    # AMP overflows this flag exists to catch are seen
                    arr = np.asarray(jnp.asarray(v).astype(jnp.float32))
                    if not np.isfinite(arr).all():
                        bad = "nan" if np.isnan(arr).any() else "inf"
                        raise RuntimeError(
                            "check_nan_inf: op '%s' produced %s in output "
                            "var '%s' (shape %s)"
                            % (op.type, bad, n, arr.shape))
        finally:
            AMP.enabled = prev_amp
        scope.set(RNG_KEY, env[RNG_KEY])
        for n in persist_names:
            if n in env:
                scope.set(n, env[n])
        out = [env[n] for n in fetch_names]
        return [np.asarray(o) for o in out] if return_numpy else out

    # -- compilation --------------------------------------------------------
    def _mesh_shardings(self, program, feed_names, fetch_names,
                        state_in_names, persist_names, mesh, dp_axis,
                        sp_axis, seq_feeds=None, zero_state=False):
        """Sharding layout of a (state, feed, rng) -> (fetch, state, rng)
        step over ``mesh``: feeds shard on dp (+sp for sequence feeds),
        persistables follow their annotated specs. This is the declarative
        replacement for the reference's multi_devices_graph_pass + NCCL
        allreduce op-handles — GSPMD inserts the collectives."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh_axes = set(mesh.axis_names)

        def to_spec(var):
            spec = getattr(var, "sharding", None)
            if spec is None:
                like = getattr(var, "sharding_like", None)
                if (like is not None
                        and tuple(var.shape or ()) == tuple(like.shape or ())):
                    spec = getattr(like, "sharding", None)
            if spec is None:
                return P()
            # axes absent from this mesh degrade to replication, so an
            # mp-annotated program runs unchanged on a dp-only mesh
            return P(*[a if a in mesh_axes else None for a in spec])

        dp_size = dict(zip(mesh.axis_names,
                           mesh.devices.shape)).get(dp_axis)
        param_shardings = {}
        for v in program.list_vars():
            if not v.persistable:
                continue
            if (getattr(v, "sharding", None) is not None
                    or getattr(getattr(v, "sharding_like", None),
                               "sharding", None) is not None):
                param_shardings[v.name] = NamedSharding(mesh, to_spec(v))
            elif (zero_state and dp_size is not None
                  and getattr(v, "is_optimizer_state", False)
                  and v.shape and len(v.shape) >= 1
                  and v.shape[0] is not None and v.shape[0] > 0
                  and v.shape[0] % dp_size == 0):
                # BuildStrategy.ReduceStrategy.Reduce: ZeRO-style sharding
                # of optimizer accumulators over the dp axis (ref
                # details/reduce_op_handle.cc parameter-partition mode).
                # GSPMD keeps the state resident-sharded and inserts the
                # gathers the update computation needs.
                param_shardings[v.name] = NamedSharding(
                    mesh, P(*([dp_axis] + [None] * (len(v.shape) - 1))))
        repl = NamedSharding(mesh, P())

        state_shard = {n: param_shardings.get(n, repl) for n in state_in_names}

        # sequence-parallel feeds: axis 1 of [B,S,...] sequence feeds -> sp
        # (ring-attention-style context sharding; GSPMD all-gathers where an
        # op needs the full sequence). Callers name the sequence feeds
        # explicitly via with_data_parallel(sequence_feeds=...) — model
        # specs carry them as ``spec.sequence_feeds``. Without them, feeds
        # shard on dp only.
        gb = program.global_block()
        sp_names = set(seq_feeds or ())

        def feed_spec(name):
            if dp_axis is None or dp_axis not in mesh_axes:
                # no data-parallel axis (e.g. a pipeline-only mesh):
                # feeds stay replicated, the engine slices microbatches
                return repl
            shp = gb.var(name).shape if gb.has_var(name) else None
            if shp is None or len(shp) == 0:
                # out-of-program feeds (e.g. a Customized loss cotangent)
                # and scalars have no batch axis to shard
                return repl
            if name in sp_names:
                return NamedSharding(mesh, P(dp_axis, sp_axis))
            return NamedSharding(mesh, P(dp_axis))

        feed_shard = {n: feed_spec(n) for n in feed_names}
        in_shardings = (state_shard, feed_shard, repl)

        # pin state OUTPUT shardings to the input layout: otherwise GSPMD
        # picks per-call layouts for un-annotated state and the next step's
        # cached executable rejects the donated arrays
        produced = set()
        for o in program.global_block().ops:
            produced.update(o.output_arg_names)
        out_state = {n for n in persist_names
                     if n in produced or n in state_in_names}
        out_shardings = (
            tuple(repl for _ in fetch_names),
            {n: param_shardings.get(n, repl) for n in out_state},
            repl)
        return in_shardings, out_shardings

    def _compile(self, program, feed_names, fetch_names, state_in_names,
                 persist_names, mesh, dp_axis, sp_axis, seq_feeds, pp,
                 zero_state, grad_scale, donate_state, placement,
                 donate_feeds=()):
        pp_cfg = None
        if pp is not None:
            pp_axis, pp_boundaries, pp_nmicro = pp
            pp_cfg = {"mesh": mesh, "axis": pp_axis,
                      "boundaries": list(pp_boundaries),
                      "n_micro": pp_nmicro, "feed_names": list(feed_names)}
        # the infer_only narrowing only applies off-mesh: _mesh_shardings
        # sizes its out_shardings for the echoed state dict
        inner = build_step_fn(program, fetch_names, persist_names,
                              pp_cfg=pp_cfg, grad_scale=grad_scale,
                              infer_only=not donate_state and mesh is None)

        def step(state, feed, rng):
            # trace-time: tells the Pallas gates where THIS step runs
            with placed(*placement):
                return inner(state, feed, rng)

        donate = (0,) if donate_state else ()
        extra = _xla_compiler_options()
        about = {"fetch_names": list(fetch_names),
                 "feed_names": list(feed_names),
                 "ops": len(program.global_block().ops),
                 "meshed": mesh is not None}
        if donate_feeds:
            # feeds the caller hands over: an argument of their own, the
            # only way jit can be told to donate them and not the rest
            def step_handed(state, feed, rng, handed):
                return step(state, {**feed, **dict(zip(donate_feeds, handed))},
                            rng)

            return _Variant(
                jax.jit(step_handed, donate_argnums=donate + (3,), **extra),
                None, about, donate_feeds)
        if mesh is None:
            return _Variant(jax.jit(step, donate_argnums=donate, **extra),
                            None, about)
        in_shardings, out_shardings = self._mesh_shardings(
            program, feed_names, fetch_names, state_in_names, persist_names,
            mesh, dp_axis, sp_axis, seq_feeds, zero_state)
        return _Variant(jax.jit(step, donate_argnums=donate,
                                in_shardings=in_shardings,
                                out_shardings=out_shardings, **extra),
                        in_shardings, about)

    def _stage(self, entry, args, ordinal, restaged=False):
        """Trace, lower and compile a variant's step for the arguments of
        this call, each stage once and under its span, keep what ``run``
        calls and ``lowered_hlo_text`` reads, and append the variant's
        compile record."""
        from ..ops import gates, kernel_names

        hits, misses = _cache_events[_CACHE_HIT], _cache_events[_CACHE_MISS]
        t0 = time.perf_counter()
        with obs_trace.span("executor.trace") as sp, \
                gates.collect() as met, \
                kernel_names.collect_traces() as bodies:
            traced = entry.jfn.trace(*args)
            decisions = gates.tally(met)
            kernel_traces = kernel_names.tally_traces(bodies)
            if sp:
                sp.set(gates=decisions, kernel_traces=kernel_traces)
        t1 = time.perf_counter()
        with obs_trace.span("executor.lower"):
            entry.lowered = traced.lower()
        t2 = time.perf_counter()
        with obs_trace.span("executor.backend_compile") as sp:
            entry.compiled = entry.lowered.compile()
            # the persistent cache is asked once for a step: a hit means
            # the executable was loaded, a miss that XLA compiled it, and
            # neither that the cache is off
            cache = ("hit" if _cache_events[_CACHE_HIT] > hits else
                     "miss" if _cache_events[_CACHE_MISS] > misses else "off")
            if sp:
                sp.set(persistent_cache=cache)
        t3 = time.perf_counter()
        memory = entry.compiled.memory_analysis()
        self.compile_records.append(dict(
            entry.about, ordinal=ordinal, restaged=restaged,
            trace_s=t1 - t0, lower_s=t2 - t1, backend_compile_s=t3 - t2,
            persistent_cache=cache, gates=decisions,
            kernel_traces=kernel_traces,
            # bytes of the feeds handed over (``donate_feeds``); the
            # hand-over engages where ``memory["alias_bytes"]`` is no less
            donated_feed_bytes=sum(_nbytes(a) for a in args[3])
            if entry.handed else 0,
            memory=None if memory is None else {
                "temp_bytes": int(memory.temp_size_in_bytes),
                "argument_bytes": int(memory.argument_size_in_bytes),
                "output_bytes": int(memory.output_size_in_bytes),
                "alias_bytes": int(memory.alias_size_in_bytes)}))
