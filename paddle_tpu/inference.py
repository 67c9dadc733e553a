"""Inference predictor stack.

Reference: ``paddle/fluid/inference/api/analysis_predictor.cc:183``
(AnalysisPredictor::Init) + ``:734`` (Run), configured by
``paddle_inference_api.h`` AnalysisConfig, exposed in Python as
``fluid.core.AnalysisConfig`` / ``create_paddle_predictor``.

TPU-native re-design: the analysis pass stack (IR optimization, fusion,
TensorRT/MKLDNN subgraphs, memory optimization) is subsumed by XLA
compilation of the whole pruned program — the predictor's job is model
loading, an isolated scope, a warm shape-keyed jit cache (the Executor's
program cache), and zero-copy device-resident feeds (jax.Array passthrough).

Placement (the serving tier's mesh story, ISSUE 14):

  * ``clone(device=...)`` pins a replica to ONE device: the clone gets its
    own scope with every weight ``jax.device_put`` onto that device, so
    jit dispatch (which follows committed inputs) runs there — the
    ``ServingEngine(placement="per_device")`` building block.
  * ``shard(mesh)`` returns a tensor-parallel predictor: weights are laid
    out per their ``ParamAttr(sharding=...)`` annotations over the mesh
    (axes absent from the mesh degrade to replication, the
    ``executor._mesh_shardings`` rule), and runs go through
    ``CompiledProgram`` so GSPMD inserts the collectives. Models bigger
    than one chip's HBM serve through the same ``run`` API.
"""

import os

from . import io as io_mod
from .core.executor import Executor, Scope, scope_guard, XLAPlace

__all__ = ["AnalysisConfig", "Predictor", "ProgramPredictor",
           "create_paddle_predictor", "StableHLOPredictor",
           "load_stablehlo_predictor"]


class AnalysisConfig:
    """Parity shim for the reference AnalysisConfig.

    INERT KNOBS — read this before tuning: ``enable_use_gpu``,
    ``disable_gpu``, ``switch_ir_optim`` and ``enable_memory_optim`` are
    recorded but change NOTHING on TPU. The reference's analysis passes
    (IR fusion, TensorRT/MKLDNN subgraphs, memory reuse) are subsumed by
    XLA compiling the whole pruned program; execution always targets the
    XLA default device. Only the model paths and ``enable_int8`` act."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.params_file = params_file
        self._use_gpu = False
        self._mem_optim = True
        self._ir_optim = True
        self._int8 = None  # None = auto-detect params.int8.npz in the dir

    # -- reference-API surface (no-op on TPU, XLA subsumes) -----------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_gpu = True  # accepted; execution targets the XLA device

    def disable_gpu(self):
        self._use_gpu = False

    def switch_ir_optim(self, x=True):
        self._ir_optim = bool(x)

    def enable_memory_optim(self, x=True):
        self._mem_optim = bool(x)

    def enable_int8(self, x=True):
        """Serve from the ``contrib.quantize`` int8 export
        (``params.int8.npz`` beside the model): dequantized onto the
        quantization grid at load. ``True`` requires the export, ``False``
        forces fp32, the default ``None`` auto-detects."""
        self._int8 = bool(x)

    def set_model(self, model_dir):
        self.model_dir = model_dir


class ProgramPredictor:
    """Predictor over an already-built (program, scope) pair — the
    in-process serving adapter for programs constructed in this very
    process (decode step programs, test programs), with no save/load
    round trip. ``Predictor`` subclasses it with the model-dir loading
    front end; everything below ``run`` is shared."""

    def __init__(self, program, feed_names, fetch_vars, scope=None):
        self.config = None
        self._scope = scope if scope is not None else Scope()
        self._exe = Executor(XLAPlace(0))
        self._program = program
        self._compiled = None
        self.feed_names = list(feed_names)
        self._fetch_vars = list(fetch_vars)
        self.fetch_names = [v.name if hasattr(v, "name") else str(v)
                            for v in fetch_vars]

    def run(self, inputs, return_numpy=True, donate_feeds=()):
        """``inputs``: dict name->array, or a list/tuple in feed order.
        Returns outputs in fetch order. ``donate_feeds`` names the inputs
        the caller hands over for good (``Executor.run``): arrays of the
        caller's own that it will not read again, such as a decode loop's
        carried caches; after the call they are deleted and what the
        program made of them is among the outputs."""
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != len(self.feed_names):
                raise ValueError("expected %d inputs (%s), got %d"
                                 % (len(self.feed_names), self.feed_names,
                                    len(inputs)))
            feed = dict(zip(self.feed_names, inputs))
        else:
            feed = dict(inputs)
            missing = set(self.feed_names) - set(feed)
            if missing:
                raise ValueError("missing feeds: %s" % sorted(missing))
        # scope passed explicitly (not via the global scope_guard stack):
        # clones serving concurrently from other threads must not race on
        # process-global scope resolution. donate_state=False for the same
        # reason: the weights are the SCOPE's, which clones share, and
        # donating them would invalidate them mid-call, a use-after-free
        # when another clone reads them. A handed-over feed is the
        # caller's own array and nobody else's, so donating it is safe
        # whatever else serves from this scope.
        return self._exe.run(self._compiled if self._compiled is not None
                             else self._program, feed=feed,
                             fetch_list=self._fetch_vars,
                             scope=self._scope,
                             return_numpy=return_numpy,
                             donate_state=False,
                             donate_feeds=donate_feeds)

    predict = run

    def stage(self, inputs, donate_feeds=()):
        """Make the executable that ``run(inputs, donate_feeds=...)`` would
        use, and run nothing (``Executor.stage``): ``inputs`` is a dict whose
        values may be ``jax.ShapeDtypeStruct``s in the arrays' places."""
        self._exe.stage(self._compiled if self._compiled is not None
                        else self._program, feed=dict(inputs),
                        fetch_list=self._fetch_vars, scope=self._scope,
                        donate_state=False, donate_feeds=donate_feeds)

    def fetch_argmax(self, fetch_name):
        """Ask for one more fetch: the ``argmax`` over the last axis of the
        fetch called ``fetch_name`` (int32, the first maximum, as
        ``np.argmax`` takes it), computed inside the same executable.
        Returns the new fetch's name; asked again, or of a clone that shares
        the program, it gives the same one. The op is appended to the
        program this predictor holds, so ask before the first ``run`` or
        ``stage``: a variant made before lacks the fetch and is made again.
        A decode loop asks this of its step predictor and reads ``[b]`` ids
        where it would copy ``[b, vocabulary]`` logits to the host."""
        name = fetch_name + "@argmax"
        if name not in self.fetch_names:
            block = self._program.global_block()
            if not block.has_var(name):
                x = block.var(fetch_name)
                out = block.create_var(
                    name=name, dtype="int32", stop_gradient=True,
                    shape=None if x.shape is None else x.shape[:-1])
                block.append_op("argmax", {"X": x}, {"Out": out},
                                {"axis": -1})
            # new lists: a clone shares the old ones
            self._fetch_vars = self._fetch_vars + [block.var(name)]
            self.fetch_names = self.fetch_names + [name]
        return name

    def clone(self, device=None):
        """A predictor sharing this one's weights (ref
        ``AnalysisPredictor::Clone``): same scope/program, fresh exe cache.

        ``device``: pin the clone to one jax device — its scope becomes a
        COPY with every array ``device_put`` there (weights no longer
        shared with the parent; jit dispatch follows the committed
        arrays). The ``placement="per_device"`` replica constructor."""
        other = object.__new__(type(self))
        other.config = self.config
        other._scope = self._scope
        other._exe = Executor(XLAPlace(0))
        other._program = self._program
        other._compiled = self._compiled
        other.feed_names = list(self.feed_names)
        other._fetch_vars = self._fetch_vars
        other.fetch_names = list(self.fetch_names)
        if hasattr(self, "int8"):  # Predictor subclass advertises it
            other.int8 = self.int8
        if device is not None:
            import jax

            pinned = Scope()
            for name in self._scope.var_names():
                if name.startswith("@"):
                    continue  # RNG key re-seeds per scope
                pinned.set(name,
                           jax.device_put(self._scope.get(name), device))
            other._scope = pinned
        return other

    def shard(self, mesh, dp_axis="dp"):
        """A tensor-parallel predictor over ``mesh``: every annotated
        weight (``ParamAttr(sharding=...)``) is laid out per its spec
        (axes absent from the mesh degrade to replication), the rest
        replicate, and runs compile through ``CompiledProgram`` so GSPMD
        inserts the collectives. Weights are placed ONCE here — with the
        same NamedShardings the Executor derives — so the per-call jit
        never re-ships them. Feeds stay replicated unless the mesh has a
        ``dp_axis`` axis (the serving default: mp-only mesh)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .core.compiler import CompiledProgram

        mesh_axes = set(mesh.axis_names)
        gb = self._program.global_block()
        sharded = Scope()
        for name in self._scope.var_names():
            if name.startswith("@"):
                continue
            var = gb.vars.get(name)
            spec = getattr(var, "sharding", None) if var is not None \
                else None
            if spec is not None:
                spec = P(*[a if a in mesh_axes else None for a in spec])
            else:
                spec = P()
            sharded.set(name, jax.device_put(
                self._scope.get(name), NamedSharding(mesh, spec)))
        other = self.clone()
        other._scope = sharded
        other._exe = Executor(XLAPlace(0))
        other._compiled = CompiledProgram(self._program).with_data_parallel(
            mesh=mesh, dp_axis=dp_axis)
        return other

    def get_input_names(self):
        return list(self.feed_names)

    def get_output_names(self):
        return list(self.fetch_names)


class Predictor(ProgramPredictor):
    """Loads a saved inference model into an isolated scope and serves
    ``run``/``predict`` with a warm compile cache.

    Ref ``analysis_predictor.cc``: Init loads + optimizes the program once;
    Run executes with feed/fetch binding. Here the first call per feed-shape
    compiles (XLA) and subsequent calls hit the Executor's program cache."""

    def __init__(self, config):
        if isinstance(config, str):
            config = AnalysisConfig(model_dir=config)
        scope = Scope()
        exe = Executor(XLAPlace(0))
        model_dir = config.model_dir
        # combined-file form (ref SetModel(prog_file, params_file)): the
        # directory comes from the file paths, which must agree
        for fp in (config.prog_file, config.params_file):
            if fp is None:
                continue
            d = os.path.dirname(os.path.abspath(fp))
            if model_dir is None:
                model_dir = d
            elif os.path.abspath(model_dir) != d:
                raise ValueError(
                    "AnalysisConfig: %r is not inside model_dir %r"
                    % (fp, model_dir))
        if model_dir is None:
            raise ValueError("AnalysisConfig needs model_dir (the "
                             "save_inference_model output directory) or "
                             "prog_file/params_file paths")
        with scope_guard(scope):
            prog, feed_names, fetch_vars = io_mod.load_inference_model(
                model_dir, exe,
                model_filename=(os.path.basename(config.prog_file)
                                if config.prog_file else None),
                params_filename=(os.path.basename(config.params_file)
                                 if config.params_file else None))
        ProgramPredictor.__init__(self, prog, feed_names, fetch_vars,
                                  scope=scope)
        self.config = config
        self._exe = exe
        # int8 serving path: the contrib.quantize export, dequantized onto
        # the quantization grid at load (flag or auto-detect on the dir)
        self.int8 = False
        if config._int8 is not False:
            from .contrib.quantize.quantize_transpiler import \
                load_int8_params

            loaded = load_int8_params(model_dir, scope,
                                      require=config._int8 is True)
            self.int8 = bool(loaded)


def create_paddle_predictor(config):
    """Factory-name parity with the reference C-API."""
    return Predictor(config)


class StableHLOPredictor:
    """Serves from the serialized StableHLO artifact alone — no
    model-building Python, no symbolic program replay (ref parity:
    ``CreatePaddlePredictor`` runs from the serialized program+params,
    ``analysis_predictor.cc:734``). ``save_inference_model`` writes the
    artifact (``model.stablehlo.bin`` via jax.export) next to the params;
    this loader deserializes and executes it.

    Batch-size note: with a symbolic-batch export (manifest
    ``batch_mode: symbolic``) any batch works; a ``pinned-1`` export only
    accepts batch 1."""

    def __init__(self, dirname, params_filename=None):
        import json

        import jax.numpy as jnp
        import numpy as np
        from jax import export as jexport

        with open(os.path.join(dirname, "model.stablehlo.bin"), "rb") as f:
            self._exported = jexport.deserialize(f.read())
        with open(os.path.join(dirname, "stablehlo_manifest.json")) as f:
            man = json.load(f)
        self.feed_names = list(man["feed_names"])
        self.fetch_names = list(man["fetch_names"])
        self.batch_mode = man["batch_mode"]
        params = np.load(os.path.join(dirname,
                                      params_filename or "params.npz"),
                         allow_pickle=False)
        state_names = man["state_names"]
        missing = [n for n in state_names if n not in params]
        if missing:
            raise ValueError("params file lacks exported state vars %s"
                             % missing)
        self._state = {n: jnp.asarray(params[n]) for n in state_names}

    def run(self, inputs, return_numpy=True):
        import jax.numpy as jnp
        import numpy as np

        if isinstance(inputs, (list, tuple)):
            feed = dict(zip(self.feed_names, inputs))
        else:
            feed = dict(inputs)
        missing = set(self.feed_names) - set(feed)
        if missing:
            raise ValueError("missing feeds: %s" % sorted(missing))
        feed = {n: jnp.asarray(feed[n]) for n in self.feed_names}
        out = self._exported.call(self._state, feed)
        return [np.asarray(o) for o in out] if return_numpy else list(out)

    predict = run

    def clone(self, device=None):
        """API parity with ``Predictor.clone()`` (ref
        ``AnalysisPredictor::Clone``) so a replica pool — e.g.
        ``serving.ServingEngine`` — can treat either predictor type
        uniformly. The exported computation and the param arrays are
        immutable, so clones share both; there is no per-clone executor
        cache to refresh (``jax.export``'s ``call`` compiles per shape
        internally). ``device``: pin the clone's state arrays to one
        device (the per_device placement hook)."""
        import jax

        other = object.__new__(StableHLOPredictor)
        other._exported = self._exported
        other._state = self._state
        if device is not None:
            other._state = {n: jax.device_put(a, device)
                            for n, a in self._state.items()}
        other.feed_names = list(self.feed_names)
        other.fetch_names = list(self.fetch_names)
        other.batch_mode = self.batch_mode
        return other

    def get_input_names(self):
        return list(self.feed_names)

    def get_output_names(self):
        return list(self.fetch_names)


def load_stablehlo_predictor(dirname, params_filename=None):
    """Load-and-run from the ``save_inference_model`` StableHLO artifact."""
    return StableHLOPredictor(dirname, params_filename)
