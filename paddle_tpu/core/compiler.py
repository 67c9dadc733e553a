"""CompiledProgram: multi-device execution strategies.

Reference: ``python/paddle/fluid/compiler.py:62`` (CompiledProgram +
``with_data_parallel:116``) wrapping the C++ ParallelExecutor
(``parallel_executor.cc:184``) — SSA graph, NCCL allreduce insertion,
threaded dataflow scheduling. The TPU-native equivalent is declarative:
choose a ``jax.sharding.Mesh`` and shard the batch axis (data parallel)
and/or parameter axes (tensor parallel / sharded "reduce mode"); GSPMD
inserts and schedules the collectives over ICI.

BuildStrategy/ExecutionStrategy are accepted for API parity; the knobs that
have TPU meaning are mapped (reduce_strategy -> parameter sharding a la
ZeRO), the rest are no-ops documented as subsumed by XLA.
"""


__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy"]


class ExecutionStrategy:
    """Accepted for parity (ref ``pybind.cc:1021``); XLA owns scheduling."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1


class BuildStrategy:
    """Ref ``details/build_strategy.h:35-140``. ``reduce_strategy=Reduce``
    shards optimizer accumulators over the dp axis (ZeRO-style; see
    ``executor._mesh_shardings``) — the capability the reference implements
    with ReduceOpHandle parameter-partitioning. Verified by
    ``tests/test_parallel.py::test_zero_reduce_strategy_shards_optimizer_state``."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.memory_optimize = True   # XLA buffer assignment: always on
        self.enable_inplace = True    # buffer donation: always on
        self.fuse_elewise_add_act_ops = True  # XLA fusion: always on
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class CompiledProgram:
    def __init__(self, program):
        self._program = program
        self._mesh = None
        self._dp_axis = None
        self._sp_axis = None
        self._build_strategy = None
        self._exec_strategy = None
        self._seq_feeds = None
        self._pp_axis = None
        self._pp_boundaries = None
        self._pp_nmicro = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None, mesh=None, dp_axis="dp",
                           sp_axis=None, sequence_feeds=None):
        """Shard the batch over a device mesh axis (ref
        ``compiler.py:116``). ``mesh`` defaults to a 1-D mesh over all local
        devices — the analog of ParallelExecutor claiming all visible GPUs.

        ``sequence_feeds``: with ``sp_axis`` set, the feed names whose dim 1
        is the sequence axis to shard — model specs carry them as
        ``spec.sequence_feeds``. With None, feeds shard on dp only."""
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._dp_axis = dp_axis
        self._sp_axis = sp_axis
        self._seq_feeds = (tuple(sorted(sequence_feeds))
                           if sequence_feeds is not None else None)
        self._mesh = mesh
        self._places = places
        return self

    def with_pipeline(self, loss_name=None, mesh=None, pp_axis="pp",
                      boundaries=None, n_microbatches=None):
        """Pipeline-parallel training over ``mesh``'s ``pp_axis``.

        The program's forward is split into ``mesh.shape[pp_axis]`` stages
        at the producers of the named ``boundaries`` variables; each device
        runs its stage, microbatches ride a ppermute ring, and the backward
        (via the program's autodiff op) follows the GPipe reverse schedule.
        New TPU-first capability — the 2019 reference has no pipeline
        engine (SURVEY §2.5D); contrast ``pipeline_apply`` for the raw
        homogeneous-stack form.

        Per-microbatch losses are averaged (the data-parallel convention).
        Fetching forward activations other than the loss falls back to a
        replicated recompute of those ops. ``n_microbatches`` defaults to
        the number of stages."""
        if not boundaries:
            raise ValueError("with_pipeline requires boundaries: the "
                             "activation var names to cut stages at")
        self._pp_axis = pp_axis
        self._pp_boundaries = tuple(
            b.name if hasattr(b, "name") else str(b) for b in boundaries)
        self._pp_nmicro = n_microbatches
        self._mesh = mesh
        self._places = None
        return self

    def with_inference_optimize(self, config=None):
        # analysis passes are subsumed by XLA; keep chainable API
        return self

    def _resolve_mesh(self, place):
        """The mesh given, else a 1-D mesh over ``places``, else over
        every device of the executor's ``place`` backend (a TPUPlace
        executor never spreads over CPU devices)."""
        if self._mesh is not None:
            return self._mesh
        from jax.sharding import Mesh
        import numpy as np
        from .executor import as_jax_devices

        devices = (as_jax_devices(self._places) if self._places
                   else place.jax_devices())
        axis = self._pp_axis or self._dp_axis or "dp"
        self._mesh = Mesh(np.array(devices), (axis,))
        return self._mesh
