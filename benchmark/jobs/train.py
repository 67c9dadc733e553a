"""The ``train`` job kind: one compiled training step, fed from the host.

Set-up builds ONE object, the program with its optimizer, its compiled step
and its state; gives it the benchmark's seeded weights; drives it through its
first three steps with the window's own call and feed; and hands that same
object to the window. Those three steps are what ``correct`` is decided on:
once the window has closed and the program's state is freed, the plain
reference follows the same three steps from the same weights and batches
(``train_check.py``).

The window: steps are dispatched for ``--seconds`` seconds with at most two
in flight (block on the loss of step k-2 before dispatching step k), each fed
one of ``pool`` distinct host batches as numpy through ``exe.run(feed=...)``;
then the last loss is awaited and the clock stops. The rate counts the
samples of steps that completed, over all the time of the window.
"""

import gc
import os
import shutil
import time

import numpy as np

from benchmark import harness, seeded
from benchmark.jobs import train_check

CHECK_STEPS = 3
PROFILE_STEPS = 5


def build_program(run):
    """The configuration's program with its optimizer, not yet run. Returns
    (fluid, main, startup, loss, optimizer module, builder arguments)."""
    import paddle_tpu as fluid

    cfg = run.config
    optimizer = harness.load_module(os.path.join(
        harness.HERE, "optimizers", cfg["optimizer"]["name"] + ".py"))
    module, function = cfg["builder"].split(":")
    builder = getattr(__import__(module, fromlist=[function]), function)
    args = dict(cfg["builder_args"])
    for arg, size in cfg.get("builder_sizes", {}).items():
        args[arg] = run.traffic["sizes"][size]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seeded.PROGRAM_SEED
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        spec = builder(**args)
        opt = optimizer.build(fluid, cfg["optimizer"])
        if cfg.get("amp"):
            opt = fluid.amp.decorate(opt)
        opt.minimize(spec.loss)
    return fluid, main, startup, spec.loss, optimizer, args


def weight_specs(main, cfg):
    """[(name, shape, init kind)] of the trainable parameters, in the order
    the builder created them."""
    return [(p.name, p.shape, seeded.init_kind(p.name, cfg["init"]))
            for p in main.global_block().all_parameters() if p.trainable]


def leaf_sizes(specs):
    return {name: int(np.prod(shape)) for name, shape, _ in specs}


class Trainer:
    """The one object: program, executor, scope, host batches, the call."""

    def __init__(self, run):
        self.run = run
        cfg, traffic = run.config, run.traffic
        self.sizes = dict(traffic["sizes"])
        (fluid, self.main, self.startup, self.loss, self.optimizer,
         self.builder_args) = build_program(run)
        self.hp = cfg["optimizer"]

        on_tpu = run.devices[0].platform == "tpu"
        place = fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace()
        self.exe = fluid.Executor(place)
        self.target = self.main
        if traffic.get("mesh") == "data_parallel":
            self.target = fluid.CompiledProgram(self.main).with_data_parallel(
                loss_name=self.loss.name, places=list(run.devices))
        elif traffic.get("mesh"):
            raise KeyError("unknown mesh %r" % traffic["mesh"])
        self.scope = fluid.Scope()
        t0 = time.perf_counter()
        self.exe.run(self.startup, scope=self.scope)
        print("set-up: startup program run in %.1f s"
              % (time.perf_counter() - t0))

        # the benchmark's weights take the place of the startup program's
        specs = weight_specs(self.main, cfg)
        self.param_names = [name for name, _, _ in specs]
        self.param_sizes = leaf_sizes(specs)
        self.weights_fn = seeded.make_weights_fn(specs, run.seed)
        t0 = time.perf_counter()
        for name, value in self.weights_fn().items():
            self.scope.set(name, value)
        print("set-up: seeded weights made in %.1f s"
              % (time.perf_counter() - t0))

        self.batches = seeded.make_batches(
            cfg["feeds"], self.sizes, run.seed, int(traffic["pool"]))
        per_row = cfg["samples_per_row"]
        self.samples_per_step = int(self.sizes["batch"]) * int(
            self.sizes[per_row] if isinstance(per_row, str) else per_row)
        self.steps_done = 0

    def step(self):
        """Dispatch one step (the call a Fluid user's loop makes); returns
        the loss, still on the device."""
        feed = self.batches[self.steps_done % len(self.batches)]
        self.steps_done += 1
        loss, = self.exe.run(self.target, feed=feed, fetch_list=[self.loss],
                             scope=self.scope, return_numpy=False)
        return loss

    def state(self, names):
        return {n: self.scope.get(n) for n in names}

    def free(self):
        """Drop the program's state so that the reference has the chip."""
        for name in list(self.scope.var_names()):
            self.scope.drop(name)
        self.exe.close()
        self.batches = None
        gc.collect()


def first_steps(trainer):
    """Drive the first CHECK_STEPS steps and read what the check compares:
    each loss, the norms of the first gradient as the optimizer got it (from
    its state after one step), the norms of the parameters' change."""
    import jax
    import jax.numpy as jnp

    suffix, factor = trainer.optimizer.first_gradient(trainer.hp)
    names = trainer.param_names

    @jax.jit
    def gradient_norms(state):
        return {n: jnp.linalg.norm(state[n + suffix].astype(
            jnp.float32).ravel()) * factor for n in names}

    @jax.jit
    def change_norms(params, start):
        return {n: jnp.linalg.norm((params[n] - start[n]).astype(
            jnp.float32).ravel()) for n in names}

    t0 = time.perf_counter()
    losses = [float(np.asarray(trainer.step()))]
    first_step_s = time.perf_counter() - t0
    grads = gradient_norms(trainer.state([n + suffix for n in names]))
    grads = {n: float(v) for n, v in grads.items()}
    for _ in range(CHECK_STEPS - 1):
        losses.append(float(np.asarray(trainer.step())))
    change = change_norms(trainer.state(names), trainer.weights_fn())
    change = {n: float(v) for n, v in change.items()}
    return {"losses": losses, "grad_norms": grads,
            "change_norms": change}, first_step_s


def window(trainer, seconds, spans):
    """The measured window. Returns (steps completed, failed, elapsed)."""
    in_flight = int(trainer.run.traffic["in_flight"])
    pending = []
    done = failed = 0

    def settle(loss):
        nonlocal done, failed
        if np.isfinite(float(np.asarray(loss))):
            done += 1
        else:
            failed += 1

    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        if len(pending) >= in_flight:
            settle(pending.pop(0))
        t = time.perf_counter()
        try:
            pending.append(trainer.step())
        except Exception as e:  # a step that raises is a failed step
            failed += 1
            print("step raised: %r" % (e,))
        spans.add("exe_run", t, time.perf_counter())
    for loss in pending:
        settle(loss)
    return done, failed, time.perf_counter() - start


def run(run):
    import warnings

    run.claim_devices()
    warnings.filterwarnings("ignore", message=".*int64.*")
    import jax

    t0 = time.perf_counter()
    trainer = Trainer(run)
    print("set-up: program built, startup run, weights and batches made "
          "in %.1f s" % (time.perf_counter() - t0))
    t0 = time.perf_counter()
    observed, first_step_s = first_steps(trainer)
    print("set-up: first %d steps in %.1f s (the first: %.1f s)"
          % (CHECK_STEPS, time.perf_counter() - t0, first_step_s))

    compiles_before = run.compiles.count
    setup_s = time.time() - run.process_start
    seconds = run.seconds / 2 if run.trace else run.seconds
    done, failed, elapsed = window(trainer, seconds, run.spans)
    compiled_inside = run.compiles.count - compiles_before
    if compiled_inside:
        raise RuntimeError("%d compilation(s) inside the measured window"
                           % compiled_inside)
    rate = done * trainer.samples_per_step / elapsed
    print("window: %d steps completed, %d failed, %.3f s, %d samples a step"
          % (done, failed, elapsed, trainer.samples_per_step))

    result = {"attempted": done + failed, "failed": failed,
              "metrics": {"train_samples_per_s": rate, "setup_s": setup_s}}
    ctx = None
    if run.trace:
        trace_dir = run.path("benchmark_out", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        steps = []

        def body():
            for _ in range(PROFILE_STEPS):
                with jax.profiler.TraceAnnotation("bench.exe_run"):
                    steps.append(trainer.step())
                if len(steps) >= 2:
                    with jax.profiler.TraceAnnotation("bench.wait_loss"):
                        np.asarray(steps[-2])
            with jax.profiler.TraceAnnotation("bench.wait_loss"):
                np.asarray(steps[-1])

        xplane = harness.profile(trace_dir, body)
        ctx = {"trainer": trainer, "rate": rate,
               "first_step_s": first_step_s}

    result["memory_peak_bytes"] = run.memory_peak_bytes()
    if ctx is not None:
        from benchmark import trace_reduce

        # the device's events carry no scope: it is looked up in the
        # compiled step's HLO text by the instruction's name
        t0 = time.perf_counter()
        hlo_text = ctx["hlo_text"] = trainer.exe.lowered_hlo_text(
            optimized=True)
        print("compiled HLO text read back in %.1f s"
              % (time.perf_counter() - t0))
        ctx["trace"] = trace_reduce.load(xplane, len(run.devices),
                                         PROFILE_STEPS, hlo_text)
        result["metrics"] = run.read_layer_metrics(ctx)
        result["busy_s"] = ctx["trace"].busy_s
        result["window_s"] = ctx["trace"].window_s
        result["breakdown"] = ctx["trace"].breakdown()
        for scope, ms in ctx["trace"].ms_a_step_by_op_type()[:16]:
            print("device time by op scope  %-28s %8.3f ms a step"
                  % (scope, ms))

    # the reference follows the same three steps, after the window, with the
    # program's state gone from the chip; its time is in no metric
    weights_fn, batches = trainer.weights_fn, trainer.batches[:CHECK_STEPS]
    args, sizes = trainer.builder_args, trainer.param_sizes
    trainer.free()
    t0 = time.perf_counter()
    expected = train_check.follow(run, weights_fn, batches, args, "exact")
    rows = train_check.compare(observed, expected, run.config["limits"],
                               sizes)
    train_check.dump(run, "program", observed, expected)
    print("reference: %d steps followed in %.1f s" % (
        CHECK_STEPS, time.perf_counter() - t0))
    result["compared"] = rows
    result["correct"] = all(r["ok"] for r in rows) and failed == 0 \
        and done > 0
    return result
