"""Where a computation runs is decided by its place and its mesh, never
guessed from ``jax.devices()``: places, the trace-time placement the Pallas
gates read, one process per chip, the compile cache's directory, and the
entry point that refuses to run without a TPU."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import op_registry
from paddle_tpu.distributed import launch
from paddle_tpu.ops import gates

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    """Run a repo entry point in a child held to the CPU (unless ``env``
    says otherwise)."""
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=_REPO, env=full_env,
                          capture_output=True, text=True, timeout=300)


# -- places -----------------------------------------------------------------

def test_tpu_place_raises_without_tpu():
    with pytest.raises(RuntimeError, match="no tpu device"):
        fluid.TPUPlace(0)


@pytest.mark.parametrize("place,device", [
    (fluid.CPUPlace(), jax.devices("cpu")[0]),
    (fluid.CPUPlace(3), jax.devices("cpu")[3]),
    (fluid.XLAPlace(0), jax.devices()[0]),
    (fluid.CUDAPlace(0), jax.devices()[0]),
])
def test_place_names_its_device(place, device):
    assert place.jax_device() == device


def test_place_out_of_range_raises():
    with pytest.raises(RuntimeError, match="8 such device"):
        fluid.CPUPlace(8).jax_device()


def test_executor_state_lives_on_its_place():
    """The scope's arrays land on the place's device, not on device 0."""
    x = fluid.layers.data("x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(x, size=3))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace(2))
    exe.run(fluid.default_startup_program())
    exe.run(feed={"x": np.ones((2, 4), "f4")}, fetch_list=[loss])
    scope = fluid.global_scope()
    want = {jax.devices("cpu")[2]}
    for p in fluid.default_main_program().global_block().all_parameters():
        assert scope.get(p.name).devices() == want, p.name


def test_serving_engine_devices_take_places(tmp_path):
    """``ServingEngine(devices=[places])`` pins each replica where its
    place says, not on the default device."""
    from paddle_tpu.serving import ServingEngine

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        prob = fluid.layers.softmax(fluid.layers.fc(
            x, size=3, param_attr=fluid.ParamAttr(name="pl_fc.w")))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        model_dir = str(tmp_path / "model")
        fluid.io.save_inference_model(model_dir, ["x"], [prob], exe,
                                      main_program=main)
    eng = ServingEngine(model_dir, num_replicas=2, ladder=(1, 2),
                        placement="per_device",
                        devices=[fluid.CPUPlace(5), fluid.CPUPlace(6)])
    try:
        eng.predict({"x": np.ones((1, 4), "f4")}, timeout_s=60.0)
        devs = [next(iter(w.predictor._scope.get("pl_fc.w").devices()))
                for w in eng._workers]
    finally:
        eng.shutdown()
    assert devs == [jax.devices("cpu")[5], jax.devices("cpu")[6]]


# -- the placement the gates read -------------------------------------------

def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jax.numpy.bfloat16)


def _flash_attention_gate():
    from paddle_tpu.ops import flash_attention as fa

    return fa.plan_for(_sds(2, 128, 64), _sds(2, 128, 64), None, 4, False,
                       0.0, None)


def _fused_conv_gate():
    from paddle_tpu.ops import fused_conv

    return fused_conv.gate((2, 8, 8, 8), (8, 8, 1, 1), (1, 1), (0, 0),
                           (1, 1), 1, 2, False)


def _fused_ce_gate():
    from paddle_tpu.ops import fused_ce

    # 2.1e9 logits: past the size threshold, and never made
    return fused_ce._use_fused(_sds(65536, 8), _sds(8, 32768))


def _scatter_gate():
    from paddle_tpu.ops import scatter

    return scatter.gate(1000, 16, 1024, "float32")


def _gated_delta_gate():
    from paddle_tpu.ops import gated_delta

    return gated_delta.plan_for(_sds(1, 128, 2 * 128), _sds(1, 128, 4 * 128),
                                2, 4, 64)


@pytest.mark.parametrize("placement,one_chip", [
    (("cpu",), False), (("tpu",), True), (("tpu", True), False)],
    ids=["cpu", "one_tpu_chip", "tpu_under_a_mesh"])
@pytest.mark.parametrize("family", [
    _flash_attention_gate, _fused_conv_gate, _fused_ce_gate, _scatter_gate,
    _gated_delta_gate], ids=lambda f: f.__name__[1:-5])
def test_gates_follow_the_declared_placement(family, placement, one_chip):
    """One TPU chip gets its kernels whatever the host holds; a meshed
    step and a CPU step do not, and all that stands in the way of a shape
    the family admits is ``gates``' own 'platform' reason."""
    with gates.placed(*placement):
        assert gates.single_tpu() == one_chip
        decision = family()
        own = gates.platform_reason()
    assert decision.admitted == one_chip, decision
    if one_chip:
        assert own is None and not decision.blocking_reasons
    else:
        assert [r.to_dict() for r in decision.blocking_reasons] == [
            own.to_dict()], decision
        assert ("mesh" in own.detail) == (len(placement) == 2)
    assert gates.placed_platform() == "cpu"  # this process; restored


def test_executor_declares_placement_while_tracing():
    """The step traced by a CPUPlace executor sees 'cpu, not meshed'; the
    same program under with_data_parallel sees 'meshed'."""
    seen = []

    @op_registry.register("_probe_placement")
    def _probe(env, op):
        seen.append((gates.placed_platform(), gates.PLACEMENT.meshed))
        op_registry.put(env, op.output("Out"),
                        op_registry.get(env, op.input("X")))

    try:
        x = fluid.layers.data("x", shape=[4])
        block = fluid.default_main_program().global_block()
        out = block.create_var(name="probe_out", shape=[-1, 4],
                               dtype="float32")
        block.append_op("_probe_placement", {"X": x}, {"Out": out}, {})
        exe = fluid.Executor(fluid.CPUPlace())
        feed = {"x": np.ones((8, 4), "f4")}
        exe.run(feed=feed, fetch_list=[out])
        compiled = fluid.CompiledProgram(
            fluid.default_main_program()).with_data_parallel()
        exe.run(compiled, feed=feed, fetch_list=[out])
    finally:
        del op_registry.OP_IMPLS["_probe_placement"]
    assert seen == [("cpu", False), ("cpu", True)]


# -- one owner, and no switch beside it --------------------------------------

def _sources(*parts):
    root = os.path.join(_REPO, *parts)
    for folder, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".pyc"):
                with open(os.path.join(folder, name), errors="replace") as f:
                    yield os.path.relpath(f.name, _REPO), f.read()


def test_environment_variables_are_deployment_settings_only():
    """The ``PADDLE_TPU_*`` names under ``paddle_tpu/`` are paths, fault
    plans, tracing, verification and ready lines: none selects a
    performance path (the code chooses from shapes and placement)."""
    import re

    names = set()
    for _, text in _sources("paddle_tpu"):
        names.update(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", text))
    assert names == {"PADDLE_TPU_" + n for n in (
        "CACHE", "COMPILE_CACHE_BUDGET", "DATASET_DOWNLOAD", "DATA_HOME",
        "DECODE_MAX_NEW", "PREFIX_CACHE_MB", "FAULTS", "FLIGHT", "TRACE",
        "VERIFY", "XLA_OPTIONS", "ROUTER_READY", "TRAINER_READY",
        "WORKER_READY")}


def test_ops_ask_gates_where_they_run_and_nothing_above_them():
    """``ops/`` is the lowest layer: placement lives in ``ops/gates.py``,
    and no module there reaches up into ``paddle_tpu.core`` for it or for
    a switch."""
    import ast

    for attr in ("placed", "single_tpu", "placement_reason", "PLACEMENT",
                 "placed_platform"):
        assert not hasattr(op_registry, attr), attr
    reached = []
    for path, text in _sources("paddle_tpu", "ops"):
        if not path.endswith(".py"):
            continue
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.ImportFrom) and (
                    (node.level == 2 and (node.module or "").startswith(
                        "core")) or (node.module or "").startswith(
                        "paddle_tpu.core")):
                reached += [(path, a.name) for a in node.names]
    assert not {n for _, n in reached} & {
        "placed", "single_tpu", "placement_reason", "placed_platform",
        "PLACEMENT", "env_flag"}, reached
    # what is left is held by name in tests/test_layering.py (ROADMAP D23)


# -- one process for each chip ----------------------------------------------

@pytest.mark.parametrize("chips,n_procs,env,want", [
    (0, 4, {}, {}),                              # no chips: nothing to do
    (4, 1, {}, {}),                              # one child drives them all
    (4, 4, {"JAX_PLATFORMS": "cpu"}, {}),        # children held to the CPU
    (4, 2, {}, {"TPU_VISIBLE_CHIPS": "1",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}),
])
def test_one_chip_env(monkeypatch, chips, n_procs, env, want):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: chips)
    assert launch.one_chip_env(1, n_procs, env) == want


def test_one_chip_env_refuses_more_children_than_chips(monkeypatch):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 1)
    with pytest.raises(RuntimeError, match="this host has 1"):
        launch.one_chip_env(0, 2, {})


def test_launch_refuses_many_local_processes_on_a_tpu_host(monkeypatch):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="one process drives all 4"):
        launch.launch(["--nproc_per_node=2", "train.py"])


# -- the compile cache is placed from outside -------------------------------

@pytest.mark.parametrize("env,want", [
    ({"JAX_PLATFORMS": ""}, os.path.join(_REPO, ".jax_cache")),
    ({"JAX_PLATFORMS": "", "JAX_COMPILATION_CACHE_DIR": "/tmp/some/cache"},
     "/tmp/some/cache"),
    ({"JAX_PLATFORMS": "cpu"}, "None"),  # a CPU-held process: no default
], ids=["default", "placed_from_outside", "cpu_held"])
def test_compile_cache_directory(env, want):
    # importing the package initialises no backend, so the child needs no
    # device for the platforms it is (not) held to
    out = _run(["-c", "import paddle_tpu.compile_cache as c; "
                      "print(c.directory())"], **env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want


# -- the entry point that needs the chip says so ----------------------------

def test_chip_smoke_refuses_to_run_without_a_tpu():
    out = _run(["chip_smoke.py"])
    assert out.returncode not in (0, None)
    assert out.stdout == ""  # no result line that could be read as a run
    assert '"ok": false' in out.stderr
