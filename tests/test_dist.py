"""Multi-process distributed training tests (SURVEY.md §4 item d — the
``test_dist_base.py`` analog: spawn localhost jax.distributed processes and
compare losses against the single-process run)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import models

_DIR = os.path.dirname(os.path.abspath(__file__))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn_cluster(nproc=2, steps=4, devs_per_proc=2, model="mlp",
                   return_outs=False):
    """Run dist_runner.py in nproc clean-env subprocesses."""
    port = _free_port()
    env = {
        "PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", ""),
        # a clean env: each worker is a CPU process with its own devices
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=%d"
                     % devs_per_proc,
    }
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(_DIR, "dist_runner.py"),
             str(i), str(nproc), str(port), str(steps), model],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(nproc)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
        assert p.returncode == 0, out[-3000:]
    losses = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("DIST_LOSSES ")]
        assert line, out[-3000:]
        losses.append(json.loads(line[0][len("DIST_LOSSES "):]))
    if return_outs:
        return losses, outs
    return losses


def _single_process_losses(steps=4, n_devices=4, model="mlp"):
    import jax
    from jax.sharding import Mesh
    from dist_runner import build_model

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1234
    scope = fluid.Scope()
    with fluid.program_guard(main_p, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        spec, batch = build_model(model, fluid, models)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if model == "mlp":
            mesh = Mesh(np.array(jax.devices()[:n_devices]), ("dp",))
        else:
            mesh = Mesh(np.array(jax.devices()[:n_devices]).reshape(2, 2),
                        ("dp", "mp"))
        cp = fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=spec.loss.name, mesh=mesh)
        losses = []
        for _ in range(steps):
            lv, = exe.run(cp, feed=batch, fetch_list=[spec.loss])
            losses.append(float(lv))
    return losses


@pytest.mark.slow
def test_two_process_dp_matches_single_process():
    """2 processes x 2 devices must converge like 1 process x 4 devices on
    the same global batch (the reference's dist-vs-local criterion)."""
    cluster = _spawn_cluster(nproc=2, steps=4)
    # both trainers see the same (replicated-loss) values
    np.testing.assert_allclose(cluster[0], cluster[1], rtol=1e-5)
    single = _single_process_losses(steps=4)
    np.testing.assert_allclose(cluster[0], single, rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_two_process_transformer_dp_mp():
    """Multi-host transformer on a (dp=2 procs, mp=2 local devs) mesh:
    megatron-sharded FFN/attention weights span each host's ICI while the
    batch splits across hosts over DCN."""
    cluster = _spawn_cluster(nproc=2, steps=3, model="transformer")
    np.testing.assert_allclose(cluster[0], cluster[1], rtol=1e-5)
    single = _single_process_losses(steps=3, model="transformer")
    np.testing.assert_allclose(cluster[0], single, rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_two_process_sharded_embedding():
    """Multi-host pserver-analog: the is_distributed table row-shards over
    the mp axis (spec asserted from the workers' actual state arrays);
    training losses match the single-process run."""
    losses, outs = _spawn_cluster(nproc=2, steps=4, model="sharded_emb",
                                  return_outs=True)
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("TABLE_SPEC ")]
        assert line and "mp" in line[0], out[-2000:]
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    single = _single_process_losses(steps=4, model="sharded_emb")
    np.testing.assert_allclose(losses[0], single, rtol=5e-3, atol=5e-3)
