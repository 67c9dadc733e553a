"""Multi-process launcher (ref ``python/paddle/distributed/launch.py``):

    python -m paddle_tpu.distributed.launch --nproc_per_node=2 \\
        [--started_port 6170] [--log_dir logs] \\
        [--max_restarts N --restart_backoff S] train.py [args...]

Spawns one worker per process slot with the PADDLE_TRAINER_* env protocol
(``PADDLE_TRAINER_ID``, ``PADDLE_TRAINER_ENDPOINTS``,
``PADDLE_CURRENT_ENDPOINT``) that ``parallel/env.py:init_distributed``
consumes to form the jax.distributed world. Multi-node: pass
``--cluster_node_ips`` + ``--node_ip`` and run the launcher once per node,
exactly like the reference.

Failure semantics (default): first worker failure terminates the rest and
the launcher exits with that worker's code (the reference's fate-sharing
behavior, which external whole-job restart setups rely on).

Elastic mode (``--max_restarts N``): a worker crash restarts the WHOLE
local group up to N times, with an exponential ``--restart_backoff``
schedule (``paddle_tpu.reliability.RetryPolicy``) between attempts;
state recovery is the workers' job via ``checkpoint.resume_or_init`` /
``AutoCheckpoint`` (SURVEY §5.3 — the pserver ``checkpoint_notify`` +
external-restart analog). Group restart — not restart-in-place of the
one crashed process — because a jax.distributed world is all-or-nothing:
a surviving peer would hang in its next collective waiting for the lost
rank, and a respawned rank cannot rejoin an already-initialized world.
Exhausting the budget falls back to fate-sharing. Worker log files are
flushed/closed before a restart and reopened in append mode so one
``workerlog.<id>`` carries the whole incarnation history; workers can
read ``PADDLE_RESTART_COUNT`` to tell which incarnation they are.

SIGTERM/SIGINT to the launcher are forwarded to the workers and the
workers are reaped — Ctrl-C never orphans the subprocess tree.
"""

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "reap_procs", "local_tpu_chips", "one_chip_env"]


def local_tpu_chips():
    """TPU chips attached to this host, counted from their device files.
    A supervisor must not ask JAX: a chip belongs to one process at a
    time, and a parent that initialises JAX takes the chips its children
    need."""
    import glob

    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def one_chip_env(slot, n_procs, env):
    """Environment overlay that gives local child ``slot`` of ``n_procs``
    INDEPENDENT children its own chip, to be set before the child imports
    JAX (libtpu reads it at start-up). Empty where there is nothing to
    divide: a host without chips, children held to the CPU
    (``JAX_PLATFORMS=cpu`` in ``env``), or one child (it may drive every
    chip). More children than chips is refused: they would all reach for
    the same chips and fail or hang."""
    chips = local_tpu_chips()
    if chips == 0 or n_procs <= 1 or env.get("JAX_PLATFORMS") == "cpu":
        return {}
    if n_procs > chips:
        raise RuntimeError(
            "%d processes need a TPU chip each, this host has %d"
            % (n_procs, chips))
    return {"TPU_VISIBLE_CHIPS": str(slot),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def reap_procs(procs, sig=signal.SIGTERM, grace_s=10.0):
    """Signal every live ``Popen`` in ``procs`` and wait it out;
    stragglers get SIGKILL. The one way any supervisor here (this
    launcher, ``serving.router.Router``) ends a child — never orphan
    the subprocess tree, never wait unboundedly."""
    live = [p for p in procs if p is not None and p.poll() is None]
    for p in live:
        try:
            p.send_signal(sig)
        except OSError:
            pass
    deadline = time.time() + grace_s
    for p in live:
        try:
            p.wait(max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            try:
                p.wait(5.0)
            except subprocess.TimeoutExpired:
                pass


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description=__doc__.splitlines()[0])
    ap.add_argument("--nproc_per_node", type=int, default=1)
    ap.add_argument("--cluster_node_ips", type=str, default="127.0.0.1")
    ap.add_argument("--node_ip", type=str, default="127.0.0.1")
    ap.add_argument("--started_port", type=int, default=6170)
    ap.add_argument("--log_dir", type=str, default=None)
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="whole-group crash-restart budget (0 = "
                         "fate-sharing, the default)")
    ap.add_argument("--restart_backoff", type=float, default=1.0,
                    help="base seconds before a group restart "
                         "(doubles per attempt)")
    ap.add_argument("training_script", type=str)
    ap.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return ap.parse_args(argv)


class _Worker:
    """One process slot: its trainer id, live Popen, open log file, and
    which restart incarnation it is on."""

    __slots__ = ("tid", "proc", "log", "restarts")

    def __init__(self, tid):
        self.tid = tid
        self.proc = None
        self.log = None
        self.restarts = 0

    def close_log(self):
        if self.log is not None:
            try:
                self.log.flush()
                self.log.close()
            except OSError:
                pass
            self.log = None


def _reap(workers, sig=signal.SIGTERM, grace_s=10.0):
    """Signal every live worker and wait it out; stragglers get SIGKILL."""
    reap_procs([w.proc for w in workers], sig=sig, grace_s=grace_s)


def launch(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    ips = args.cluster_node_ips.split(",")
    if args.node_ip not in ips:
        sys.exit("--node_ip %s not in --cluster_node_ips %s"
                 % (args.node_ip, args.cluster_node_ips))
    endpoints = [
        "%s:%d" % (ip, args.started_port + i)
        for ip in ips for i in range(args.nproc_per_node)
    ]
    node_rank = ips.index(args.node_ip)
    local_ids = range(node_rank * args.nproc_per_node,
                      (node_rank + 1) * args.nproc_per_node)

    if (args.nproc_per_node > 1 and local_tpu_chips()
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # the workers form ONE jax.distributed world, which libtpu builds
        # from whole hosts: N local processes would each reach for every
        # chip of this host, and chips handed out one apiece
        # (one_chip_env) are separate one-chip worlds with no collective
        # between them
        sys.exit("--nproc_per_node=%d on a TPU host: one process drives "
                 "all %d local chips; launch one process per host "
                 "(--cluster_node_ips), or set JAX_PLATFORMS=cpu for a "
                 "CPU world" % (args.nproc_per_node, local_tpu_chips()))

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    # per-worker backoff schedule, shared with the serving layer's retry
    # machinery: base * 2**k, deterministic (jitter would only desync the
    # operator's expectations here)
    if args.max_restarts > 0:
        from ..reliability import RetryPolicy

        backoffs = RetryPolicy(max_attempts=args.max_restarts + 1,
                               base_delay_s=args.restart_backoff,
                               max_delay_s=60.0, multiplier=2.0,
                               jitter=0.0).delays()
    else:
        backoffs = []

    def spawn(w, log_mode="w"):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(w.tid),
            "PADDLE_TRAINERS_NUM": str(len(endpoints)),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[w.tid],
            "PADDLE_RESTART_COUNT": str(w.restarts),
        })
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        if args.log_dir:
            w.log = open(os.path.join(args.log_dir,
                                      "workerlog.%d" % w.tid), log_mode)
        w.proc = subprocess.Popen(cmd, env=env, stdout=w.log,
                                  stderr=subprocess.STDOUT
                                  if w.log else None)

    workers = [_Worker(tid) for tid in local_ids]
    for w in workers:
        spawn(w)

    # forward termination to the workers: SIGTERM raises into the wait
    # loop, which tears the tree down on the same path as Ctrl-C. Only
    # installable from the main thread (in-process/test callers elsewhere
    # keep their own handling).
    def _on_term(signum, frame):
        raise KeyboardInterrupt
    prev_term = None
    try:
        prev_term = signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass

    rc = 0
    restarts_used = 0
    remaining = {w.tid: w for w in workers}
    try:
        while remaining:
            crashed = None
            for tid, w in list(remaining.items()):
                code = w.proc.poll()
                if code is None:
                    continue
                if code == 0:
                    w.close_log()
                    del remaining[tid]
                    continue
                crashed = (tid, code)
                break
            if crashed is not None:
                tid, code = crashed
                if restarts_used < args.max_restarts:
                    # elastic: tear the WHOLE group down (a partial world
                    # would hang in its next collective), flush/close the
                    # logs, back off, respawn everyone; each worker
                    # recovers its own state through
                    # resume_or_init/AutoCheckpoint
                    delay = (backoffs[restarts_used]
                             if restarts_used < len(backoffs)
                             else backoffs[-1] if backoffs else 0.0)
                    restarts_used += 1
                    print("launch: worker %d exited %d; restarting the "
                          "group (%d/%d) in %.1fs"
                          % (tid, code, restarts_used, args.max_restarts,
                             delay), file=sys.stderr)
                    _reap(list(remaining.values()))
                    for w in workers:
                        w.close_log()
                    time.sleep(delay)
                    for w in workers:
                        w.restarts = restarts_used
                        spawn(w, log_mode="a")
                    remaining = {w.tid: w for w in workers}
                    continue
                # fate-sharing: budget spent (or elastic mode off)
                rc = code
                del remaining[tid]
                _reap(list(remaining.values()))
                remaining = {}
                break
            time.sleep(0.2)
    except KeyboardInterrupt:
        # Ctrl-C / SIGTERM: forward and reap — never orphan the workers
        _reap(list(remaining.values()))
        rc = 128 + signal.SIGTERM
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        for w in workers:
            w.close_log()
    return rc


if __name__ == "__main__":
    sys.exit(launch())
