#!/usr/bin/env python
"""Chaos drill for fleet-coordinated continuous learning (ISSUE 19).

Exercises the three failure planes the multi-host streaming loop must
survive, and audits the flight-recorder evidence each one leaves:

  * **exactly-once-resume cursor** — a consumer dies mid-file; a fresh
    stream seeded from its durable cursor must cover every row with a
    bounded (<= one chunk) counted replay. A cursor at the parse
    position instead of the delivered boundary silently loses the
    in-flight tail; this drill would catch it.
  * **partition-lease takeover** — a host stops heartbeating; past the
    TTL the survivor reclaims its partitions (``lease.reassign``) and
    the returning zombie drops ownership loudly (``lease.lost``)
    instead of double-reading.
  * **two-phase fleet swap** — a target's commit dies past its retry
    budget mid-swap; the fleet must converge around it (straggler
    quarantined, ``publish.partial_commit`` flight event, nonzero
    ``fleet_version_skew`` gauge, BOTH served versions kept pinned) and
    heal on readmit.

    python tools/chaos_fleet.py              # full: adds a real 2-host
                                             # drill (a trainer process
                                             # SIGKILLed mid-publish)
                                             # and a live router fleet
                                             # whose straggler worker is
                                             # SIGKILLed mid-commit
    python tools/chaos_fleet.py --smoke      # lint.sh gate: in-process,
                                             # deterministic fake clock

Prints one JSON summary line (counters + verdict); exit 0 = ok.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _rows(n, start=0):
    return [("row-%06d" % i).encode() for i in range(start, start + n)]


def _drill_cursor(streaming, summary):
    """Kill a consumer mid-file; resume from its cursor must be
    complete and boundedly duplicated."""
    data = tempfile.mkdtemp(prefix="chaos-fleet-cursor-")
    rows = _rows(48)
    path = os.path.join(data, "part-00000.recordio")
    for i in range(0, len(rows), 8):  # 8-row chunks
        streaming.write_records(path, rows[i:i + 8])

    def drained():
        s = streaming.RecordStream(data, poll_interval_s=0.0,
                                   sleep=lambda _t: None)
        s.close()
        return s

    s = drained()
    it = s.records()
    got = [next(it) for _ in range(20)]  # dies 2.5 chunks in
    cur = s.cursor()
    s2 = drained()
    s2.seek(cur)
    rest = list(s2.records())
    replay = len(got) + len(rest) - len(rows)
    summary["cursor"] = {
        "delivered_before_death": len(got), "cursor_rows": cur["rows"],
        "replayed_rows": replay,
        "complete": set(got) | set(rest) == set(rows)}
    return (summary["cursor"]["complete"] and 0 <= replay <= 8
            and cur["rows"] == 16)


def _drill_lease(streaming, flight, summary):
    """Fake-clock takeover: survivor reclaims a dead host's partitions
    past the TTL; the zombie's next renewal loses them loudly."""
    lease_root = tempfile.mkdtemp(prefix="chaos-fleet-lease-")
    clk = [1000.0]

    def mk(host):
        return streaming.PartitionCoordinator(
            lease_root, host, num_partitions=4, ttl_s=5.0,
            target_share=2, clock=lambda: clk[0])

    a, b = mk("host-a"), mk("host-b")
    a.poll()
    b.poll()
    balanced = len(a.owned) == 2 and len(b.owned) == 2
    clk[0] += 6.0  # host-a misses every heartbeat past the TTL
    gained = b.poll()
    a.renew()  # the zombie returns
    ev = flight.RECORDER.events(kind="lease.reassign")
    summary["lease"] = {
        "balanced": balanced, "reassigned": b.reassigned,
        "zombie_lost": a.lost, "reassign_events": len(ev)}
    return bool(balanced and len(gained) == 2
                and b.reassigned == 2 and b.owned == {0, 1, 2, 3}
                and a.owned == set() and a.lost == 2 and len(ev) >= 2
                and flight.RECORDER.events(kind="lease.lost"))


def _drill_swap(targets, ckpt_dir, publish, streaming, flight, summary):
    """Two-phase swap with a commit-faulted straggler: quarantine +
    skew gauge + partial_commit evidence, then heal on readmit.
    ``targets`` maps name -> engine-or-RouterTarget; ``publish()``
    lands a fresh version in ``ckpt_dir``."""
    import warnings

    from paddle_tpu import checkpoint
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.policy import RetryPolicy

    fp = streaming.FleetPublisher(
        ckpt_dir, targets,
        retry=RetryPolicy(max_attempts=2, base_delay_s=0.0,
                          sleep=lambda _s: None))
    v1 = fp.poll_once()
    clean = v1 is not None and fp.version_skew() == 0
    publish()
    v2 = checkpoint.candidate_versions(ckpt_dir)[0]
    straggler = sorted(targets)[-1]
    with faults.fault_scope(faults.FaultPlan.from_spec(
            "swap.commit:error@2-3")), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        committed = fp.poll_once()
    ev = flight.RECORDER.events(kind="publish.partial_commit")
    quarantined = (committed == v2 and fp.quarantined == {straggler}
                   and fp.version_skew() == 1
                   and {v1, v2} <= checkpoint.pinned_versions(ckpt_dir)
                   and "paddle_tpu_stream_fleet_version_skew 1"
                   in fp.registry.prometheus_text()
                   and ev and ev[-1]["target"] == straggler)
    fp.readmit(straggler)
    healed = fp.poll_once() == v2 and fp.version_skew() == 0
    summary["swap"] = {
        "fleet_version": fp.fleet_version, "clean_round": clean,
        "quarantined": sorted(fp.quarantined),
        "partial_commits": fp.partial_commits, "healed": healed,
        "partial_commit_events": len(ev)}
    fp.release()
    return clean and quarantined and healed


def _drill_router_kill(targets, rb, ckpt, publish, streaming, flight,
                       summary, timeout_s):
    """SIGKILL a router's worker process MID-COMMIT: prepares land on
    every target, then the straggler's worker dies the instant before
    its commit RPC. The fleet must end fully swapped or loudly
    quarantined (skew gauge + ``publish.partial_commit``) — never
    silently mixed — and heal once the supervisor respawns the worker."""
    import warnings

    from paddle_tpu import checkpoint
    from paddle_tpu.reliability.policy import RetryPolicy

    fp = streaming.FleetPublisher(
        ckpt, targets,
        retry=RetryPolicy(max_attempts=2, base_delay_s=0.0,
                          sleep=lambda _s: None))
    fp.poll_once()  # converge the cold fleet before the drill round
    publish()
    v = checkpoint.candidate_versions(ckpt)[0]
    straggler = sorted(targets)[-1]
    target_b = targets[straggler]
    orig_commit = target_b.commit
    kills = []

    def killing_commit(version=None):
        if not kills:  # first commit attempt only: die mid-round
            kills.append(rb._workers[0].pid)
            os.kill(rb._workers[0].pid, signal.SIGKILL)
        return orig_commit(version=version)

    target_b.commit = killing_commit
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fp.poll_once()
    finally:
        target_b.commit = orig_commit
    quarantined = sorted(fp.quarantined)
    skew = fp.version_skew()
    loud = (skew == 0 and not quarantined) or (
        skew == 1 and quarantined == [straggler]
        and bool(flight.RECORDER.events(kind="publish.partial_commit")))
    healed = skew == 0 and fp.fleet_version == v
    deadline = time.time() + timeout_s
    while not healed and time.time() < deadline:
        time.sleep(0.3)  # give the supervisor time to respawn
        for name in list(fp.quarantined):
            fp.readmit(name)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fp.poll_once()
        healed = (fp.version_skew() == 0 and not fp.quarantined
                  and fp.fleet_version == v)
    summary["router_kill"] = {
        "killed_pid": kills[0] if kills else None,
        "quarantined_after_kill": quarantined,
        "skew_after_kill": skew, "healed": healed,
        "fleet_version": fp.fleet_version}
    fp.release()
    return bool(kills) and loud and healed


def _spawn_trainer(data_dir, ckpt_dir, host, peer_dir, steps, env_extra):
    from paddle_tpu.streaming.trainer import TRAINER_READY_PREFIX

    env = dict(os.environ)
    env.update(env_extra)
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.streaming.trainer",
         "--data-dir", data_dir, "--ckpt-dir", ckpt_dir,
         "--steps", str(steps), "--publish-every", "2",
         "--batch-size", "16", "--poll-interval", "0.02",
         "--partitions", "2", "--num-hosts", "2", "--lease-ttl", "1.0",
         "--host-id", host, "--peer-dirs", peer_dir],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)
    for line in proc.stdout:
        if line.startswith(TRAINER_READY_PREFIX):
            return proc
    proc.kill()
    raise RuntimeError("trainer %s died before READY" % host)


def _drill_host_loss(streaming, flight, flight_dir, summary, timeout_s):
    """Full mode only: two REAL trainer processes split the stream;
    one is SIGKILLed MID-PUBLISH (a ``checkpoint.write:hang`` fault
    holds its second version's array write open, so the kill lands in
    the torn window: version dir on disk, no manifest). The survivor
    must adopt its partitions + the newest INTACT version's cursor and
    still finish its step budget, and its flight dump must hold the
    ``lease.reassign`` evidence."""
    from paddle_tpu import checkpoint

    root = tempfile.mkdtemp(prefix="chaos-fleet-hosts-")
    data = os.path.join(root, "data")
    streaming.synthesize_stream_files(data, n_files=4, rows_per_file=64,
                                      seed=3, chunk_rows=16)
    env = {"PADDLE_TPU_FLIGHT": flight_dir}
    ckpt_a = os.path.join(root, "ckpt_a")
    pa = _spawn_trainer(data, ckpt_a, "host-a",
                        os.path.join(root, "ckpt_b"), 999,
                        dict(env, PADDLE_TPU_FAULTS=
                             "checkpoint.write:hang(3.0)@2"))
    pb = _spawn_trainer(data, os.path.join(root, "ckpt_b"), "host-b",
                        os.path.join(root, "ckpt_a"), 30, env)
    deadline = time.time() + timeout_s
    torn_dir = os.path.join(ckpt_a, "checkpoint_1")
    manifest = os.path.join(torn_dir, checkpoint._MANIFEST)
    killed_mid_publish = False
    while time.time() < deadline:
        if os.path.isdir(torn_dir) and not os.path.exists(manifest):
            killed_mid_publish = True
            break
        if pa.poll() is not None:
            break
        time.sleep(0.005)
    os.kill(pa.pid, signal.SIGKILL)
    pa.wait()
    torn_invisible = checkpoint.candidate_versions(ckpt_a) == [0]
    result, start = None, 256
    while time.time() < deadline:
        if pb.poll() is not None:
            for line in pb.stdout:
                line = line.strip()
                if line.startswith("{"):
                    result = json.loads(line)
            break
        # the log collectors keep appending: fresh files land in both
        # partitions so the survivor has rows to finish its budget on
        streaming.synthesize_stream_files(
            data, n_files=4, rows_per_file=16, seed=3,
            start_index=start, chunk_rows=16)
        start += 64
        time.sleep(0.3)
    if result is None:
        pb.kill()
        summary["host_loss"] = {"error": "survivor never exited"}
        return False
    reassigns = sum(
        1 for d in flight.load_dir(flight_dir)
        for e in d["events"] if e["kind"] == "lease.reassign")
    summary["host_loss"] = {
        "killed_mid_publish": killed_mid_publish,
        "torn_version_invisible": torn_invisible,
        "survivor_steps": result["steps"],
        "publish_failures": result["publish_failures"],
        "partitions_owned": result["partitions_owned"],
        "reassigned": result["reassigned"],
        "replayed_rows": result["replayed_rows"],
        "reassign_events": reassigns}
    serve_dir = os.path.join(root, "ckpt_b", "serve")
    ok = (killed_mid_publish and torn_invisible
          and result["steps"] == 30 and result["publish_failures"] == 0
          and result["partitions_owned"] == [0, 1]
          and result["reassigned"] >= 1 and reassigns >= 1)
    return ok, os.path.join(root, "ckpt_b"), serve_dir


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="chaos_fleet", description=__doc__.splitlines()[0])
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: in-process drills on a fake clock "
                         "(no subprocesses)")
    args = ap.parse_args(argv)

    from paddle_tpu import serving, streaming
    from paddle_tpu.obs import flight

    flight_dir = tempfile.mkdtemp(prefix="paddle-tpu-flight-")
    os.environ[flight.ENV_FLIGHT_DIR] = flight_dir
    flight.install()
    flight.RECORDER.clear()

    summary = {"mode": "smoke" if args.smoke else "full"}
    ok_cursor = _drill_cursor(streaming, summary)
    ok_lease = _drill_lease(streaming, flight, summary)

    if args.smoke:
        # in-process fleet: a throwaway trainer publishes, two live
        # engines are the swap targets
        root = tempfile.mkdtemp(prefix="chaos-fleet-swap-")
        data = os.path.join(root, "data")
        ckpt = os.path.join(root, "ckpt")
        streaming.synthesize_stream_files(data, n_files=1,
                                          rows_per_file=256, seed=5)
        trainer = streaming.StreamingTrainer(
            ckpt, batch_size=16, publish_every_steps=4, max_versions=4,
            hidden_sizes=(16,), holdout_batches=2)
        s = streaming.RecordStream(data, poll_interval_s=0.0,
                                   sleep=lambda _t: None)
        s.close()
        trainer.run(s, max_steps=4)
        # the periodic publish writes in the background, and the drill's
        # clean round needs that version ON DISK: on a loaded machine the
        # write lost its race with the engines' start-up and the round
        # found nothing to swap to (swap_ok false). close() joins it.
        trainer.close()
        engines = {"a": serving.ServingEngine(trainer.serve_dir,
                                              num_replicas=1),
                   "b": serving.ServingEngine(trainer.serve_dir,
                                              num_replicas=1)}

        def publish():
            w = trainer.publish()
            if not w.wait() or w.error is not None:
                raise RuntimeError("publish failed: %r" % (w.error,))

        try:
            ok_swap = _drill_swap(engines, ckpt, publish, streaming,
                                  flight, summary)
        finally:
            trainer.close()
            for e in engines.values():
                e.shutdown()
        ok_hosts = ok_rkill = None
    else:
        # full: real trainer subprocesses first (the survivor's ckpt
        # dir then feeds a REAL router fleet for the swap drill)
        res = _drill_host_loss(streaming, flight, flight_dir, summary,
                               args.timeout_s)
        if res is False:
            ok_hosts, ok_swap, ok_rkill = False, False, False
        else:
            ok_hosts, ckpt, serve_dir = res
            from paddle_tpu.serving import Router, RouterClient

            # the commit fault must trip in the STRAGGLER's worker
            # process (the swap sites live engine-side, across the
            # wire) — the in-process plan in _drill_swap cannot reach
            # it. Invocation 1 is the clean round's commit; 2-3 are the
            # faulted round's commit + its one retry.
            ra = Router(serve_dir, num_workers=1, spawn_timeout_s=120.0)
            rb = Router(serve_dir, num_workers=1, spawn_timeout_s=120.0,
                        worker_env={"PADDLE_TPU_FAULTS":
                                    "swap.commit:error@2-3"})
            try:
                ra.start()
                rb.start()
                ca = RouterClient(ra.address, default_timeout_s=60.0)
                cb = RouterClient(rb.address, default_timeout_s=60.0)
                targets = {"a": streaming.RouterTarget(ca),
                           "b": streaming.RouterTarget(cb)}
                pub_env = {"PADDLE_TPU_FLIGHT": flight_dir}
                pub_data = os.path.join(os.path.dirname(ckpt), "data")
                pub_start = [4096]

                def publish():
                    # the survivor drained the stream before exiting;
                    # a publisher trainer resuming from its cursor
                    # needs FRESH rows or it tail-follows forever
                    streaming.synthesize_stream_files(
                        pub_data, n_files=2, rows_per_file=64, seed=9,
                        start_index=pub_start[0], chunk_rows=16)
                    pub_start[0] += 64
                    r = subprocess.run(
                        [sys.executable, "-m",
                         "paddle_tpu.streaming.trainer", "--data-dir",
                         pub_data, "--ckpt-dir", ckpt, "--steps", "2",
                         "--publish-every", "1", "--batch-size", "16",
                         "--poll-interval", "0.02"],
                        env=dict(os.environ, **pub_env), timeout=120,
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL)
                    if r.returncode != 0:
                        raise RuntimeError("publisher trainer failed")

                ok_swap = _drill_swap(targets, ckpt, publish, streaming,
                                      flight, summary)
                ok_rkill = _drill_router_kill(
                    targets, rb, ckpt, publish, streaming, flight,
                    summary, args.timeout_s)
                ca.close()
                cb.close()
            finally:
                ra.shutdown()
                rb.shutdown()

    summary.update({"cursor_ok": ok_cursor, "lease_ok": ok_lease,
                    "swap_ok": ok_swap, "host_loss_ok": ok_hosts,
                    "router_kill_ok": ok_rkill,
                    "flight_dir": flight_dir})
    ok = (ok_cursor and ok_lease and ok_swap
          and ok_hosts in (None, True) and ok_rkill in (None, True))
    summary["verdict"] = "ok" if ok else "FAIL"
    print(json.dumps(summary))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
