"""WorkerSupervisor: crash/hang recovery, requeue, routing, admission.

These tests run against STUB workers (``{"stub": ...}`` spec — the
jax-free echo backend in serving/worker.py): the supervisor's contracts
(process monitoring, restart backoff, the zero-dropped-requests requeue
invariant, consistent-hash routing, deadline propagation) are properties
of the control pipe, not of what computes ``y``; real-jax workers are
covered by test_multiworker_e2e.py and scripts/serve_chaos_smoke.sh."""

import json
import sys
import time

import pytest

from keystone_tpu.reliability.recovery import get_recovery_log
from keystone_tpu.serving.config import (
    RequestShed,
    RequestTimeout,
    ServerClosed,
    ServingError,
)
from keystone_tpu.serving.supervisor import (
    HashRing,
    SupervisorConfig,
    WorkerSupervisor,
)

pytestmark = pytest.mark.serving


def make_supervisor(workers=2, delay_ms=0, chaos=None, **cfg):
    """Stub-worker supervisor tuned for test speed (fast beats, tight
    hang detection, sub-second backoff)."""
    defaults = dict(
        workers=workers,
        heartbeat_s=0.05,
        hang_timeout_s=0.8,
        ready_timeout_s=15.0,
        monitor_interval_s=0.02,
    )
    defaults.update(cfg)
    env = {}
    for worker_id, specs in (chaos or {}).items():
        env[f"KEYSTONE_FAULT_SPECS_WORKER_{worker_id}"] = json.dumps(specs)
    return WorkerSupervisor(
        {"stub": {"delay_ms": delay_ms}}, SupervisorConfig(**defaults), env=env
    )


def settle(futures, timeout=30):
    return [f.result(timeout=timeout) for f in futures]


# ------------------------------------------------------------------ routing


def test_hash_ring_spreads_and_is_consistent():
    ring = HashRing(["0", "1", "2", "3"])
    first = {f"k{i}": next(iter(ring.walk(f"k{i}"))) for i in range(400)}
    by_node = {}
    for node in first.values():
        by_node[node] = by_node.get(node, 0) + 1
    assert set(by_node) == {"0", "1", "2", "3"}
    assert min(by_node.values()) > 40  # no starved node at 400 keys
    # Same ring → identical placement (routing is a pure function).
    again = HashRing(["0", "1", "2", "3"])
    assert {k: next(iter(again.walk(k))) for k in first} == first
    # walk yields every node exactly once
    assert sorted(ring.walk("anything")) == ["0", "1", "2", "3"]


def test_hash_ring_failover_moves_only_dead_nodes_keys():
    ring = HashRing(["0", "1", "2"])
    keys = [f"k{i}" for i in range(300)]
    placements = {k: list(ring.walk(k)) for k in keys}
    for k in keys:
        order = placements[k]
        # Skipping a dead first choice lands on the SECOND ring node —
        # keys owned by healthy nodes never move.
        assert order[1] != order[0]


# ----------------------------------------------------------------- lifecycle


def test_round_trip_and_aggregated_stats():
    sup = make_supervisor(workers=2).start()
    try:
        sup.wait_ready()
        futures = [sup.submit([float(i)]) for i in range(30)]
        results = settle(futures)
        assert [r[0] for r in results] == [2.0 * i for i in range(30)]
        time.sleep(0.15)  # one beat so worker stats reach the supervisor
        stats = sup.stats()
        assert stats["served"] == 30
        assert set(stats["workers"]) == {"0", "1"}
        assert stats["supervisor"]["alive"] == 2
        assert stats["supervisor"]["requeued"] == 0
        # both workers took traffic (hash spread over request ids)
        per_worker = [w["stats"].get("served", 0) for w in stats["workers"].values()]
        assert all(v > 0 for v in per_worker), per_worker
    finally:
        sup.stop()


def test_affinity_key_pins_one_worker():
    sup = make_supervisor(workers=2).start()
    try:
        sup.wait_ready()
        settle([sup.submit([1.0], key="tenant-A") for _ in range(12)])
        time.sleep(0.15)
        served = [
            w["stats"].get("served", 0) for w in sup.stats()["workers"].values()
        ]
        assert sorted(served) == [0, 12], served
    finally:
        sup.stop()


def test_submit_after_stop_refuses():
    sup = make_supervisor(workers=1).start()
    sup.wait_ready()
    sup.stop()
    with pytest.raises(ServerClosed):
        sup.submit([1.0])


# ------------------------------------------------------------ chaos: crash


def test_sigkill_mid_load_drops_nothing_and_restarts():
    """THE supervisor invariant: a worker SIGKILLed mid-load loses zero
    requests — its in-flight work is requeued onto the healthy worker —
    and the supervisor restarts it with backoff, landing worker_crash +
    worker_restart in the recovery ledger."""
    sup = make_supervisor(
        workers=2,
        delay_ms=2,
        chaos={"0": [{"match": "serving.worker.request", "kind": "kill",
                      "calls": [4]}]},
    ).start()
    try:
        sup.wait_ready()
        futures = [sup.submit([float(i)], deadline_s=30) for i in range(50)]
        results = settle(futures)
        assert [r[0] for r in results] == [2.0 * i for i in range(50)]
        assert sup.requeued > 0  # the kill really stranded work
        sup.wait_ready(timeout_s=20)  # the killed worker comes back
        kinds = [e.kind for e in get_recovery_log().events()]
        assert "worker_crash" in kinds
        crash = get_recovery_log().events("worker_crash")[0]
        assert crash.detail["reason"] == "crash"
        # restart lands (backoff schedule is sub-second in this config)
        assert get_recovery_log().events("worker_restart"), kinds
        # the fleet serves again after recovery
        assert settle([sup.submit([3.0])])[0] == [6.0]
    finally:
        sup.stop()


def test_single_worker_kill_parks_requests_until_restart():
    """With no healthy sibling, stranded requests PARK (pending queue)
    rather than fail, and the restarted worker serves them."""
    sup = make_supervisor(
        workers=1,
        delay_ms=2,
        chaos={"0": [{"match": "serving.worker.request", "kind": "kill",
                      "calls": [3]}]},
    ).start()
    try:
        sup.wait_ready()
        futures = [sup.submit([float(i)], deadline_s=30) for i in range(10)]
        results = settle(futures)
        assert [r[0] for r in results] == [2.0 * i for i in range(10)]
        assert sup.stats()["supervisor"]["restarts"] == 1
    finally:
        sup.stop()


def test_restart_budget_exhaustion_fails_outstanding_loudly():
    """A crash-looping worker (exits immediately, never ready) consumes
    its restart budget and outstanding requests fail with a classified
    UNAVAILABLE error instead of hanging forever."""
    sup = WorkerSupervisor(
        {"stub": {}},
        SupervisorConfig(
            workers=1,
            max_restarts=2,
            monitor_interval_s=0.02,
            restart_policy=__import__(
                "keystone_tpu.reliability.retry", fromlist=["RetryPolicy"]
            ).RetryPolicy(max_attempts=3, base_delay_s=0.02, max_delay_s=0.05),
        ),
        worker_cmd=lambda wid: [sys.executable, "-c", "import sys; sys.exit(3)"],
    ).start()
    try:
        future = sup.submit([1.0])
        with pytest.raises(ServingError, match="restart budget"):
            future.result(timeout=20)
        assert sup.stats()["workers"]["0"]["state"] == "failed"
        assert get_recovery_log().events("worker_failed")
        # A submit AFTER the fleet failed must fail fast too — parking it
        # would strand the future (no worker will ever be ready again).
        late = sup.submit([2.0])
        with pytest.raises(ServingError, match="restart budget"):
            late.result(timeout=5)
    finally:
        sup.stop(drain=False)


# ------------------------------------------------------------- chaos: hang


def test_stopped_heartbeats_detected_as_hang_and_restarted():
    sup = make_supervisor(
        workers=1,
        chaos={"0": [{"match": "serving.worker.heartbeat", "kind": "hang",
                      "calls": [2], "hang_s": 60.0}]},
    ).start()
    try:
        sup.wait_ready()
        deadline = time.monotonic() + 20
        while not get_recovery_log().events("worker_crash"):
            assert time.monotonic() < deadline, "hang never detected"
            time.sleep(0.05)
        crash = get_recovery_log().events("worker_crash")[0]
        assert crash.detail["reason"] == "hang"
        sup.wait_ready(timeout_s=20)
        assert settle([sup.submit([1.0])])[0] == [2.0]
    finally:
        sup.stop()


def test_corrupt_heartbeats_are_not_heartbeats():
    """A garbled heartbeat line must not refresh liveness: a worker whose
    channel is corrupt gets hang-detected and recycled."""
    sup = make_supervisor(
        workers=1,
        chaos={"0": [{"match": "serving.worker.heartbeat", "kind": "corrupt",
                      "first_n": 10000}]},
    ).start()
    try:
        deadline = time.monotonic() + 20
        while not get_recovery_log().events("worker_crash"):
            assert time.monotonic() < deadline, "corrupt channel never detected"
            time.sleep(0.05)
        assert get_recovery_log().events("worker_crash")[0].detail["reason"] == "hang"
        sup.wait_ready(timeout_s=20)  # clean incarnation takes over
        assert settle([sup.submit([2.0])])[0] == [4.0]
    finally:
        sup.stop()


# ------------------------------------------------- deadlines and admission


def test_deadline_budget_crosses_the_boundary():
    """The REMAINING deadline crosses supervisor → worker: the worker
    sees a positive budget no larger than what was submitted, and a
    request submitted without a deadline crosses with none."""
    sup = make_supervisor(workers=1).start()
    try:
        sup.wait_ready()
        echoed = sup.submit(["deadline-echo"], deadline_s=5.0).result(timeout=10)
        assert 0.0 < echoed[0] <= 5000.0, echoed
        bare = sup.submit(["deadline-echo"]).result(timeout=10)
        assert bare[0] == -1.0  # no deadline submitted → none forwarded
    finally:
        sup.stop()


def test_expired_requeue_fails_as_timeout_not_zombie():
    """A request whose deadline lapses while parked fails with
    RequestTimeout instead of dispatching with zero budget."""
    sup = WorkerSupervisor(
        {"stub": {}},
        SupervisorConfig(workers=1, monitor_interval_s=0.02, ready_timeout_s=15),
        worker_cmd=lambda wid: [sys.executable, "-c", "import time; time.sleep(60)"],
    ).start()
    try:
        future = sup.submit([1.0], deadline_s=0.2)  # parked: worker never ready
        with pytest.raises(RequestTimeout):
            future.result(timeout=10)
    finally:
        sup.stop(drain=False)


def test_swap_survives_a_dead_worker_mid_broadcast():
    """A worker whose pipe is already gone when the swap broadcast
    reaches it fails ITS ack (swap_failed) — the remaining workers must
    still receive and ack the swap, and swap() must not raise."""
    sup = make_supervisor(workers=2).start()
    try:
        sup.wait_ready()
        # Close worker 0's stdin under the supervisor: the write path
        # raises deterministically while state still reads "ready".
        sup._workers["0"].proc.stdin.close()
        acks = sup.swap({"stub": {}})
        assert set(acks) == {"0", "1"}
        assert acks["0"]["kind"] == "swap_failed"
        assert acks["1"]["kind"] == "swapped"
    finally:
        sup.stop(drain=False)


def test_every_pipe_broken_parks_without_recursing():
    """When EVERY ready worker's pipe breaks inside one routing pass, the
    route loop must walk each worker once and park — not ping-pong
    between two broken pipes until RecursionError. The parked request is
    then served by the restarted fleet (EOF on stdin ends the workers,
    the monitor recycles them)."""
    sup = make_supervisor(workers=2).start()
    try:
        sup.wait_ready()
        for worker in sup._workers.values():
            worker.proc.stdin.close()  # every write now raises
        future = sup.submit([5.0], deadline_s=30)
        assert sup.requeued >= 2  # both pipes were tried, then it parked
        assert future.result(timeout=20) == [10.0]
    finally:
        sup.stop()


def test_park_after_final_drain_settles_closed_not_stranded():
    """A submit that races stop() past the final drain must settle its
    future with ServerClosed instead of parking on a queue nothing will
    ever drain again."""
    sup = make_supervisor(workers=1)  # never started: no ready workers
    sup._drained = True  # the state stop() leaves behind
    future = sup.submit([1.0])
    with pytest.raises(ServerClosed):
        future.result(timeout=5)


def test_admission_sheds_at_capacity():
    sup = make_supervisor(workers=1, delay_ms=200, queue_depth=4).start()
    try:
        sup.wait_ready()
        futures, sheds = [], 0
        for i in range(16):
            try:
                futures.append(sup.submit([float(i)]))
            except RequestShed:
                sheds += 1
        assert sheds > 0, "capacity 4 never shed under 16 instant submits"
        settle(futures)  # admitted requests all complete
    finally:
        sup.stop()


# ------------------------------------------- restart-monotonic aggregation


def test_restart_keeps_aggregated_counters_monotonic():
    """The satellite fix: a restarted worker's telemetry counters restart
    from zero, but stats() aggregates per-worker high-water marks — the
    fleet's `served` is LIFETIME and never resets across incarnations."""
    sup = make_supervisor(
        workers=1,
        chaos={"0": [{"match": "serving.worker.request", "kind": "kill",
                      "calls": [6]}]},
    ).start()
    try:
        sup.wait_ready()
        settle([sup.submit([float(i)], deadline_s=30) for i in range(5)])
        time.sleep(0.3)  # beats carry served=5 into the high-water mark
        before = sup.stats()
        assert before["served"] == 5
        # Request 6 kills the worker pre-completion; it requeues onto the
        # restarted incarnation, whose own counters restart from zero.
        settle([sup.submit([float(i)], deadline_s=30) for i in range(5, 10)])
        time.sleep(0.3)
        after = sup.stats()
        assert after["workers"]["0"]["incarnation"] >= 1
        # incarnation-local counter really did reset...
        assert after["workers"]["0"]["stats"]["served"] < 10
        # ...but the aggregate is lifetime: 5 before the kill + 5 after.
        assert after["served"] == 10
        # fleet_counter_totals (the /metrics source) agrees
        assert sup.fleet_counter_totals()["0"]["served"] == 10.0
    finally:
        sup.stop()


# --------------------------------------------------- cross-process tracing


def test_trace_context_crosses_the_pipe_and_fragments_return():
    """Fleet tracing end to end over stub workers: the submit-time trace
    context rides every dispatch line, the worker re-parents its spans
    under it, and the fragments come back on heartbeats — the merged
    trace shows ONE trace id across supervisor + both worker processes."""
    from keystone_tpu.obs import spans

    with spans.tracing_session("sup-trace", sync_timings=False) as session:
        sup = WorkerSupervisor(
            {"stub": {}},
            SupervisorConfig(
                workers=2, heartbeat_s=0.05, hang_timeout_s=5.0,
                ready_timeout_s=15.0, monitor_interval_s=0.02,
            ),
            env={"KEYSTONE_FLEET_TRACE": "1"},
        ).start()
        try:
            sup.wait_ready()
            with spans.span("ingress"):
                settle([sup.submit([1.0, float(i)]) for i in range(12)])
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                fragments = sup.fleet.fragments()
                worker_requests = [
                    f for frags in fragments.values() for f in frags
                    if f["n"] == "worker:request"
                ]
                if len(worker_requests) >= 12 and len(fragments) >= 2:
                    break
                time.sleep(0.05)
            merged = sup.fleet.merge(local_session=session)
        finally:
            sup.stop()

    # supervisor-side dispatch spans parent under the ingress span
    dispatches = [s for s in session.spans() if s.name == "supervisor:dispatch"]
    ingress = next(s for s in session.spans() if s.name == "ingress")
    assert len(dispatches) == 12
    assert all(s.trace_id == session.trace_id for s in dispatches)
    assert all(s.parent_id == ingress.span_id for s in dispatches)
    # worker fragments carry the SAME trace id, parented under a dispatch
    dispatch_ids = {s.span_id for s in dispatches}
    assert len(worker_requests) >= 12
    assert all(f["t"] == session.trace_id for f in worker_requests)
    assert all(f.get("p") in dispatch_ids for f in worker_requests)
    # both worker processes shipped, and the merged Perfetto artifact has
    # the single trace id across >= 3 pids (supervisor + 2 workers)
    assert len(fragments) >= 2
    pids = {
        e["pid"] for e in merged["traceEvents"]
        if e.get("ph") == "X" and e["args"].get("trace_id") == session.trace_id
    }
    assert len(pids) >= 3
    assert session.trace_id in merged["otherData"]["trace_ids"]
    # clock anchors arrived via the ready/heartbeat handshake
    assert merged["otherData"]["clock_skew_s"]


def test_tracing_off_adds_no_wire_field():
    """With no session, submit captures no context and the control line
    carries no trace field — tracing off is zero wire bytes."""
    captured = []
    sup = make_supervisor(workers=1).start()
    try:
        sup.wait_ready()
        worker = sup._workers["0"]
        real_stdin = worker.proc.stdin

        class _Spy:
            def write(self, line):
                captured.append(line)
                return real_stdin.write(line)

            def flush(self):
                return real_stdin.flush()

        worker.proc.stdin = _Spy()
        settle([sup.submit([1.0])])
        worker.proc.stdin = real_stdin
        requests = [json.loads(l) for l in captured if l.strip()]
        assert requests and all("trace" not in r for r in requests)
    finally:
        sup.stop()


# ------------------------------------------------------ one process per chip


def _chip_fleet(monkeypatch, chips, workers):
    """A server-spec supervisor on a host the probe says has ``chips``
    TPU chips; its workers are stub processes, so no backend starts."""
    from keystone_tpu.serving import supervisor as sup

    monkeypatch.setattr(sup, "probe_local_chips", lambda env: chips)
    stub = json.dumps({"stub": {}})
    return WorkerSupervisor(
        {"synthetic": {"d": 4}},
        SupervisorConfig(
            workers=workers, heartbeat_s=0.05, hang_timeout_s=0.8,
            ready_timeout_s=15.0, monitor_interval_s=0.02,
        ),
        worker_cmd=lambda worker_id: [
            sys.executable, "-m", "keystone_tpu.serving.worker",
            "--spec", stub, "--worker-id", worker_id, "--heartbeat-s", "0.05",
        ],
    )


def test_more_workers_than_chips_fails_at_start(monkeypatch):
    supervisor = _chip_fleet(monkeypatch, chips=1, workers=2)
    with pytest.raises(RuntimeError, match="1 TPU chip"):
        supervisor.start()
    assert all(w.proc is None for w in supervisor._workers.values())


def test_each_worker_owns_one_chip_and_a_retired_chip_is_reused(monkeypatch):
    from keystone_tpu.serving.supervisor import chip_env

    assert chip_env(1)["TPU_VISIBLE_CHIPS"] == "1"
    assert chip_env(0)["TPU_PROCESS_PORT"] != chip_env(1)["TPU_PROCESS_PORT"]
    with _chip_fleet(monkeypatch, chips=2, workers=2) as supervisor:
        supervisor.wait_ready()
        rows = supervisor.stats()["workers"]
        assert sorted(row["chip"] for row in rows.values()) == [0, 1]
        assert supervisor.worker_ceiling == 2
        with pytest.raises(RuntimeError, match="every local TPU chip"):
            supervisor.add_worker()
        freed = rows[supervisor.remove_worker()]["chip"]
        deadline = time.monotonic() + 15
        while len(supervisor.stats()["workers"]) > 1:
            assert time.monotonic() < deadline, "drained worker never retired"
            time.sleep(0.02)
        new_id = supervisor.add_worker()
        supervisor.wait_ready()
        assert supervisor.stats()["workers"][new_id]["chip"] == freed


def test_cpu_workers_are_not_probed_or_pinned(monkeypatch):
    from keystone_tpu.serving.supervisor import probe_local_chips

    assert probe_local_chips({"JAX_PLATFORMS": "cpu"}) == 0
    with make_supervisor(workers=2) as supervisor:
        supervisor.wait_ready()
        assert supervisor.worker_ceiling is None
        assert {r["chip"] for r in supervisor.stats()["workers"].values()} == {None}
