"""TransferManager control-plane behaviour: fleet scheduling, caps,
tenant fairness, lifecycle (pause/resume/cancel), session sharing, and
Advisor-driven route selection (paper §2.1-§2.2: the managed third-party
orchestrator, scaled out)."""

import os
import threading
import time

import pytest

from repro.connectors import MemoryConnector, PosixConnector
from repro.core import (Advisor, Credential, CredentialStore, Endpoint,
                        FaultSchedule, PerfModel, Route, RouteCandidate,
                        TransferManager, TransferOptions)
from repro.core.clock import Clock
from repro.sim import ScenarioRunner

MB = 1024 * 1024
GB = 1e9


def make_manager(tmp_path, creds=None, **kw):
    creds = creds or CredentialStore()
    kw.setdefault("max_workers", 4)
    kw.setdefault("per_endpoint_cap", 2)
    return TransferManager(credential_store=creds,
                           marker_root=os.path.join(str(tmp_path), "markers"),
                           clock=Clock(scale=0.0), **kw)


def seeded_posix(tmp_path, files):
    root = os.path.join(str(tmp_path), "srcroot")
    conn = PosixConnector(root)
    for name, payload in files.items():
        p = os.path.join(root, name)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "wb") as f:
            f.write(payload)
    return conn


class OpCountingMemory(MemoryConnector):
    """Counts concurrently-active data-plane ops — independent evidence
    that the manager's per-endpoint cap holds at the connector."""

    def __init__(self):
        super().__init__()
        self._lock = threading.Lock()
        self.active = 0
        self.peak = 0
        self.starts = 0

    def _enter(self):
        with self._lock:
            self.active += 1
            self.peak = max(self.peak, self.active)

    def _exit(self):
        with self._lock:
            self.active -= 1

    def start(self, credential=None):
        with self._lock:
            self.starts += 1
        return super().start(credential)

    def recv(self, session, path, channel):
        self._enter()
        try:
            return super().recv(session, path, channel)
        finally:
            self._exit()

    def recv_batch(self, session, paths, channel_factory):
        self._enter()
        try:
            return super().recv_batch(session, paths, channel_factory)
        finally:
            self._exit()


# --------------------------------------------------------------------------
# acceptance: a chaos fleet across tenants
# --------------------------------------------------------------------------
def test_fleet_chaos_pause_resume_byte_exact(tmp_path):
    """>= 4 concurrent tasks across 2 tenants under an injected
    FaultSchedule, with a pause->resume mid-run: every task completes
    byte-exact, caps hold, and markers end cleared."""
    runner = ScenarioRunner(str(tmp_path), clock=Clock(scale=0.0))
    schedule = (FaultSchedule(seed=11)
                .transient(op="recv", at=1, times=1)
                .transient(op="read", at=3, times=1))
    res = runner.run_multi(n_tasks=5, tenants=("alice", "bob"),
                           trees=("mixed", "many-small"),
                           route="posix->memory", schedule=schedule,
                           max_workers=3, per_endpoint_cap=2,
                           pause_resume=(1, 3), seed=7, strict=True)
    assert res.ok
    assert len(res.tasks) == 5
    for task in res.tasks:
        assert task.status == task.SUCCEEDED, (task.task_id, task.events[-3:])
    # the schedule actually fired (chaos was live, not a no-op)
    assert schedule.events
    m = res.manager.metrics
    assert m.peak_active <= 3
    assert all(peak <= 2 for peak in m.peak_by_endpoint.values())
    assert set(m.dispatches_by_tenant) == {"alice", "bob"}


def test_endpoint_cap_holds_at_connector(tmp_path):
    """Cap evidence measured at the destination connector itself: with
    per-task concurrency 1, concurrently-active recv ops == concurrently
    active tasks on that endpoint."""
    files = {f"d/f{i}.bin": os.urandom(64 * 1024) for i in range(6)}
    src = seeded_posix(tmp_path, files)
    dst = OpCountingMemory()
    creds = CredentialStore()
    mgr = make_manager(tmp_path, creds, max_workers=4, per_endpoint_cap=2)
    opts = TransferOptions(startup_cost=0.0, concurrency=1,
                           coalesce_threshold=0)
    tasks = [mgr.submit(Endpoint(src, "d", f"src{i}"),
                        Endpoint(dst, f"out{i}", "the-dst"), opts,
                        task_id=f"cap{i}")
             for i in range(6)]
    assert mgr.wait_all(timeout=60)
    for t in tasks:
        assert t.status == t.SUCCEEDED
    assert mgr.metrics.peak_by_endpoint["the-dst"] <= 2
    assert dst.peak <= 2
    mgr.shutdown()


def test_pause_resume_no_resend_of_completed_ranges(tmp_path):
    """Pause mid-transfer; the resume must move only the holes the
    MarkerStore says are missing (paper §3 'holey' restart, driven
    through the control plane)."""
    payload = os.urandom(8 * MB)
    src = seeded_posix(tmp_path, {"big.bin": payload})

    gate = threading.Event()      # set => reads flow
    reached = threading.Event()   # first 2 MB landed
    seen = {"n": 0}
    lock = threading.Lock()

    class GateMemory(MemoryConnector):
        def recv(self, session, path, channel):
            outer = self

            class Wrap:
                def __getattr__(w, k):
                    return getattr(channel, k)

                def read(w, offset, length):
                    with lock:
                        seen["n"] += length
                        hit = seen["n"] >= 2 * MB
                    if hit:
                        reached.set()
                        gate.wait(timeout=30)
                    return channel.read(offset, length)

            super().recv(session, path, Wrap())

    dst = GateMemory()
    mgr = make_manager(tmp_path)
    opts = TransferOptions(startup_cost=0.0, blocksize=256 * 1024,
                           parallelism=1, concurrency=1)
    task = mgr.submit(Endpoint(src, "big.bin"), Endpoint(dst, "big.bin"),
                      opts, task_id="pr1")
    assert reached.wait(30), "transfer never reached the gate"
    assert mgr.pause("pr1")
    gate.set()
    assert task.wait_idle(30)
    assert task.status == task.PAUSED

    state = mgr.service.markers.load("pr1")
    done_ranges = state["files"]["big.bin"]["done"]
    done_bytes = sum(length for _, length in done_ranges)
    assert 0 < done_bytes < len(payload)
    assert not state["files"]["big.bin"].get("complete")

    sent = {"n": 0}
    orig = PosixConnector.send

    def counting_send(self, session, path, channel):
        class Wrap:
            def __getattr__(w, k):
                return getattr(channel, k)

            def write(w, offset, data):
                sent["n"] += len(data)
                channel.write(offset, data)

        return orig(self, session, path, Wrap())

    PosixConnector.send = counting_send
    try:
        assert mgr.resume("pr1")
        assert task.wait(60)
    finally:
        PosixConnector.send = orig
    assert task.status == task.SUCCEEDED, task.events[-5:]
    # only the holes crossed the wire on resume
    assert sent["n"] == len(payload) - done_bytes
    dst.start(None)
    assert dst.store.get("big.bin") == payload
    assert mgr.service.markers.load("pr1") == {"files": {}}
    assert task.stats.resumes == 1
    mgr.shutdown()


def test_pause_lands_after_sender_claimed_everything(tmp_path):
    """A pause request must interrupt a file even when the send side has
    already claimed (and pushed) every block range.  The sender has no
    backpressure, so on an unloaded run it rips through the whole claim
    queue in milliseconds; the claim-side abort gate then can never fire
    again, and before the receive side also checked the abort hook the
    transfer ran to SUCCEEDED despite pause() returning True."""
    payload = os.urandom(8 * MB)

    sent_done = threading.Event()  # sender pushed every block

    class DoneSignalPosix(PosixConnector):
        def send(self, session, path, channel):
            try:
                super().send(session, path, channel)
            finally:
                sent_done.set()

    root = os.path.join(str(tmp_path), "srcroot")
    src = DoneSignalPosix(root)
    p = os.path.join(root, "big.bin")
    os.makedirs(root, exist_ok=True)
    with open(p, "wb") as f:
        f.write(payload)

    gate = threading.Event()      # set => receive-side reads flow
    reached = threading.Event()   # first 2 MB landed
    seen = {"n": 0}
    lock = threading.Lock()

    class GateMemory(MemoryConnector):
        def recv(self, session, path, channel):
            class Wrap:
                def __getattr__(w, k):
                    return getattr(channel, k)

                def read(w, offset, length):
                    with lock:
                        seen["n"] += length
                        hit = seen["n"] >= 2 * MB
                    if hit:
                        reached.set()
                        gate.wait(timeout=30)
                    return channel.read(offset, length)

            super().recv(session, path, Wrap())

    dst = GateMemory()
    mgr = make_manager(tmp_path)
    opts = TransferOptions(startup_cost=0.0, blocksize=256 * 1024,
                           parallelism=1, concurrency=1)
    task = mgr.submit(Endpoint(src, "big.bin"), Endpoint(dst, "big.bin"),
                      opts, task_id="late-pause")
    assert reached.wait(30), "transfer never reached the gate"
    # the receiver is gated, so the unthrottled sender drains its claim
    # queue completely — THEN the pause arrives, deterministically after
    # the last claim (the racy ordering the flaky version only hit under
    # machine load)
    assert sent_done.wait(30), "sender never finished claiming"
    assert mgr.pause("late-pause")
    gate.set()
    assert task.wait_idle(30)
    assert task.status == task.PAUSED, task.events[-5:]

    state = mgr.service.markers.load("late-pause")
    done_ranges = state["files"]["big.bin"]["done"]
    done_bytes = sum(length for _, length in done_ranges)
    assert 0 < done_bytes < len(payload)

    # resume closes only the holes
    assert mgr.resume("late-pause")
    assert task.wait(60)
    assert task.status == task.SUCCEEDED, task.events[-5:]
    dst.start(None)
    assert dst.store.get("big.bin") == payload
    mgr.shutdown()


def test_resume_races_inflight_pause(tmp_path):
    """resume() fired immediately after pause() — before the run loop
    drains — must still re-queue the task, never wedge it in PAUSED."""
    payload = os.urandom(4 * MB)
    src = seeded_posix(tmp_path, {"big.bin": payload})

    gate = threading.Event()
    reached = threading.Event()
    seen = {"n": 0}
    lock = threading.Lock()

    class GateMemory(MemoryConnector):
        def recv(self, session, path, channel):
            outer = self

            class Wrap:
                def __getattr__(w, k):
                    return getattr(channel, k)

                def read(w, offset, length):
                    with lock:
                        seen["n"] += length
                        hit = seen["n"] >= MB
                    if hit:
                        reached.set()
                        gate.wait(timeout=30)
                    return channel.read(offset, length)

            super().recv(session, path, Wrap())

    dst = GateMemory()
    mgr = make_manager(tmp_path)
    opts = TransferOptions(startup_cost=0.0, blocksize=256 * 1024,
                           parallelism=1, concurrency=1)
    task = mgr.submit(Endpoint(src, "big.bin"), Endpoint(dst, "big.bin"),
                      opts, task_id="race1")
    assert reached.wait(30)
    assert mgr.pause("race1")
    # no wait_idle: the pause is still draining when we resume
    assert mgr.resume("race1")
    gate.set()
    assert task.wait(60)
    assert task.status == task.SUCCEEDED, task.events[-5:]
    dst.start(None)
    assert dst.store.get("big.bin") == payload
    mgr.shutdown()


def test_pause_queued_and_cancel(tmp_path):
    files = {f"d/f{i}.bin": os.urandom(32 * 1024) for i in range(3)}
    src = seeded_posix(tmp_path, files)
    dst = MemoryConnector()
    mgr = make_manager(tmp_path, max_workers=1)

    gate = threading.Event()
    reached = threading.Event()

    class SlowSrc(PosixConnector):
        def send(self, session, path, channel):
            reached.set()
            gate.wait(timeout=30)
            return super().send(session, path, channel)

    slow = SlowSrc(src.root)
    opts = TransferOptions(startup_cost=0.0, coalesce_threshold=0)
    t_busy = mgr.submit(Endpoint(slow, "d"), Endpoint(dst, "busy"), opts,
                        task_id="busy")
    t_queued = mgr.submit(Endpoint(src, "d"), Endpoint(dst, "q"), opts,
                          task_id="queued")
    t_cancel = mgr.submit(Endpoint(src, "d"), Endpoint(dst, "c"), opts,
                          task_id="doomed")
    assert reached.wait(10)
    # one-slot manager: the other two are still queued -> deterministic
    assert mgr.pause("queued")
    assert t_queued.status == t_queued.PAUSED
    assert mgr.cancel("doomed")
    assert t_cancel.status == t_cancel.CANCELLED
    gate.set()
    assert t_busy.wait(60)
    # paused task does not run until resumed
    assert t_queued.status == t_queued.PAUSED
    # wait_all must not wedge on (or wait for) the paused task
    assert mgr.wait_all(timeout=10)
    assert t_queued.status == t_queued.PAUSED
    assert mgr.resume("queued")
    assert t_queued.wait(60)
    assert t_queued.status == t_queued.SUCCEEDED
    assert mgr.wait_all(timeout=60)
    dst.start(None)
    assert dst.store.get("q/f0.bin") == files["d/f0.bin"]
    # cancelled before running: nothing landed
    assert not any(k.startswith("c/") for k in dst.store.keys())
    mgr.shutdown()


def test_tenant_fair_round_robin(tmp_path):
    """A tenant flooding the queue cannot starve another: dispatch order
    alternates tenants even when one submitted everything first."""
    files = {"d/f.bin": os.urandom(16 * 1024)}
    src = seeded_posix(tmp_path, files)
    dst = MemoryConnector()
    creds = CredentialStore()
    creds.register("src-alice", Credential("local-user",
                                           {"identity": "alice"}))
    creds.register("src-bob", Credential("local-user", {"identity": "bob"}))
    mgr = make_manager(tmp_path, creds, max_workers=1,
                       per_endpoint_cap=None)

    gate = threading.Event()

    class Gated(PosixConnector):
        def send(self, session, path, channel):
            gate.wait(timeout=30)
            return super().send(session, path, channel)

    gated = Gated(src.root)
    opts = TransferOptions(startup_cost=0.0)
    # alice floods 4 tasks, then bob submits 2
    for i in range(4):
        mgr.submit(Endpoint(gated, "d", "src-alice"),
                   Endpoint(dst, f"a{i}"), opts, task_id=f"a{i}")
    for i in range(2):
        mgr.submit(Endpoint(gated, "d", "src-bob"),
                   Endpoint(dst, f"b{i}"), opts, task_id=f"b{i}")
    gate.set()
    assert mgr.wait_all(timeout=60)
    order = [tenant for tenant, _ in mgr.metrics.dispatch_log]
    # bob's first task is dispatched before alice's queue drains
    first_bob = order.index("bob")
    assert first_bob <= 2, order
    assert mgr.metrics.dispatches_by_tenant == {"alice": 4, "bob": 2}
    mgr.shutdown()


def test_priority_within_tenant(tmp_path):
    files = {"d/f.bin": os.urandom(8 * 1024)}
    src = seeded_posix(tmp_path, files)
    dst = MemoryConnector()
    mgr = make_manager(tmp_path, max_workers=1)

    gate = threading.Event()

    class Gated(PosixConnector):
        def send(self, session, path, channel):
            gate.wait(timeout=30)
            return super().send(session, path, channel)

    gated = Gated(src.root)
    opts = TransferOptions(startup_cost=0.0)
    mgr.submit(Endpoint(gated, "d"), Endpoint(dst, "o0"), opts,
               task_id="first")  # occupies the slot
    mgr.submit(Endpoint(gated, "d"), Endpoint(dst, "o1"), opts,
               task_id="later", priority=5)
    mgr.submit(Endpoint(gated, "d"), Endpoint(dst, "o2"), opts,
               task_id="urgent", priority=0)
    gate.set()
    assert mgr.wait_all(timeout=60)
    ids = [tid for _, tid in mgr.metrics.dispatch_log]
    assert ids.index("urgent") < ids.index("later")
    mgr.shutdown()


def test_session_sharing_across_tasks(tmp_path):
    """One Session per endpoint for the whole fleet — not one per task."""
    files = {f"d/f{i}.bin": os.urandom(16 * 1024) for i in range(2)}
    src = seeded_posix(tmp_path, files)
    dst = OpCountingMemory()
    mgr = make_manager(tmp_path, max_workers=2)
    opts = TransferOptions(startup_cost=0.0)
    for i in range(4):
        mgr.submit(Endpoint(src, "d"), Endpoint(dst, f"out{i}", "dst-ep"),
                   opts, task_id=f"s{i}")
    assert mgr.wait_all(timeout=60)
    assert dst.starts == 1  # shared, not 4
    assert mgr.sessions.live_sessions == 2  # src + dst, still warm
    mgr.shutdown()
    assert mgr.sessions.live_sessions == 0


def _mk_model(route, t0, R, s0=0.0, B=GB):
    return PerfModel(route=route, t0=t0, alpha=B / R + s0,
                     bytes_total=int(B), s0=s0)


def test_advisor_route_selection_and_refit(tmp_path):
    """Candidates are placed by the fitted models; predictions and
    actuals land in TaskStats; the observation log refits the route."""
    files = {f"d/f{i}.bin": os.urandom(4 * 1024) for i in range(8)}
    src = seeded_posix(tmp_path, files)
    fast_dst = MemoryConnector()
    slow_dst = MemoryConnector()
    advisor = Advisor([
        Route("fast", _mk_model("fast", t0=0.01, R=500e6)),
        Route("slow", _mk_model("slow", t0=2.0, R=5e6)),
    ])
    mgr = make_manager(tmp_path, advisor=advisor, max_workers=1)
    candidates = [
        RouteCandidate("slow", Endpoint(src, "d"),
                       Endpoint(slow_dst, "out")),
        RouteCandidate("fast", Endpoint(src, "d"),
                       Endpoint(fast_dst, "out")),
    ]
    shared_opts = TransferOptions(startup_cost=0.0)
    task = mgr.submit(candidates=candidates, options=shared_opts,
                      task_id="routed", sync=True)
    assert task.status == task.SUCCEEDED
    assert task.stats.route == "fast"
    # the advisor tunes a per-task copy, never the caller's options
    assert shared_opts.concurrency == TransferOptions().concurrency
    assert shared_opts.coalesce_threshold == \
        TransferOptions().coalesce_threshold
    assert task.stats.predicted_seconds > 0
    assert task.stats.actual_model_seconds >= 0
    fast_dst.start(None)
    assert fast_dst.store.get("out/f0.bin") == files["d/f0.bin"]
    assert slow_dst.store.keys() == []

    # vary the workload so the observation log supports a refit
    for i, n in enumerate((2, 4, 6)):
        sub = {f"w{i}/g{j}.bin": os.urandom(2 * 1024) for j in range(n)}
        subsrc = seeded_posix(os.path.join(str(tmp_path), f"w{i}"), sub)
        mgr.submit(candidates=[
            RouteCandidate("fast", Endpoint(subsrc, f"w{i}"),
                           Endpoint(fast_dst, f"r{i}"))],
            options=TransferOptions(startup_cost=0.0),
            task_id=f"obs{i}", sync=True)
    obs = mgr.observations("fast")
    assert len(obs) == 4
    model = mgr.refit_route("fast", min_points=3)
    assert model is not None
    assert advisor.routes[0].model is model
    mgr.shutdown()


def test_unknown_candidate_route_raises(tmp_path):
    mgr = make_manager(tmp_path, advisor=Advisor())
    with pytest.raises(ValueError):
        mgr.submit(candidates=[RouteCandidate(
            "nope", Endpoint(MemoryConnector(), "a"),
            Endpoint(MemoryConnector(), "b"))])
    with pytest.raises(ValueError):
        mgr.submit()  # neither src/dst nor candidates
    mgr.shutdown(wait=False)


# --------------------------------------------------------------------------
# per-task model-time attribution (the shared-clock skew fix)
# --------------------------------------------------------------------------
def test_concurrent_tasks_model_time_not_inflated(tmp_path):
    """With max_workers >= 4 and overlapping tasks, each task's
    ``actual_model_seconds`` is exactly its OWN charges — a concurrent
    task's latency never inflates it — and the per-route observations
    carry those exact values."""
    n = 4
    latch_n = [0]
    latch = threading.Event()
    lock = threading.Lock()

    class LatchMemory(MemoryConnector):
        """First recv of every task blocks until all n tasks are
        mid-flight, so the tasks genuinely overlap."""

        def recv(self, session, path, channel):
            with lock:
                latch_n[0] += 1
                if latch_n[0] >= n:
                    latch.set()
            assert latch.wait(30), "fleet never overlapped"
            return super().recv(session, path, channel)

    dst = LatchMemory()
    # t0=0: per-file path (coalesce threshold 0) and cc=1 from the ladder
    advisor = Advisor([Route("r", _mk_model("r", t0=0.0, R=1e12),
                             max_concurrency=1)])
    mgr = make_manager(tmp_path, advisor=advisor, max_workers=n,
                       per_endpoint_cap=None, refit_every=0)
    clock = mgr.service.clock
    tasks, expected = [], []
    for i in range(n):
        n_files = i + 2
        files = {f"d/f{j}.bin": os.urandom(1024) for j in range(n_files)}
        src = seeded_posix(os.path.join(str(tmp_path), f"s{i}"), files)
        opts = TransferOptions(startup_cost=0.5 * (i + 1),
                               file_pipeline_cost=0.125, parallelism=1)
        tasks.append(mgr.submit(
            candidates=[RouteCandidate("r", Endpoint(src, "d"),
                                       Endpoint(dst, f"out{i}"))],
            options=opts, task_id=f"attr{i}",
            n_files=n_files, nbytes=n_files * 1024))
        # posix->memory over loopback charges exactly startup + one
        # pipelined control exchange per file — nothing else
        expected.append(0.5 * (i + 1) + 0.125 * n_files)
    assert mgr.wait_all(timeout=60)
    for i, task in enumerate(tasks):
        assert task.status == task.SUCCEEDED, task.events[-3:]
        assert task.stats.actual_model_seconds == \
            pytest.approx(expected[i], abs=1e-9), \
            f"task {i}: cross-task inflation"
    # the four tasks PARTITION the shared clock: their charges sum to
    # (not each observe) the total modeled time
    assert sum(t.stats.actual_model_seconds for t in tasks) == \
        pytest.approx(clock.virtual_elapsed, abs=1e-9)
    obs = {nf: sec for nf, _, sec in mgr.observations("r")}
    for i, task in enumerate(tasks):
        assert obs[i + 2] == pytest.approx(expected[i], abs=1e-9)
    mgr.shutdown()


def test_auto_refit_loop_converges_and_retunes_queued(tmp_path):
    """The closed loop: a deliberately miscalibrated seed model is refit
    automatically every ``refit_every`` completions, still-queued
    submissions pick up the refreshed knobs + prediction, and post-refit
    median prediction error collapses."""
    dst = MemoryConnector()
    # seed model is ~1000x off: t0=5 s/file when the true per-file cost
    # is the 5 ms pipelined exchange
    advisor = Advisor([Route("r", _mk_model("r", t0=5.0, R=1e12),
                             max_concurrency=1)])
    mgr = make_manager(tmp_path, advisor=advisor, max_workers=1,
                       per_endpoint_cap=None, refit_every=3)
    tasks = []
    seed_predictions = {}
    for i in range(6):
        n_files = 2 + 2 * (i % 3)
        files = {f"d/f{j}.bin": os.urandom(512) for j in range(n_files)}
        src = seeded_posix(os.path.join(str(tmp_path), f"s{i}"), files)
        t = mgr.submit(
            candidates=[RouteCandidate("r", Endpoint(src, "d"),
                                       Endpoint(dst, f"out{i}"))],
            options=TransferOptions(startup_cost=0.01),
            task_id=f"refit{i}", n_files=n_files, nbytes=n_files * 512)
        seed_predictions[t.task_id] = t.stats.predicted_seconds
        tasks.append(t)
    assert mgr.wait_all(timeout=60)
    for t in tasks:
        assert t.status == t.SUCCEEDED, t.events[-3:]
    assert mgr.metrics.refits.get("r", 0) >= 1
    # queued submissions were re-predicted by the refreshed model
    gens = [g for _, g, _, _ in mgr.metrics.prediction_log]
    assert 0 in gens and max(gens) >= 1
    retuned = [t for t in tasks
               if t.stats.predicted_seconds != seed_predictions[t.task_id]]
    assert retuned, "no queued submission picked up the refit model"
    pre = mgr.prediction_error(generation=0)
    post = mgr.prediction_error(min_generation=1)
    assert post < pre, (pre, post)
    # the seed model was off by orders of magnitude; the refit one must
    # actually predict (not just improve)
    assert post < 1.0
    mgr.shutdown()


def test_observation_history_is_bounded(tmp_path):
    """Stale observations age out: the per-route ring keeps only the
    most recent ``history_limit`` points."""
    dst = MemoryConnector()
    advisor = Advisor([Route("r", _mk_model("r", t0=0.0, R=1e12),
                             max_concurrency=1)])
    mgr = make_manager(tmp_path, advisor=advisor, max_workers=1,
                       refit_every=0, history_limit=4)
    files = {"d/f.bin": os.urandom(256)}
    src = seeded_posix(tmp_path, files)
    for i in range(7):
        mgr.submit(candidates=[RouteCandidate(
            "r", Endpoint(src, "d"), Endpoint(dst, f"o{i}"))],
            options=TransferOptions(startup_cost=0.1 * (i + 1)),
            task_id=f"h{i}", n_files=1, nbytes=256, sync=True)
    obs = mgr.observations("r")
    assert len(obs) == 4
    # the survivors are the most recent four (largest startup charges)
    assert [round(sec, 6) for _, _, sec in obs] == \
        [round(0.1 * (i + 1) + 0.005, 6) for i in range(3, 7)]
    mgr.shutdown()


class _BrokenHistogram:
    def observe(self, *args, **kwargs):
        raise RuntimeError("accounting fault")


def test_task_completes_when_its_accounting_raises(tmp_path, monkeypatch):
    """The manager completes a task after filing its run; a fault in
    that filing still releases whoever waits on the task (and still
    reaches the worker thread's exception hook)."""
    raised = []
    caught = threading.Event()

    def hook(args):
        raised.append(args.exc_value)
        caught.set()
    monkeypatch.setattr(threading, "excepthook", hook)
    mgr = make_manager(tmp_path, max_workers=1)
    mgr._task_seconds = _BrokenHistogram()
    src = seeded_posix(tmp_path, {"d/f.bin": os.urandom(256)})
    task = mgr.submit(Endpoint(src, "d"), Endpoint(MemoryConnector(), "o"),
                      TransferOptions(startup_cost=0.0), task_id="acct")
    assert task.wait(30)
    assert task.status == task.SUCCEEDED
    assert caught.wait(30)
    assert [str(e) for e in raised] == ["accounting fault"]
    mgr.shutdown()


def test_refit_convergence_under_multitenant_chaos(tmp_path):
    """Acceptance: a multi-tenant fleet under fault injection still
    shrinks its median prediction error once the online refit loop has
    fired (run_multi's convergence invariant, strict)."""
    runner = ScenarioRunner(str(tmp_path), clock=Clock(scale=0.0))
    advisor = Advisor([Route("fleet", _mk_model("fleet", t0=3.0, R=1e9),
                             max_concurrency=1)])
    schedule = (FaultSchedule(seed=5)
                .transient(op="read", at=4, times=2)
                .latency(op="stat", delay=0.05, times=3))
    res = runner.run_multi(n_tasks=10, tenants=("alice", "bob", "carol"),
                           trees=("mixed", "many-small"),
                           route="posix->memory", schedule=schedule,
                           max_workers=3, per_endpoint_cap=None,
                           advisor=advisor, refit_every=3, seed=3,
                           strict=True)
    assert res.ok
    mgr = res.manager
    assert mgr.metrics.refits.get("fleet", 0) >= 1
    assert mgr.prediction_error(min_generation=1) < \
        mgr.prediction_error(generation=0)


# --------------------------------------------------------------------------
# scheduler races
# --------------------------------------------------------------------------
def test_cancel_while_queued_races_pump(tmp_path):
    """Cancels fired from other threads while _pump is dispatching:
    every task drains to a terminal state, the queue empties, and the
    accounting adds up — no wedge, no double-dispatch."""
    files = {"d/f.bin": os.urandom(4 * 1024)}
    src = seeded_posix(tmp_path, files)
    dst = MemoryConnector()
    mgr = make_manager(tmp_path, max_workers=2, per_endpoint_cap=None)

    gate = threading.Event()

    class Gated(PosixConnector):
        def send(self, session, path, channel):
            gate.wait(timeout=30)
            return super().send(session, path, channel)

    gated = Gated(src.root)
    opts = TransferOptions(startup_cost=0.0)
    n = 24
    tasks = [mgr.submit(Endpoint(gated, "d"), Endpoint(dst, f"o{i}"),
                        opts, task_id=f"c{i}") for i in range(n)]
    doomed = [f"c{i}" for i in range(0, n, 3)]

    def chop():
        for tid in doomed:
            mgr.cancel(tid)

    cancellers = [threading.Thread(target=chop) for _ in range(3)]
    for t in cancellers:
        t.start()
    gate.set()  # open the flood while cancels are in flight
    for t in cancellers:
        t.join()
    assert mgr.wait_all(timeout=120)
    counts = mgr.counts()
    assert counts["queued"] == 0 and counts["running"] == 0
    for task in tasks:
        assert task.status in (task.SUCCEEDED, task.CANCELLED), task.status
    m = mgr.metrics
    assert m.completed + m.cancelled == n
    # a task cancelled while queued must never have been dispatched
    dispatched = {tid for _, tid in m.dispatch_log}
    for task in tasks:
        if task.status == task.CANCELLED and task.task_id not in dispatched:
            assert task.stats.bytes_done == 0
    mgr.shutdown()


def test_resume_pending_cycles_under_concurrent_pump(tmp_path):
    """Repeated pause->immediate-resume cycles against a running fleet
    (so _pump is constantly re-entered) always drain to completion,
    byte-exact."""
    payload = {f"d/f{i}.bin": os.urandom(64 * 1024) for i in range(8)}
    src = seeded_posix(tmp_path, payload)
    dst = MemoryConnector()

    class Dawdling(PosixConnector):
        def send(self, session, path, channel):
            time.sleep(0.002)  # a window for pause to land mid-run
            return super().send(session, path, channel)

    slow = Dawdling(src.root)
    mgr = make_manager(tmp_path, max_workers=3, per_endpoint_cap=None)
    opts = TransferOptions(startup_cost=0.0, concurrency=2,
                           coalesce_threshold=0)
    main = mgr.submit(Endpoint(slow, "d"), Endpoint(dst, "main"), opts,
                      task_id="main")
    noise = [mgr.submit(Endpoint(slow, "d"), Endpoint(dst, f"n{i}"), opts,
                        task_id=f"n{i}") for i in range(4)]
    for _ in range(5):
        mgr.pause("main")
        mgr.resume("main")  # may race the drain -> resume_pending path
        time.sleep(0.005)
    # a final resume in case the last pause landed after its resume
    main.wait_idle(60)
    mgr.resume("main")
    assert mgr.wait_all(timeout=120)
    assert main.status == main.SUCCEEDED, main.events[-5:]
    for t in noise:
        assert t.status == t.SUCCEEDED
    dst.start(None)
    for name, data in payload.items():
        assert dst.store.get("main/" + name[len("d/"):]) == data
    mgr.shutdown()


# --------------------------------------------------------------------------
# session pool generations
# --------------------------------------------------------------------------
def test_session_pool_stale_release_is_noop(tmp_path):
    """A holder of a dead session releasing after the pool replaced it
    must not touch the replacement's refcount or destroy it."""
    from repro.core import SessionPool
    conn = MemoryConnector()
    creds = CredentialStore()
    pool = SessionPool(creds)
    ep = Endpoint(conn, "a", "ep")
    s1 = pool.acquire(ep)
    # the provider drops the session mid-task
    conn.destroy(s1)
    assert s1.closed
    # next task replaces the generation
    s2 = pool.acquire(ep)
    assert s2 is not s1 and not s2.closed
    # the stale holder's release is a no-op against the new generation
    pool.release(ep, s1)
    assert not s2.closed
    assert pool.live_sessions == 1
    # and a second stale release cannot drive anything negative / kill s2
    pool.release(ep, s1)
    pool.release(ep, s2)
    assert not s2.closed  # refcount 0: stays warm, not destroyed
    assert pool.live_sessions == 1
    pool.close_all()
    assert s2.closed


def test_session_pool_usable_after_close_all(tmp_path):
    """close_all retires the current generations only: the pool keeps
    sessions warm for work that starts afterwards instead of destroying
    every future session at refcount zero."""
    from repro.core import SessionPool
    conn = MemoryConnector()
    pool = SessionPool(CredentialStore())
    ep = Endpoint(conn, "a", "ep")
    s1 = pool.acquire(ep)
    pool.release(ep, s1)
    pool.close_all()
    assert s1.closed and pool.live_sessions == 0
    # the pool drained once; it must still pool (keep warm) afterwards
    s2 = pool.acquire(ep)
    pool.release(ep, s2)
    assert not s2.closed
    assert pool.live_sessions == 1
    s3 = pool.acquire(ep)
    assert s3 is s2  # warm reuse, not a fresh start
    pool.release(ep, s3)
    pool.close_all()
    assert s2.closed


def test_session_drop_mid_task_spares_replacement(tmp_path):
    """A chaos session drop mid-task (via FaultProxyConnector) closes
    the shared session; the victim task's stale release must not tear
    down the replacement the rest of the fleet is using."""
    from repro.connectors.faultproxy import FaultProxyConnector
    from repro.core.errors import SessionClosed

    files = {f"d/f{i}.bin": os.urandom(16 * 1024) for i in range(3)}
    src = seeded_posix(tmp_path, files)

    class DroppingProxy(FaultProxyConnector):
        """An injected drop also closes the live session, the way a real
        transport teardown would."""

        def recv(self, session, path, channel):
            try:
                return super().recv(session, path, channel)
            except SessionClosed:
                session.closed = True
                raise

    schedule = FaultSchedule(seed=1).session_drop(op="recv", at=1, times=1,
                                                  scope="global")
    dst = DroppingProxy(MemoryConnector(), schedule,
                        clock=Clock(scale=0.0))
    mgr = make_manager(tmp_path, max_workers=1)
    opts = TransferOptions(startup_cost=0.0, coalesce_threshold=0,
                           concurrency=1)
    victim = mgr.submit(Endpoint(src, "d"), Endpoint(dst, "v", "dst-ep"),
                        opts, task_id="victim")
    assert victim.wait(60)
    assert victim.status == victim.FAILED  # SessionClosed is permanent
    # the fleet keeps going on a fresh generation
    healthy = mgr.submit(Endpoint(src, "d"), Endpoint(dst, "h", "dst-ep"),
                         opts, task_id="healthy", sync=True)
    assert healthy.status == healthy.SUCCEEDED, healthy.events[-5:]
    inner = dst.inner
    inner.start(None)
    assert inner.store.get("h/f0.bin") == files["d/f0.bin"]
    # all references drained; the replacement session is alive and warm
    assert all(e.refs == 0 for e in mgr.sessions._by_session.values())
    assert mgr.sessions.live_sessions == 2  # src + replacement dst
    mgr.shutdown()
    assert mgr.sessions.live_sessions == 0


def test_degenerate_service_submit_is_managed(tmp_path):
    """A bare service.submit rides the same control plane (the implicit
    manager) and still behaves exactly as before."""
    from repro.core import TransferService
    svc = TransferService(marker_root=os.path.join(str(tmp_path), "m"),
                         clock=Clock(scale=0.0))
    payload = os.urandom(MB)
    src = seeded_posix(tmp_path, {"a.bin": payload})
    dst = MemoryConnector()
    task = svc.submit(Endpoint(src, "a.bin"), Endpoint(dst, "a.bin"),
                      TransferOptions(startup_cost=0.0), sync=True)
    assert task.status == task.SUCCEEDED
    dst.start(None)
    assert dst.store.get("a.bin") == payload
    assert svc.default_manager().metrics.completed == 1
