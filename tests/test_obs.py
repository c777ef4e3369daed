"""Observability plane: model-time tracing, the metrics registry, and
per-task time-budget attribution.

Covers the tentpole invariants end-to-end: spans ride the charge-owner
machinery across pool/sender threads, trace ids survive federation
handoff, same-seed runs export byte-identical canonical traces, and
``TaskStats.time_budget()`` decomposes ``actual_model_seconds`` exactly
(within float tolerance) on chaos fleets.  Plus the satellites: bounded
event/rate-sample rings with exact dropped counters, and lint rule
R006 (``Tracer.span`` is a ``with`` context manager ONLY).

Everything here carries the ``obs`` marker (its own CI lane).
"""

import json
import os
import textwrap
import threading

import pytest

from repro.connectors import MemoryConnector
from repro.core import (CredentialStore, Endpoint, FaultSchedule,
                        TransferManager, TransferOptions)
from repro.core.clock import Clock, bind_charge_owner, charge_to, host_now
from repro.core.transfer import TransferTask
from repro.fed import TransferSpec
from repro.lint.engine import run_lint
from repro.obs import (CATEGORIES, DEFAULT_BUCKETS, MetricsRegistry,
                       NULL_TRACER, Tracer, host_spans, thread_span)
from repro.obs import trace as obs_trace
from repro.sim import ScenarioRunner

KB = 1024

pytestmark = pytest.mark.obs


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def make_manager(tmp_path, **kw):
    kw.setdefault("max_workers", 4)
    kw.setdefault("per_endpoint_cap", 2)
    kw.setdefault("clock", Clock(scale=0.0))
    return TransferManager(credential_store=CredentialStore(),
                           marker_root=os.path.join(str(tmp_path), "markers"),
                           **kw)


class _GatedMemory(MemoryConnector):
    """A memory store whose sessions open only once ``gate`` is set."""

    def __init__(self, gate):
        super().__init__()
        self.gate = gate

    def start(self, credential):
        assert self.gate.wait(30)
        return super().start(credential)


def seed_memory(files):
    conn = MemoryConnector()
    for name, payload in files.items():
        conn.store.put(name, payload)
    return conn


def run_fleet(tmp_path, n_tasks=3, n_files=4, **mgr_kw):
    """Small traced fleet over the per-file data plane; returns
    (manager, tasks)."""
    src = seed_memory({f"t{t}/f{i}.bin": bytes([t]) * (8 * KB)
                       for t in range(n_tasks) for i in range(n_files)})
    dst = MemoryConnector()
    mgr = make_manager(tmp_path, **mgr_kw)
    opts = TransferOptions(startup_cost=0.0, concurrency=2,
                           coalesce_threshold=0)
    tasks = [mgr.submit(Endpoint(src, f"t{t}", f"src{t}"),
                        Endpoint(dst, f"out/t{t}", f"dst{t}"),
                        opts, task_id=f"obs-{t}",
                        tenant=("alice", "bob")[t % 2])
             for t in range(n_tasks)]
    assert mgr.wait_all(timeout=120)
    return mgr, tasks


# --------------------------------------------------------------------------
# tracer unit semantics
# --------------------------------------------------------------------------
def test_span_outside_binding_records_nothing():
    tracer = Tracer(clock=Clock(scale=0.0))
    with tracer.span("orphan", "wire"):
        pass
    assert tracer.spans_recorded == 0


def test_bind_and_span_attach_and_tally():
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    with tracer.bind("trace-1", "t1"):
        with charge_to("t1"):
            with tracer.span("send", "wire", path="a.bin"):
                clock.sleep(0.5)
    spans = tracer.spans()
    assert [(s.trace_id, s.task_id, s.name, s.category)
            for s in spans] == [("trace-1", "t1", "send", "wire")]
    assert spans[0].self_seconds == pytest.approx(0.5)
    assert tracer.category_seconds("t1") == {"wire": pytest.approx(0.5)}
    tracer.forget("t1")
    assert tracer.category_seconds("t1") == {}


def test_nested_span_charges_innermost_only():
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    with tracer.bind("trace-1", "t1"):
        with charge_to("t1"):
            with tracer.span("outer", "overhead"):
                clock.sleep(1.0)
                with tracer.span("inner", "integrity"):
                    clock.sleep(0.25)
                clock.sleep(0.5)
    per = tracer.category_seconds("t1")
    assert per["integrity"] == pytest.approx(0.25)
    assert per["overhead"] == pytest.approx(1.5)


def test_disabled_tracer_is_inert():
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock, enabled=False)
    with tracer.bind("trace-1", "t1"):
        with tracer.span("send", "wire"):
            clock.sleep(0.5)
    assert tracer.spans_recorded == 0
    assert tracer.category_seconds("t1") == {}
    assert NULL_TRACER.enabled is False


def test_record_is_charge_free():
    tracer = Tracer(clock=Clock(scale=0.0))
    tracer.record("queue-wait", "queue", 1.0, 3.5,
                  trace_id="trace-1", task_id="t1", tenant="alice")
    assert tracer.spans_recorded == 1
    # observed windows never feed the time-budget tally
    assert tracer.category_seconds("t1") == {}
    span = tracer.spans()[0]
    assert (span.t0, span.t1) == (1.0, 3.5)
    assert span.self_seconds == 0.0


def test_span_ring_bounded_with_exact_drop_count():
    tracer = Tracer(clock=Clock(scale=0.0), max_spans=4)
    for i in range(10):
        tracer.record(f"w{i}", "queue", 0.0, 0.0, task_id="t1")
    assert len(tracer.spans()) == 4
    assert tracer.spans_dropped == 6
    assert tracer.spans_recorded == 10
    # survivors are the newest
    assert [s.name for s in tracer.spans()] == ["w6", "w7", "w8", "w9"]


def test_charge_crosses_threads_via_bind_charge_owner():
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    with tracer.bind("trace-1", "t1"):
        with charge_to("t1"):
            with tracer.span("pool-op", "wire"):
                # capture owner + span context exactly like the
                # connector pools do, run the work on a foreign thread
                fn = bind_charge_owner(lambda: clock.sleep(0.75))
                th = threading.Thread(target=fn)
                th.start()
                th.join()
    assert tracer.category_seconds("t1") == {"wire": pytest.approx(0.75)}
    assert clock.charged("t1") == pytest.approx(0.75)


# --------------------------------------------------------------------------
# host time: stamps, the process-wide host record, the profiler
# --------------------------------------------------------------------------
def test_span_host_stamps_lie_between_perf_counter_reads():
    tracer = Tracer(clock=Clock(scale=0.0))
    with tracer.bind("trace-h", "th"):
        before = host_now()
        with tracer.span("host-stamped", "wire"):
            pass
        after = host_now()
    span = tracer.spans()[-1]
    assert before <= span.host_t0 <= span.host_t1 <= after
    rec = host_spans("host-stamped").spans[-1]
    assert (rec.category, rec.t0, rec.t1) == ("wire", span.host_t0,
                                              span.host_t1)
    assert rec.thread == threading.get_ident()


def test_host_ring_bounded_with_exact_drop_count(monkeypatch):
    monkeypatch.setattr(obs_trace, "_HOST", obs_trace._HostRing(4))
    tracer = Tracer(clock=Clock(scale=0.0))
    with tracer.bind("trace-r", "tr"):
        for i in range(10):
            with tracer.span(f"r{i}"):
                pass
    record = host_spans()
    assert [s.name for s in record.spans] == ["r6", "r7", "r8", "r9"]
    assert record.dropped == 6
    assert [s.name for s in host_spans("r9").spans] == ["r9"]
    assert host_spans("r9").dropped == 6
    # the latest dropped span, r5, ended before r6 opened: a window
    # opening at r6 lost nothing, one opening at r5 did
    r5_end = record.dropped_until
    assert r5_end <= record.spans[0].t0
    assert record.whole_since(record.spans[0].t0)
    assert not record.whole_since(r5_end - 1e-9)


def test_host_record_outlives_its_tracer(monkeypatch):
    monkeypatch.setattr(obs_trace, "_HOST", obs_trace._HostRing(16))
    tracer = Tracer(clock=Clock(scale=0.0))
    with tracer.bind("trace-o", "to"), tracer.span("kept"):
        pass
    del tracer
    assert [s.name for s in host_spans().spans] == ["kept"]


def test_disabled_tracer_and_unbound_threads_record_no_host_spans(
        monkeypatch):
    monkeypatch.setattr(obs_trace, "_HOST", obs_trace._HostRing(16))
    off = Tracer(clock=Clock(scale=0.0), enabled=False)
    with off.bind("trace-d", "td"), off.span("off"):
        pass
    off.record("off-window", "queue", 0.0, 1.0, host_t0=1.0, host_t1=2.0)
    on = Tracer(clock=Clock(scale=0.0))
    with on.span("unbound"):  # no binding on this thread
        pass
    with thread_span("unbound-thread"):
        pass

    def other_thread():
        with on.span("elsewhere"):
            pass
    with on.bind("trace-b", "tb"):
        th = threading.Thread(target=other_thread)  # not bound across
        th.start()
        th.join(10)
    assert not th.is_alive()
    record = host_spans()
    assert (record.spans, record.dropped) == ([], 0)
    assert on.spans_recorded == 0


def test_record_joins_host_record_only_with_host_stamps(monkeypatch):
    monkeypatch.setattr(obs_trace, "_HOST", obs_trace._HostRing(16))
    tracer = Tracer(clock=Clock(scale=0.0))
    tracer.record("no-host", "queue", 1.0, 2.0, task_id="t1")
    tracer.record("with-host", "queue", 1.0, 2.0, task_id="t1",
                  host_t0=10.0, host_t1=10.5, tenant="alice")
    assert tracer.spans_recorded == 2
    assert [(s.name, s.category, s.t0, s.t1)
            for s in host_spans().spans] == [("with-host", "queue", 10.0,
                                              10.5)]
    with_host = tracer.spans()[-1]
    assert (with_host.t0, with_host.t1) == (1.0, 2.0)
    assert with_host.attrs == {"tenant": "alice"}
    # still charge-free, with or without host stamps
    assert tracer.category_seconds("t1") == {}


def test_inherited_category_leaves_the_budget_unchanged():
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    with tracer.bind("trace-1", "t1"), charge_to("t1"):
        with tracer.span("send", "wire"):
            with thread_span("connector.io"):
                clock.sleep(0.5)
        with thread_span("connector.io"):  # at the root: "other"
            clock.sleep(0.25)
    assert tracer.category_seconds("t1") == {
        "wire": pytest.approx(0.5), "other": pytest.approx(0.25)}
    io = [s for s in tracer.spans() if s.name == "connector.io"]
    assert [s.category for s in io] == ["wire", "other"]


def test_span_annotates_a_running_profiler_trace(tmp_path):
    jax = pytest.importorskip("jax")
    from jax import profiler

    tracer = Tracer(clock=Clock(scale=0.0))
    with tracer.bind("trace-p", "tp"):
        with tracer.span("obs.before-trace"):
            pass
        profiler.start_trace(str(tmp_path))
        try:
            with tracer.span("obs.in-trace", "wire"):
                jax.numpy.zeros(4).block_until_ready()
        finally:
            profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = profiler.ProfileData.from_file(str(path))
    host = {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "obs.in-trace" in host
    assert "obs.before-trace" not in host


# --------------------------------------------------------------------------
# exports
# --------------------------------------------------------------------------
def _trace_some(tracer, clock):
    with tracer.bind("trace-1", "t1"):
        with charge_to("t1"):
            with tracer.span("send", "wire", path="a.bin"):
                clock.sleep(0.5)
            with tracer.span("verify", "integrity", path="a.bin"):
                clock.sleep(0.125)
    tracer.record("queue-wait", "queue", 0.0, 0.25,
                  trace_id="trace-1", task_id="t1", tenant="alice",
                  host_t0=host_now() - 0.25, host_t1=host_now())


def test_jsonl_export_sorted_and_stable(tmp_path):
    paths = []
    for i in range(2):
        clock = Clock(scale=0.0)
        tracer = Tracer(clock=clock)
        _trace_some(tracer, clock)
        p = str(tmp_path / f"trace{i}.jsonl")
        n = tracer.export_jsonl(p)
        assert n == 3
        paths.append(p)
    a, b = (open(p, "rb").read() for p in paths)
    assert a == b
    lines = [json.loads(line) for line in a.decode().splitlines()]
    # sorted by semantic key: category-major (integrity < queue < wire)
    assert [ln["name"] for ln in lines] == ["verify", "queue-wait", "send"]
    for ln in lines:
        assert set(ln) == {"trace_id", "task_id", "name", "category",
                           "attrs", "self_seconds"}


def test_chrome_export_is_loadable_trace_event_json(tmp_path):
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    _trace_some(tracer, clock)
    p = str(tmp_path / "trace.json")
    n = tracer.export_chrome(p)
    with open(p) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert len(events) == n == 3
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["pid"] == "t1" or ev["pid"] == "trace-1"
    # the timeline is host time; the model side rides in args
    send = next(ev for ev in events if ev["name"] == "send")
    assert send["args"]["virtual_t0"] == 0.0
    assert send["args"]["self_seconds"] == pytest.approx(0.5)
    verify = next(ev for ev in events if ev["name"] == "verify")
    assert verify["args"]["virtual_t0"] == pytest.approx(0.5)
    assert verify["ts"] >= send["ts"] + send["dur"]
    wait = next(ev for ev in events if ev["name"] == "queue-wait")
    assert wait["dur"] == pytest.approx(0.25e6, rel=1e-3)
    # a window recorded without host stamps has no place on it
    tracer.record("handoff", "queue", 0.0, 1.0, task_id="t1")
    assert tracer.export_chrome(p) == n


def test_breaker_windows_stay_on_the_chrome_timeline(tmp_path):
    from repro.core.health import EndpointHealth, HealthConfig
    clock = Clock(scale=0.0)
    tracer = Tracer(clock=clock)
    before = host_now()
    health = EndpointHealth(HealthConfig(min_samples=1,
                                         error_threshold=0.3),
                            clock=clock, tracer=tracer)
    health.record_failure("ep")  # closed -> open
    assert health.state("ep") == "open"
    after = host_now()
    p = str(tmp_path / "trace.json")
    assert tracer.export_chrome(p) == 1
    with open(p) as fh:
        (ev,) = json.load(fh)["traceEvents"]
    assert ev["name"] == "breaker-closed" and ev["args"]["to"] == "open"
    # the closed window runs from the endpoint's first sighting
    assert before * 1e6 <= ev["ts"] <= ev["ts"] + ev["dur"] \
        <= after * 1e6 + 1


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("tasks_total", "finished tasks")
    c.inc(site="a", status="SUCCEEDED")
    c.inc(site="a", status="SUCCEEDED")
    c.inc(site="b", status="FAILED")
    g = reg.gauge("queue_depth", "")
    g.set(7, site="a")
    h = reg.histogram("task_model_seconds", "")
    for v in (0.05, 0.5, 5.0):
        h.observe(v, site="a")
    snap = reg.snapshot()
    assert snap["repro_tasks_total"]['{site="a",status="SUCCEEDED"}'] == 2.0
    assert snap["repro_tasks_total"]['{site="b",status="FAILED"}'] == 1.0
    assert snap["repro_queue_depth"]['{site="a"}'] == 7.0
    hist = snap["repro_task_model_seconds"]['{site="a"}']
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(5.55)


def test_histogram_buckets_fixed_and_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "")
    h.observe(0.004)
    h.observe(100.0)
    snap = reg.snapshot()["repro_lat"][""]
    buckets = snap["buckets"]
    assert tuple(sorted(buckets)) == DEFAULT_BUCKETS
    # cumulative, le-style: every bound >= 0.004 counts the small
    # sample; 100.0 first lands at the 300 s bound
    assert buckets[0.005] == 1
    assert buckets[0.1] == 1
    assert buckets[1800.0] == 2
    assert snap["count"] == 2


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x", "")
    with pytest.raises(TypeError):
        reg.gauge("x", "")


def test_scrape_is_deterministic_and_prometheus_shaped():
    def build():
        reg = MetricsRegistry()
        c = reg.counter("tasks_total", "done")
        c.inc(tenant="b")
        c.inc(tenant="a")
        reg.histogram("secs", "").observe(1.0, site="s")
        return reg.scrape()
    a, b = build(), build()
    assert a == b
    assert 'repro_tasks_total{tenant="a"} 1' in a
    assert "# TYPE repro_tasks_total counter" in a
    assert 'repro_secs_bucket{le="+Inf",site="s"} 1' in a


def test_register_collector_feeds_snapshot():
    reg = MetricsRegistry()
    reg.register_collector(lambda: {"bus_published": 42,
                                    "depth_by_site": {"x": 7}})
    reg.register_collector(lambda: 1 / 0)  # raising collector: skipped
    snap = reg.snapshot()
    assert snap["repro_bus_published"] == 42
    assert snap["repro_depth_by_site"] == {"x": 7}
    assert "repro_bus_published 42" in reg.scrape()


# --------------------------------------------------------------------------
# bounded task rings (satellite a)
# --------------------------------------------------------------------------
def test_task_event_ring_bounded_with_exact_drop_count(monkeypatch):
    monkeypatch.setattr(TransferTask, "EVENTS_WINDOW", 8)
    task = TransferTask("t1", clock=Clock(scale=0.0))
    for i in range(20):
        task.log(f"event {i}")
    events = task.events
    assert len(events) == 8
    assert task.events_dropped == 12
    assert [msg for _, msg in events] == [f"event {i}"
                                          for i in range(12, 20)]


def test_rate_sample_ring_bounded_with_exact_drop_count(monkeypatch):
    monkeypatch.setattr(TransferTask, "RATE_WINDOW", 8)
    task = TransferTask("t1", clock=Clock(scale=0.0))
    task.stats.bytes_total = 20
    for _ in range(20):
        task._bytes_tick(1)
    assert len(task._rate_samples) == 8
    assert task.rate_samples_dropped == 12


# --------------------------------------------------------------------------
# manager integration: budgets, trace ids, metrics stream
# --------------------------------------------------------------------------
def test_fleet_budgets_sum_exactly_and_spans_attach(tmp_path):
    mgr, tasks = run_fleet(tmp_path)
    tracer = mgr.tracer
    assert tracer.spans_recorded > len(tasks)
    by_task = {}
    for s in tracer.spans():
        if s.task_id:
            by_task.setdefault(s.task_id, set()).add(s.trace_id)
    for task in tasks:
        assert task.status == task.SUCCEEDED
        assert task.trace_id == f"trace-{task.task_id}"
        # spans from this task's pool/sender threads all carry ITS
        # trace id — attribution never bleeds across fleet-mates
        assert by_task[task.task_id] == {task.trace_id, ""} \
            or by_task[task.task_id] == {task.trace_id}
        budget = task.stats.time_budget()
        total = sum(budget.values())
        assert abs(total - task.stats.actual_model_seconds) < 1e-6
        assert set(budget) - {"other"} <= set(CATEGORIES)
        # the per-file data plane slept under wire/overhead spans
        assert task.stats.span_seconds
    # finished tasks were forgotten from the live tally table
    for task in tasks:
        assert tracer.category_seconds(task.task_id) == {}


def test_queue_wait_span_recorded(tmp_path):
    mgr, tasks = run_fleet(tmp_path, max_workers=1)
    waits = [s for s in mgr.tracer.spans() if s.name == "queue-wait"]
    assert {s.task_id for s in waits} == {t.task_id for t in tasks}
    for s in waits:
        assert s.category == "queue"
        assert s.self_seconds == 0.0  # observed, not charged


def test_metrics_events_published_on_bus(tmp_path):
    src = seed_memory({f"t{t}/f.bin": b"x" * KB for t in range(4)})
    dst = MemoryConnector()
    mgr = make_manager(tmp_path, metrics_every=2)
    sub = mgr.bus.subscribe(types=("metrics",))
    opts = TransferOptions(startup_cost=0.0)
    for t in range(4):
        mgr.submit(Endpoint(src, f"t{t}", f"s{t}"),
                   Endpoint(dst, f"o/t{t}", f"d{t}"),
                   opts, task_id=f"m-{t}")
    assert mgr.wait_all(timeout=120)
    events = sub.poll()
    assert len(events) == 2  # every 2 completions
    snap = events[-1].data
    counted = sum(v for labels, v in snap["repro_tasks_total"].items()
                  if 'status="SUCCEEDED"' in labels)
    assert counted == 4
    assert 'repro_tasks_total' in mgr.scrape()


def test_manager_shares_service_tracer_and_health(tmp_path):
    from repro.core.health import EndpointHealth
    clock = Clock(scale=0.0)
    health = EndpointHealth(clock=clock)
    tracer = Tracer(clock=clock)
    mgr = make_manager(tmp_path, clock=clock, health=health, tracer=tracer)
    assert mgr.tracer is tracer
    assert mgr.service.tracer is tracer
    assert health.tracer is tracer


# --------------------------------------------------------------------------
# federation: trace ids travel (satellite c)
# --------------------------------------------------------------------------
def test_transfer_spec_round_trips_trace_id():
    spec = TransferSpec(task_id="t1", src_endpoint="a", src_path="p",
                        dst_endpoint="b", dst_path="q",
                        trace_id="trace-t1")
    payload = json.loads(json.dumps(spec.to_payload()))
    assert TransferSpec.from_payload(payload).trace_id == "trace-t1"
    # absent on the wire (older peer) -> empty, never a crash
    payload.pop("trace_id")
    assert TransferSpec.from_payload(payload).trace_id == ""


def test_trace_id_survives_export_import(tmp_path):
    src = seed_memory({"t0/f.bin": b"x" * KB})
    dst = MemoryConnector()
    a = make_manager(tmp_path / "a", max_workers=1)
    b = make_manager(tmp_path / "b", max_workers=1)
    # keep it queued on a busy site so export_state can take it: the
    # blocker holds the site's one worker until the handoff is done
    gate = threading.Event()
    blocker_src = _GatedMemory(gate)
    blocker_src.store.put("t/f.bin", b"y" * KB)
    blocker = a.submit(Endpoint(blocker_src, "t", "bsrc"),
                       Endpoint(MemoryConnector(), "o", "bdst"),
                       TransferOptions(startup_cost=0.0), task_id="blk")
    task = a.submit(Endpoint(src, "t0", "s0"), Endpoint(dst, "o/t0", "d0"),
                    TransferOptions(startup_cost=0.0), task_id="mv")
    trace_id = task.trace_id
    assert trace_id == "trace-mv"
    payload = a.export_state("mv")
    assert payload is not None and payload["trace_id"] == trace_id
    adopted = b.import_state(payload, Endpoint(src, "t0", "s0"),
                             Endpoint(dst, "o/t0", "d0"))
    assert adopted.trace_id == trace_id
    assert task.status == task.HANDED_OFF
    gate.set()
    assert a.wait_all(timeout=60) and b.wait_all(timeout=60)
    assert adopted.status == adopted.SUCCEEDED
    budget = adopted.stats.time_budget()
    assert abs(sum(budget.values())
               - adopted.stats.actual_model_seconds) < 1e-6
    assert blocker.status == blocker.SUCCEEDED


# --------------------------------------------------------------------------
# chaos fleets: the capstone acceptance invariant
# --------------------------------------------------------------------------
def test_run_multi_chaos_budgets_sum_exactly(tmp_root):
    runner = ScenarioRunner(tmp_root)
    fleet = runner.run_multi(
        n_tasks=4, tenants=("alice", "bob"),
        schedule=FaultSchedule(seed=11).transient(op="recv", at=1, times=1),
        max_workers=3, pause_resume=(1,), strict=True)
    tracer = fleet.manager.tracer
    assert tracer.enabled and tracer.spans_recorded > 0
    for task in fleet.tasks:
        budget = task.stats.time_budget()
        assert abs(sum(budget.values())
                   - task.stats.actual_model_seconds) < 1e-6
        assert task.trace_id


def test_run_federated_budgets_and_trace_ids(tmp_root):
    runner = ScenarioRunner(tmp_root)
    fed = runner.run_federated(n_sites=2, n_tasks=4, strict=True)
    moved = dict(fed.moved)
    for task in fed.tasks:
        budget = task.stats.time_budget()
        assert abs(sum(budget.values())
                   - task.stats.actual_model_seconds) < 1e-6
    # every handed-off task kept its trace id through the spec
    for task_id in moved:
        spec = fed.coordinator.last_spec(task_id)
        assert spec is not None and spec.trace_id == f"trace-{task_id}"


def test_same_seed_runs_export_identical_traces(tmp_path):
    digests = []
    for i in range(2):
        mgr, _tasks = run_fleet(tmp_path / f"run{i}", n_tasks=3, n_files=3)
        p = str(tmp_path / f"trace{i}.jsonl")
        mgr.tracer.export_jsonl(p)
        with open(p, "rb") as fh:
            digests.append(fh.read())
    assert digests[0] == digests[1]


# --------------------------------------------------------------------------
# lint rule R006 (satellite b)
# --------------------------------------------------------------------------
def lint_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    return run_lint(tmp_path)


def r006_hits(report):
    return [(f.file, f.line) for f in report.findings if f.rule == "R006"]


def test_r006_flags_bare_span_call(tmp_path):
    report = lint_tree(tmp_path, {"src/repro/core/thing.py": """\
        def work(tracer):
            cm = tracer.span("send", "wire")
            cm.__enter__()
        """})
    assert r006_hits(report) == [("src/repro/core/thing.py", 2)]


def test_r006_accepts_with_managed_span(tmp_path):
    report = lint_tree(tmp_path, {"src/repro/core/thing.py": """\
        def work(tracer, clock):
            with tracer.span("send", "wire", path="p"):
                clock.sleep(1.0)
            with tracer.span("a"), tracer.span("b"):
                pass
        """})
    assert r006_hits(report) == []


def test_r006_suppressible_with_reason(tmp_path):
    report = lint_tree(tmp_path, {"src/repro/core/thing.py": """\
        def work(tracer):
            cm = tracer.span("send")  # lint: disable=R006(test fixture)
            return cm
        """})
    assert r006_hits(report) == []
    assert any(s.rule == "R006" for s in report.suppressed)
