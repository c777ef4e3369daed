"""Transfer lab: reproduce the paper's §5-§8 experiment suite in one
script (emulated providers, virtual clock — instant).

Prints the per-(provider x placement x direction) fitted models, the
Pearson table (paper Table 1), the startup cost (Fig 12), integrity
overhead (Figs 19-21), and the §8 best-practice recommendations derived
from the fitted models.

Run:  PYTHONPATH=src:. python examples/transfer_lab.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("REPRO_BENCH_QUICK", "1")


def main():
    from benchmarks import bench_perfile, bench_startup, bench_integrity
    from repro.core import Advisor, Route

    print("== per-file overhead regression (paper §5, Figs 6-11) ==")
    models = bench_perfile.run(full=False)

    print("\n== startup cost (paper §5.4, Fig 12) ==")
    s0 = bench_startup.run()

    print("\n== integrity checking (paper §7, Figs 19-21) ==")
    bench_integrity.run()

    print("\n== §8 best practices, derived from the fitted models ==")
    adv = Advisor([Route(name, m) for name, m in models.items()
                   if "+batch" not in name])
    for n_files, gb in ((1000, 1), (10, 50)):
        route, cc, eta = adv.best(n_files, int(gb * 1e9))
        print(f"  {n_files} files / {gb} GB -> {route.name} cc={cc} "
              f"(predicted {eta:.0f}s)")

    print("\n== chaos lab: managed transfer under injected faults "
          "(§2.2/§4/§7) ==")
    # The Connector pitch is *managed* transfer — retries, restart
    # markers, end-to-end integrity.  The chaos harness replays a
    # seed-deterministic FaultSchedule through a FaultProxyConnector
    # wrapped around any route end and asserts the end-state
    # invariants: byte-exact trees, cleared markers, consistent
    # TaskStats.  Same seed -> same fault sequence -> same stats.
    import tempfile
    from repro.core import FaultSchedule, TransferOptions
    from repro.sim import ScenarioRunner

    KB = 1024
    demos = [
        ("rate-limit storm (Drive/Box quotas)", "many-small", "posix->cloud",
         lambda: FaultSchedule(seed=1).rate_limit(op="recv_batch", at=1,
                                                  times=1, retry_after=0.25),
         None),
        ("bit flip -> integrity repair", "few-large", "posix->memory",
         lambda: FaultSchedule(seed=2).bit_flip(at=1, times=1),
         TransferOptions(startup_cost=0.0, integrity=True,
                         retry_backoff=0.01)),
        ("session drop mid-batch", "many-small", "posix->memory",
         lambda: FaultSchedule(seed=3).session_drop(op="recv_batch", at=1,
                                                    times=1), None),
        ("truncated stream -> hole re-sent", "few-large", "posix->posix",
         lambda: FaultSchedule(seed=4).truncate(after_bytes=100 * KB, at=1,
                                                times=1), None),
        ("latency spikes (model clock)", "many-small", "posix->cloud",
         lambda: FaultSchedule(seed=5).latency(op="read", delay=0.5,
                                               times=None), None),
    ]
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        for name, tree, route, build, opts in demos:
            sched = build()
            res = runner.run(tree=tree, route=route, schedule=sched,
                             options=opts, strict=True)
            st = res.task.stats
            print(f"  {name}: {res.task.status.lower()} on {route}  "
                  f"files={st.files_done}/{st.files_total} "
                  f"injected={len(sched.events)} retried={st.faults_retried} "
                  f"integrity={st.integrity_failures} "
                  f"fallbacks={st.batch_fallbacks}")
    print("  invariants held: byte-exact trees, markers cleared, "
          "accounting consistent")

    print("\n== control plane: a multi-tenant fleet under chaos "
          "(§2.1-§2.2) ==")
    # The managed service's real product is *many* tasks at once: a
    # TransferManager runs a fleet with per-endpoint caps, tenant-fair
    # round-robin, shared sessions, and pause/resume checkpointed
    # through the restart markers.  Here: 4 tasks, 2 tenants, injected
    # transients, one task paused and resumed mid-run — everything must
    # land byte-exact with caps honored.
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        fleet = runner.run_multi(
            n_tasks=4, tenants=("alice", "bob"),
            trees=("many-small", "mixed"), route="posix->memory",
            schedule=FaultSchedule(seed=9).transient(op="recv", at=1,
                                                     times=1),
            max_workers=3, per_endpoint_cap=2, pause_resume=(2,),
            strict=True)
        m = fleet.manager.metrics
        print(f"  fleet: {len(fleet.tasks)} tasks, "
              f"{len(m.dispatches_by_tenant)} tenants -> all "
              f"{sum(1 for t in fleet.tasks if t.status == 'SUCCEEDED')} "
              f"succeeded; peak_active={m.peak_active} (budget 3), "
              f"endpoint peaks={dict(m.peak_by_endpoint)} (cap 2), "
              f"pauses={m.pauses} resumes={m.resumes}")
        print(f"  dispatch fairness: {m.dispatches_by_tenant}")

    print("\n== service plane: streaming status instead of polling "
          "(repro.svc) ==")
    # Every queue mutation publishes a typed lifecycle event
    # (queued/dispatched/progress/done/...) on the manager's StatusBus.
    # Subscribers ride bounded ring buffers — a slow consumer drops
    # oldest-first with an exact counter instead of stalling the
    # publisher — and digest() answers from an etag cache while the
    # queue generation is unchanged, so observing an idle fleet is
    # a dict lookup, not a recompute.
    from repro.connectors import MemoryConnector as _Mem
    from repro.connectors import PosixConnector as _Posix
    from repro.core import (CredentialStore, Endpoint as _Ep,
                            TransferManager, TransferOptions as _Opts)
    from repro.core.clock import Clock
    with tempfile.TemporaryDirectory() as tmp:
        src_root = os.path.join(tmp, "src")
        os.makedirs(src_root)
        for i in range(6):
            with open(os.path.join(src_root, f"f{i}.bin"), "wb") as f:
                f.write(os.urandom(64 * KB))
        mgr = TransferManager(credential_store=CredentialStore(),
                              marker_root=os.path.join(tmp, "markers"),
                              clock=Clock(scale=0.0), max_workers=2)
        firehose = mgr.bus.subscribe()            # every event
        tiny = mgr.bus.subscribe(capacity=4)      # deliberately slow
        done_only = mgr.bus.subscribe(types=("done",))
        src_c, dst_c = _Posix(src_root), _Mem()
        for i in range(6):
            mgr.submit(_Ep(src_c, f"f{i}.bin"), _Ep(dst_c, f"f{i}.bin"),
                       _Opts(startup_cost=0.0), task_id=f"svc-{i}")
        mgr.wait_all(30)
        events = firehose.poll()
        by_type: dict = {}
        for ev in events:
            by_type[ev.type] = by_type.get(ev.type, 0) + 1
        print(f"  firehose subscriber: {len(events)} events {by_type}")
        print(f"  slow subscriber (ring of 4): kept {len(tiny)}, "
              f"dropped {tiny.dropped} oldest-first")
        print(f"  filtered subscriber: {len(done_only.poll())} 'done' "
              f"events for 6 tasks")
        d = mgr.digest()
        mgr.digest()
        print(f"  digest etag {d['etag']}: idle fleet -> "
              f"{mgr.metrics.digest_hits} cache hits, "
              f"{mgr.metrics.digest_misses} recomputes")
        mgr.shutdown()

    print("\n== closed-loop online refit (§5: characterize without "
          "exhaustive benchmarking) ==")
    # Model time is charge-accounted per task (every clock charge names
    # its owning task), so a concurrent fleet's observations are exact —
    # and the manager refits each route automatically every
    # ``refit_every`` completions, re-predicting the still-queued tail.
    # Start from a model that is ~1000x wrong and watch it converge.
    from repro.core import Advisor, PerfModel, Route
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        bad_seed = PerfModel(route="fleet", t0=3.0, alpha=1e9 / 40e6,
                             bytes_total=int(1e9))
        fleet = runner.run_multi(
            n_tasks=10, tenants=("alice", "bob"),
            trees=("many-small", "mixed"), route="posix->memory",
            schedule=FaultSchedule(seed=5).transient(op="read", at=4,
                                                     times=2),
            max_workers=3, per_endpoint_cap=None,
            advisor=Advisor([Route("fleet", bad_seed,
                                   max_concurrency=1)]),
            refit_every=3, strict=True)
        mgr = fleet.manager
        pre = mgr.prediction_error(generation=0)
        post = mgr.prediction_error(min_generation=1)
        print(f"  refits={mgr.metrics.refits.get('fleet', 0)} "
              f"median |pred err|: seed model {pre:.2f} -> "
              f"refit model {post:.2f}")
        print(f"  fitted t0 {bad_seed.t0:.2f}s/file -> "
              f"{mgr.advisor.routes[0].model.t0 * 1e3:.1f}ms/file "
              f"from live traffic")

    print("\n== federation: two sites, one third-party coordinator "
          "(§2.1 scaled out) ==")
    # The paper's orchestrator never sits in the data path; the
    # federation plane repeats that one level up.  Submissions travel
    # as JSON TransferSpecs, the coordinator places each at the site
    # owning its source endpoint, and killing a site mid-flight hands
    # its paused tasks (hole maps + checksum folds riding the spec) to
    # a peer that re-sends only the missing bytes.  The charge clock
    # proves third-party semantics: the coordinator's model-time tally
    # stays exactly zero.
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        fed = runner.run_federated(n_sites=2, n_tasks=4, strict=True)
        coord = fed.coordinator
        m = coord.metrics
        print(f"  sites: {len(coord.sites())}  tasks: {len(fed.tasks)}  "
              f"placements: {m.placements}")
        moved = {tid: site for tid, site in fed.moved}
        for r in fed.results:
            t = r.task
            hop = f" (failed over -> {moved[t.task_id]})" \
                if t.task_id in moved else ""
            print(f"    {t.task_id}: {t.status.lower()} "
                  f"site={t.stats.site} tenant={t.stats.tenant} "
                  f"model={t.stats.actual_model_seconds:.3f}s{hop}")
        spec = next((coord.last_spec(tid) for tid, _ in fed.moved
                     if coord.last_spec(tid).done_bytes() > 0), None)
        if spec is not None:
            print(f"  handoff spec traveled {spec.done_bytes()} done "
                  f"bytes of {spec.nbytes}: the peer re-sent only the "
                  f"holes (write meter agrees, byte-exact)")
        print(f"  third-party invariant: coordinator charged "
              f"{coord.model_seconds():.1f} model seconds")

    print("\n== replica catalog: fan-out dedupe (content-addressed "
          "§7 folds) ==")
    # Every durably-committed file is indexed by its §7 content
    # checksum + the source's (size, mtime) signature.  Submitting the
    # SAME tree N times collapses to 1 real transfer + N-1 verified
    # replica reads at the destination: a send-side byte meter proves
    # the source streamed the tree once, and a corrupted replica fails
    # its checksum fold and falls back to a real transfer — the catalog
    # is a hint cache, never an authority.
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        fan = runner.run_fanout(n_fanout=4, tree="many-small",
                                chaos="none", strict=True)
        st = fan.catalog.stats()
        print(f"  fan-out of {len(fan.tasks)}: source streamed "
              f"{fan.source_bytes // KB}KB for a "
              f"{fan.tree_bytes // KB}KB tree "
              f"(moved_ratio={fan.moved_ratio:.2f}) — "
              f"{fan.replica_hits} replica hits, "
              f"hit_rate={fan.catalog.hit_rate():.2f}")
        print(f"  catalog: {st['entries']} entries / "
              f"{st['bytes'] // KB}KB indexed, write-once destination "
              f"accounting held")
        chaos = runner.run_fanout(n_fanout=2, tree="many-small",
                                  chaos="corrupt", strict=True)
        cs = chaos.catalog.stats()
        print(f"  corrupted replicas: {cs['corrupt_invalidations']} "
              f"invalidated by the fold, "
              f"{chaos.replica_fallbacks} fallbacks to real transfers, "
              f"byte-exact trees landed anyway")

    print("\n== observability: where did every model-second go? "
          "(repro.obs) ==")
    # Spans ride the charge-attribution clock — the same Clock.sleep
    # calls that feed Clock.charged also land on the innermost open
    # span — so TaskStats.time_budget() decomposes a task's
    # actual_model_seconds into categories that sum EXACTLY (within
    # float tolerance), even under chaos.  Each span is also stamped in
    # host time, and the tracer exports that Perfetto-loadable
    # timeline; the manager streams registry
    # snapshots on the StatusBus it already owns.
    with tempfile.TemporaryDirectory() as tmp:
        runner = ScenarioRunner(tmp)
        fleet = runner.run_multi(
            n_tasks=4, tenants=("alice", "bob"),
            trees=("many-small", "mixed"), route="posix->memory",
            schedule=FaultSchedule(seed=7).transient(op="recv", at=1,
                                                     times=1),
            max_workers=3, pause_resume=(1,), strict=True)
        cats = ("wire", "integrity", "backoff", "overhead", "queue")
        print(f"  {'task':12s} {'total':>8s} "
              + " ".join(f"{c:>9s}" for c in cats) + f" {'other':>8s}")
        for t in fleet.tasks:
            budget = t.stats.time_budget()
            total = t.stats.actual_model_seconds
            assert abs(sum(budget.values()) - total) < 1e-6
            row = " ".join(f"{budget.get(c, 0.0):9.3f}" for c in cats)
            print(f"  {t.task_id:12s} {total:8.3f} {row} "
                  f"{budget.get('other', 0.0):8.3f}")
        print("  (columns sum to total within 1e-6 — charged by the "
              "clock itself, not sampled)")
        tracer = fleet.manager.tracer
        trace_path = os.path.join(tmp, "fleet_trace.json")
        n = tracer.export_chrome(trace_path)
        print(f"  exported {n} spans as Chrome trace-event JSON on the "
              f"host clock (model seconds in each span's args) -> "
              f"load in ui.perfetto.dev (export_jsonl gives the "
              f"canonical byte-stable form)")
        scrape = fleet.manager.scrape()
        line = next(ln for ln in scrape.splitlines()
                    if ln.startswith("repro_tasks_total"))
        print(f"  metrics scrape ({len(scrape.splitlines())} lines), "
              f"e.g.: {line}")

    print("\n== small-file regime: coalesced batches (paper §5.3.2/§8) ==")
    # Eq. 4 says per-file overhead t0 dominates many-small-file
    # transfers.  The service coalesces files below
    # TransferOptions.coalesce_threshold into pipelined batches that
    # share one control exchange and ride the Connector bulk data plane
    # (send_batch/recv_batch); the Advisor sizes the threshold at the
    # break-even point size == t0 * R from a fitted model.
    from benchmarks.common import batched_route
    for route in adv.routes:
        batched = models.get(batched_route(route.name))
        if batched is None or "native" in route.name:
            continue
        th = adv.coalesce_threshold(route)
        speedup = (route.model.t0 / batched.t0
                   if batched.t0 > 0 else float("inf"))
        print(f"  {route.name}: t0 {route.model.t0*1e3:.0f}ms -> "
              f"{batched.t0*1e3:.0f}ms batched ({speedup:.1f}x); "
              f"coalesce files < {th / 1e6:.1f} MB")


if __name__ == "__main__":
    main()
