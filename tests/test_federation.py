"""Federation plane: TransferSpec serialization, cross-site placement,
third-party handoff, and the streaming checksum fold that lets a
resumed/handed-off task skip the §7 source re-read.

The suite is marked ``fed`` (tier-1 CI lane); the chaos-grade federated
scenario additionally carries ``chaos`` so the chaos lane picks it up.
"""

import json
import os
import random
import tempfile
import threading
import time

import pytest

from repro.connectors import MemoryConnector, PosixConnector
from repro.core import (Advisor, Credential, CredentialStore, Endpoint,
                        FaultSchedule, PerfModel, Route, TransferManager,
                        TransferOptions)
from repro.core.clock import Clock
from repro.core.transfer import COMPOSITE_PREFIX, TransferTask
from repro.fed import (FederatedCoordinator, QueueDigest,
                       StrandedTasksError, TransferSpec, SPEC_STATES)
from repro.sim import ScenarioRunner
from repro.sim.scenarios import _HoldSrc, _InstrumentedDst

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KB = 1024
MB = 1024 * 1024

pytestmark = pytest.mark.fed


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def make_site(tmp_path, name, clock, tenants=("alice",), advisor=None,
              max_workers=2):
    creds = CredentialStore()
    for tenant in tenants:
        creds.register("src-ep", Credential("local-user",
                                            {"identity": tenant}))
    return TransferManager(credential_store=creds, max_workers=max_workers,
                           per_endpoint_cap=None, advisor=advisor,
                           marker_root=os.path.join(str(tmp_path),
                                                    f"markers-{name}"),
                           clock=clock, site_id=name)


def wait_landed(task, nbytes, timeout=30):
    """The blocks _HoldSrc let through before it engaged are still in
    flight on the receive side, and a pause stops the receiver at block
    granularity: wait until ``nbytes`` landed durable before pausing
    (same sequencing as ScenarioRunner.run_federated)."""
    deadline = time.monotonic() + timeout
    while task.stats.bytes_done < nbytes and time.monotonic() < deadline:
        time.sleep(0.002)
    assert task.stats.bytes_done >= nbytes


def seed_memory(files):
    conn = MemoryConnector()
    for name, payload in files.items():
        conn.store.put(name, payload)
    return conn


def small_tree(n=12, size=3 * KB, seed=0):
    rng = random.Random(seed)
    return {f"data/f{i:02d}.bin": rng.randbytes(size) for i in range(n)}


def read_out(store, prefix="out/"):
    return {k[len(prefix):]: store.get(k)
            for k in store.keys() if k.startswith(prefix)}


# --------------------------------------------------------------------------
# TransferSpec serialization
# --------------------------------------------------------------------------
def _random_spec(seed: int) -> TransferSpec:
    rng = random.Random(f"spec|{seed}")
    state = rng.choice(SPEC_STATES)
    files = {}
    if state == "paused":
        for i in range(rng.randint(1, 4)):
            size = rng.randint(1, 4 * MB)
            done, digests, at = [], {}, 0
            for _ in range(rng.randint(0, 3)):
                if at >= size:
                    break
                ln = rng.randint(1, max(1, (size - at) // 2))
                done.append([at, ln])
                digests[f"{at}:{ln}"] = f"{rng.getrandbits(128):032x}"
                at += ln + rng.randint(0, 1024)
            files[f"data/ü{i}.bin"] = {
                "done": done, "complete": False, "digests": digests}
    return TransferSpec(
        task_id=f"t-{seed}", src_endpoint="ep-a", src_path="data",
        dst_endpoint="ep-b", dst_path="out",
        tenant=rng.choice(["alice", "bob", ""]),
        priority=rng.randint(-2, 5), state=state,
        options={"concurrency": rng.choice([1, 4]),
                 "integrity": rng.random() < 0.5,
                 "coalesce_threshold": rng.choice([0, 64 * KB])},
        route=rng.choice(["", "s3/up"]),
        n_files=rng.randint(0, 40), nbytes=rng.randint(0, 10 * MB),
        origin_site=rng.choice(["", "s0", "s1"]),
        stats={"actual_model_seconds": rng.random() * 10,
               "resumes": rng.randint(0, 3)},
        markers={"files": files})


def _roundtrip_property(seed: int) -> None:
    spec = _random_spec(seed)
    wire = spec.to_json()
    back = TransferSpec.from_json(wire)
    assert back == spec
    # canonical wire form is stable (sorted keys, value-identical)
    assert back.to_json() == wire
    # the manager payload shape round-trips too (handoff path)
    assert TransferSpec.from_payload(spec.to_payload()) == spec
    # the wire form is plain JSON a foreign control plane could parse
    raw = json.loads(wire)
    assert raw["task_id"] == spec.task_id
    assert raw["markers"] == spec.markers


if HAVE_HYPOTHESIS:
    @given(st.integers(min_value=0, max_value=2 ** 32 - 1))
    def test_spec_json_roundtrip(seed):
        _roundtrip_property(seed)
else:
    @pytest.mark.parametrize("seed", list(range(16)))
    def test_spec_json_roundtrip(seed):
        _roundtrip_property(seed)


def test_spec_roundtrip_covers_every_state():
    seen = set()
    for seed in range(64):
        spec = _random_spec(seed)
        _roundtrip_property(seed)
        seen.add(spec.state)
    assert seen == set(SPEC_STATES)


def test_spec_validation_rejects_garbage():
    with pytest.raises(ValueError):
        TransferSpec.new("", "a", "p", "b", "q").validate()
    with pytest.raises(ValueError):
        TransferSpec.new("t", "", "p", "b", "q").validate()
    spec = TransferSpec.new("t", "a", "p", "b", "q")
    spec.state = "running"  # live states never travel
    with pytest.raises(ValueError):
        spec.validate()
    spec = TransferSpec.new("t", "a", "p", "b", "q")
    spec.markers = {"oops": 1}
    with pytest.raises(ValueError):
        spec.to_json()


def test_spec_pending_bytes_from_hole_map():
    spec = TransferSpec.new("t", "a", "data", "b", "out", nbytes=100)
    assert spec.pending_bytes() == 100
    spec.markers = {"files": {"data/x": {"done": [[0, 30], [50, 10]],
                                         "complete": False}}}
    assert spec.done_bytes() == 40
    assert spec.pending_bytes() == 60


# --------------------------------------------------------------------------
# cross-site placement + attribution
# --------------------------------------------------------------------------
def test_cross_site_placement_attribution(tmp_path):
    """A spec whose source endpoint is owned by a different site is
    placed there, completes byte-exact, and both tenant and model-time
    attribution stick — while the coordinator charges nothing."""
    clock = Clock(scale=0.0)
    files = small_tree()
    src_conn = seed_memory(files)
    dst_conn = MemoryConnector()
    eps = {"src-ep": src_conn, "dst-ep": dst_conn}

    coord = FederatedCoordinator(placement="owner")
    coord.register_site("near-dst", make_site(tmp_path, "near-dst", clock),
                        eps, owns={"dst-ep"})
    coord.register_site("near-src", make_site(tmp_path, "near-src", clock),
                        eps, owns={"src-ep"})

    spec = TransferSpec.new(
        "xsite-1", "src-ep", "data", "dst-ep", "out", tenant="alice",
        options=TransferOptions(startup_cost=0.0),
        n_files=len(files), nbytes=sum(map(len, files.values())))
    task = coord.submit(spec.to_json(), sync=True)

    assert coord.site_of("xsite-1") == "near-src"
    assert task.status == task.SUCCEEDED
    assert task.stats.tenant == "alice"
    assert task.stats.site == "near-src"
    assert task.stats.origin_site == "near-src"
    assert task.stats.actual_model_seconds > 0
    got = read_out(dst_conn.store)
    assert got == {k[len("data/"):]: v for k, v in files.items()}
    coord.assert_third_party()
    assert coord.model_seconds() == 0.0
    digests = coord.exchange_digests()
    assert set(digests) == {"near-dst", "near-src"}
    assert all(isinstance(d, QueueDigest) and d.depth == 0
               for d in digests.values())
    coord.shutdown()


def test_manager_export_import_paused_task(tmp_path):
    """Manager-level travel: a paused task exports with its hole map,
    the origin handle finishes HANDED_OFF, and a peer manager resumes
    it re-sending only the holes (carried stats intact)."""
    clock = Clock(scale=0.0)
    payload = os.urandom(2 * MB)
    src_conn = _HoldSrc(seed_memory({"data/big.bin": payload}))
    src_conn.arm_hold(["data/"], 256 * KB)
    dst_inner = MemoryConnector()
    dst_conn = _InstrumentedDst(dst_inner)

    mgr_a = make_site(tmp_path, "a", clock)
    opts = TransferOptions(startup_cost=0.0, concurrency=1, parallelism=1,
                           blocksize=64 * KB, coalesce_threshold=0)
    task_a = mgr_a.submit(Endpoint(src_conn, "data", "src-ep"),
                          Endpoint(dst_conn, "out", "dst-ep"), opts,
                          task_id="trav-1", tenant="alice")
    assert src_conn.engaged.wait(30)
    wait_landed(task_a, 256 * KB)
    mgr_a.pause("trav-1")
    src_conn.release()
    deadline = time.monotonic() + 30
    payload_out = None
    while time.monotonic() < deadline:
        payload_out = mgr_a.export_state("trav-1")
        if payload_out is not None or task_a._done.is_set():
            break
        task_a.wait_idle(0.05)
    assert payload_out is not None, task_a.status
    assert task_a.status == TransferTask.HANDED_OFF
    assert task_a.wait(1)  # origin waiters unblock

    # the payload is JSON-clean and carries real partial progress
    spec = TransferSpec.from_payload(json.loads(json.dumps(payload_out)))
    assert spec.state == "paused"
    assert spec.done_bytes() > 0
    carried = spec.stats["actual_model_seconds"]

    before_import = dst_conn.written("out/")
    mgr_b = make_site(tmp_path, "b", clock)
    task_b = mgr_b.import_state(spec.to_payload(),
                                Endpoint(src_conn, "data", "src-ep"),
                                Endpoint(dst_conn, "out", "dst-ep"))
    assert task_b.wait(30)
    assert task_b.status == task_b.SUCCEEDED
    assert dst_inner.store.get("out/big.bin") == payload
    # only the holes were re-sent
    assert dst_conn.written("out/") == len(payload)
    assert dst_conn.written("out/") - before_import \
        == len(payload) - spec.done_bytes()
    assert task_b.stats.resumes == 1
    assert task_b.stats.tenant == "alice"
    assert task_b.stats.origin_site == "a"
    assert task_b.stats.site == "b"
    assert task_b.stats.actual_model_seconds >= carried
    assert mgr_a.metrics.exports == 1 and mgr_b.metrics.imports == 1
    mgr_a.shutdown(wait=False)
    mgr_b.shutdown(wait=False)


# --------------------------------------------------------------------------
# handoff race: site dies mid-batch
# --------------------------------------------------------------------------
def test_handoff_race_site_dies_mid_batch(tmp_path):
    """The victim site is killed while its task is inside a coalesced
    batch; the peer resumes byte-exact, and the destination write meter
    proves every byte landed exactly once (holes only)."""
    clock = Clock(scale=0.0)
    files = small_tree(n=16, size=4 * KB, seed=3)
    total = sum(map(len, files.values()))
    src_conn = _HoldSrc(seed_memory(files))
    src_conn.arm_hold(["data/"], 6 * KB)  # mid-batch: a few files landed
    dst_inner = MemoryConnector()
    dst_conn = _InstrumentedDst(dst_inner)
    eps = {"src-ep": src_conn, "dst-ep": dst_conn}

    coord = FederatedCoordinator(placement="owner")
    coord.register_site("a", make_site(tmp_path, "a", clock), eps,
                        owns={"src-ep", "dst-ep"})
    coord.register_site("b", make_site(tmp_path, "b", clock), eps,
                        owns=set())

    spec = TransferSpec.new(
        "race-1", "src-ep", "data", "dst-ep", "out", tenant="bob",
        options=TransferOptions(startup_cost=0.0,
                                coalesce_threshold=64 * KB,
                                max_batch_files=32),
        n_files=len(files), nbytes=total)
    task_a = coord.submit(spec.to_json())
    assert coord.site_of("race-1") == "a"
    assert src_conn.engaged.wait(30)
    # the crossing block is still in flight on the receive side; killing
    # the site before it lands durable would checkpoint zero progress
    # (same sequencing as ScenarioRunner.run_federated)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and task_a.stats.bytes_done == 0:
        time.sleep(0.002)

    moved: list = []
    failer = threading.Thread(
        target=lambda: moved.extend(coord.fail_site("a", timeout=60)),
        daemon=True)
    failer.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if task_a._pause_req.is_set() or task_a._done.is_set() \
                or task_a.status == task_a.PAUSED:
            break
        time.sleep(0.005)
    src_conn.release()
    failer.join(60)
    assert not failer.is_alive()

    assert moved == [("race-1", "b")]
    traveled = coord.last_spec("race-1")
    assert traveled.state == "paused"
    assert traveled.done_bytes() > 0
    task_b = coord.task("race-1")
    assert task_b is not task_a
    assert task_b.wait(30)
    assert task_b.status == task_b.SUCCEEDED
    assert read_out(dst_inner.store) \
        == {k[len("data/"):]: v for k, v in files.items()}
    # byte-exact accounting: nothing the first run landed was re-sent
    assert dst_conn.written("out/") == total
    assert task_b.stats.tenant == "bob"
    assert task_b.stats.origin_site == "a"
    coord.assert_third_party()
    coord.shutdown(wait=False)


# --------------------------------------------------------------------------
# streaming checksum fold (§7 without source re-reads)
# --------------------------------------------------------------------------
class ChecksumCountingPosix(PosixConnector):
    """Counts whole-file source checksum re-reads — the §7 cost the
    per-range digest journal exists to eliminate."""

    def __init__(self, root):
        super().__init__(root)
        self.checksum_calls = 0

    def checksum(self, session, path, algorithm):
        self.checksum_calls += 1
        return super().checksum(session, path, algorithm)


def seeded_posix(tmp_path, files):
    root = os.path.join(str(tmp_path), "srcroot")
    conn = ChecksumCountingPosix(root)
    for name, payload in files.items():
        p = os.path.join(root, name)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "wb") as f:
            f.write(payload)
    return conn


def test_checksum_fold_on_pause_resume_no_source_reread(tmp_path):
    clock = Clock(scale=0.0)
    payload = os.urandom(3 * MB)
    counting = seeded_posix(tmp_path, {"data/big.bin": payload})
    src_conn = _HoldSrc(counting)
    src_conn.arm_hold(["data/"], 512 * KB)
    dst_inner = MemoryConnector()
    dst_conn = _InstrumentedDst(dst_inner)

    mgr = make_site(tmp_path, "solo", clock)
    opts = TransferOptions(startup_cost=0.0, integrity=True, concurrency=1,
                           parallelism=1, blocksize=128 * KB,
                           digest_segment=256 * KB, coalesce_threshold=0)
    task = mgr.submit(Endpoint(src_conn, "data", "src-ep"),
                      Endpoint(dst_conn, "out", "dst-ep"), opts,
                      task_id="fold-1")
    assert src_conn.engaged.wait(30)
    wait_landed(task, 512 * KB)
    mgr.pause("fold-1")
    src_conn.release()
    assert task.wait_idle(30)
    deadline = time.monotonic() + 30
    while task.status != task.PAUSED and time.monotonic() < deadline:
        if task._done.is_set():
            break
        time.sleep(0.005)
    assert task.status == task.PAUSED

    # the journal now holds digest-backed resumable ranges
    state = mgr.service.markers.load("fold-1")["files"]["data/big.bin"]
    assert state["digests"]
    digested = sum(ln for _, ln in
                   (map(int, k.split(":")) for k in state["digests"]))
    assert digested == sum(ln for _, ln in state["done"])

    mgr.resume("fold-1")
    assert task.wait(30)
    assert task.status == task.SUCCEEDED
    assert dst_inner.store.get("out/big.bin") == payload
    fr = task.files[-1]
    assert fr.ok and fr.checksum.startswith(COMPOSITE_PREFIX)
    # §7 held (verify passed) with ZERO source re-reads
    assert counting.checksum_calls == 0
    assert task.stats.integrity_failures == 0
    mgr.shutdown(wait=False)


def test_checksum_fold_travels_across_handoff(tmp_path):
    """A handed-off integrity task must not re-read the source on the
    new site: the per-range digests ride the spec's marker state."""
    clock = Clock(scale=0.0)
    payload = os.urandom(2 * MB)
    counting = seeded_posix(tmp_path, {"data/big.bin": payload})
    src_conn = _HoldSrc(counting)
    src_conn.arm_hold(["data/"], 256 * KB)
    dst_inner = MemoryConnector()
    dst_conn = _InstrumentedDst(dst_inner)
    eps = {"src-ep": src_conn, "dst-ep": dst_conn}

    coord = FederatedCoordinator(placement="owner")
    coord.register_site("a", make_site(tmp_path, "a", clock), eps,
                        owns={"src-ep", "dst-ep"})
    coord.register_site("b", make_site(tmp_path, "b", clock), eps,
                        owns=set())
    spec = TransferSpec.new(
        "foldoff-1", "src-ep", "data", "dst-ep", "out", tenant="alice",
        options=TransferOptions(startup_cost=0.0, integrity=True,
                                concurrency=1, parallelism=1,
                                blocksize=64 * KB,
                                digest_segment=128 * KB,
                                coalesce_threshold=0),
        n_files=1, nbytes=len(payload))
    task_a = coord.submit(spec.to_json())
    assert src_conn.engaged.wait(30)
    wait_landed(task_a, 256 * KB)

    out: list = []
    mover = threading.Thread(
        target=lambda: out.append(coord.handoff("foldoff-1", timeout=60)),
        daemon=True)
    mover.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if task_a._pause_req.is_set() or task_a._done.is_set():
            break
        time.sleep(0.005)
    src_conn.release()
    mover.join(60)
    assert not mover.is_alive()
    task_b = out[0]
    assert task_b is not None
    assert coord.site_of("foldoff-1") == "b"

    traveled = coord.last_spec("foldoff-1")
    fstate = traveled.markers["files"]["data/big.bin"]
    assert fstate["digests"], "digests did not travel with the spec"
    assert task_b.wait(30)
    assert task_b.status == task_b.SUCCEEDED
    assert dst_inner.store.get("out/big.bin") == payload
    assert task_b.files[-1].checksum.startswith(COMPOSITE_PREFIX)
    assert counting.checksum_calls == 0
    assert coord.metrics.handoffs == 1
    coord.assert_third_party()
    coord.shutdown(wait=False)


def test_checksum_fold_discarded_when_source_changes_under_pause(tmp_path):
    """A source modified while the task was paused invalidates the
    journaled digests AND hole map: the resume re-sends the whole file
    (no stale old/new mix can pass §7)."""
    clock = Clock(scale=0.0)
    old = os.urandom(2 * MB)
    new = os.urandom(2 * MB)
    src_inner = seed_memory({"data/big.bin": old})
    src_conn = _HoldSrc(src_inner)
    src_conn.arm_hold(["data/"], 256 * KB)
    dst_inner = MemoryConnector()
    dst_conn = _InstrumentedDst(dst_inner)

    mgr = make_site(tmp_path, "mut", clock)
    opts = TransferOptions(startup_cost=0.0, integrity=True, concurrency=1,
                           parallelism=1, blocksize=64 * KB,
                           digest_segment=128 * KB, coalesce_threshold=0)
    task = mgr.submit(Endpoint(src_conn, "data", "src-ep"),
                      Endpoint(dst_conn, "out", "dst-ep"), opts,
                      task_id="mut-1")
    assert src_conn.engaged.wait(30)
    wait_landed(task, 256 * KB)
    mgr.pause("mut-1")
    src_conn.release()
    assert task.wait_idle(30)
    deadline = time.monotonic() + 30
    while task.status != task.PAUSED and time.monotonic() < deadline:
        time.sleep(0.005)
    assert task.status == task.PAUSED
    st = mgr.service.markers.load("mut-1")["files"]["data/big.bin"]
    assert st["done"] and st["digests"]  # real partial progress existed

    # the source changes while the task is paused (same size)
    src_inner.store.put("data/big.bin", new)
    mgr.resume("mut-1")
    assert task.wait(30)
    assert task.status == task.SUCCEEDED, task.events[-3:]
    # byte-exact against the CURRENT source, verified, no stale mix
    assert dst_inner.store.get("out/big.bin") == new
    assert task.stats.integrity_failures == 0
    # the whole file was re-sent: old partial progress was discarded
    assert dst_conn.written("out/") >= len(new)
    assert any("source changed" in msg for _, msg in task.events)
    mgr.shutdown(wait=False)


def test_cancelled_spec_import_leaves_no_markers(tmp_path):
    """A cancelled spec is registered terminal on arrival; its traveled
    markers must NOT be installed (a later same-id submission would
    silently inherit the hole map)."""
    clock = Clock(scale=0.0)
    mgr = make_site(tmp_path, "c", clock)
    spec = TransferSpec.new("dead-1", "src-ep", "data", "dst-ep", "out",
                            tenant="alice")
    spec.state = "cancelled"
    spec.markers = {"files": {"data/x.bin": {"done": [[0, 1024]],
                                             "complete": False}}}
    task = mgr.import_state(
        spec.to_payload(),
        Endpoint(MemoryConnector(), "data", "src-ep"),
        Endpoint(MemoryConnector(), "out", "dst-ep"))
    assert task.status == TransferTask.CANCELLED
    assert task.wait(1)
    assert mgr.service.markers.load("dead-1") == {"files": {}}
    mgr.shutdown(wait=False)


# --------------------------------------------------------------------------
# placement policies
# --------------------------------------------------------------------------
def _fabricated_sites(tmp_path, clock, depths, advisors=None):
    coord = FederatedCoordinator(placement="owner")
    eps = {"src-ep": MemoryConnector(), "dst-ep": MemoryConnector()}
    sites = []
    for i, depth in enumerate(depths):
        adv = (advisors or {}).get(i)
        handle = coord.register_site(
            f"s{i}", make_site(tmp_path, f"s{i}", clock, advisor=adv), eps)
        handle.digest = QueueDigest(site_id=f"s{i}", seq=i, queued=depth,
                                    running=0, paused=0, in_flight_bytes=0)
        sites.append(handle)
    return coord, sites


def test_least_loaded_placement(tmp_path):
    clock = Clock(scale=0.0)
    coord, sites = _fabricated_sites(tmp_path, clock, depths=(5, 0, 2))
    coord.placement = "least-loaded"
    spec = TransferSpec.new("p1", "src-ep", "data", "dst-ep", "out")
    assert coord._place(spec, sites).site_id == "s1"


def test_advisor_placement_prefers_predicted_fastest(tmp_path):
    clock = Clock(scale=0.0)
    fast = Advisor([Route("r", PerfModel(route="r", t0=0.001,
                                         alpha=10.0, bytes_total=MB))])
    slow = Advisor([Route("r", PerfModel(route="r", t0=0.5,
                                         alpha=10.0, bytes_total=MB))])
    coord, sites = _fabricated_sites(tmp_path, clock, depths=(0, 0),
                                     advisors={0: slow, 1: fast})
    coord.placement = "advisor"
    spec = TransferSpec.new("p2", "src-ep", "data", "dst-ep", "out",
                            route="r", n_files=100, nbytes=MB)
    assert coord._place(spec, sites).site_id == "s1"
    # load scales the prediction: pile depth onto the fast site and the
    # slow-but-idle one wins
    sites[1].digest = QueueDigest(site_id="s1", seq=9, queued=2000,
                                  running=0, paused=0, in_flight_bytes=0)
    assert coord._place(spec, sites).site_id == "s0"


def test_callable_placement_policy(tmp_path):
    clock = Clock(scale=0.0)
    coord, sites = _fabricated_sites(tmp_path, clock, depths=(0, 0))
    coord.placement = lambda spec, candidates: candidates[-1]
    spec = TransferSpec.new("p3", "src-ep", "data", "dst-ep", "out")
    assert coord._place(spec, sites).site_id == "s1"


def test_handoff_without_adoptable_peer_never_strands_the_task(tmp_path):
    """If no peer can adopt, handoff must raise BEFORE the destructive
    export — the task (and its marker state) stays on the origin and
    remains resumable."""
    clock = Clock(scale=0.0)
    payload = os.urandom(1 * MB)
    src_conn = _HoldSrc(seed_memory({"data/big.bin": payload}))
    src_conn.arm_hold(["data/"], 128 * KB)
    dst_conn = MemoryConnector()
    coord = FederatedCoordinator(placement="owner")
    coord.register_site("a", make_site(tmp_path, "a", clock),
                        {"src-ep": src_conn, "dst-ep": dst_conn},
                        owns={"src-ep", "dst-ep"})
    # the only peer cannot reach the destination endpoint
    coord.register_site("b", make_site(tmp_path, "b", clock),
                        {"src-ep": src_conn}, owns=set())
    spec = TransferSpec.new(
        "strand-1", "src-ep", "data", "dst-ep", "out", tenant="alice",
        options=TransferOptions(startup_cost=0.0, concurrency=1,
                                parallelism=1, blocksize=64 * KB,
                                coalesce_threshold=0),
        n_files=1, nbytes=len(payload))
    task = coord.submit(spec.to_json())
    assert src_conn.engaged.wait(30)
    mgr_a = coord.sites()["a"].manager
    mgr_a.pause("strand-1")
    src_conn.release()
    assert task.wait_idle(30)
    deadline = time.monotonic() + 30
    while task.status != task.PAUSED and time.monotonic() < deadline:
        time.sleep(0.005)
    assert task.status == task.PAUSED

    with pytest.raises(LookupError):
        coord.handoff("strand-1")
    # nothing was destroyed: still placed at (and resumable on) site a
    assert coord.site_of("strand-1") == "a"
    assert task.status == task.PAUSED
    assert mgr_a.service.markers.load("strand-1")["files"]
    assert mgr_a.resume("strand-1")
    assert task.wait(30)
    assert task.status == task.SUCCEEDED
    assert dst_conn.store.get("out/big.bin") == payload
    coord.shutdown(wait=False)


def test_handoff_and_adoption_stay_on_the_chrome_timeline(tmp_path):
    """The coordinator's charge-free ``handoff`` and ``adopt`` windows
    carry host stamps, so the sites' Chrome exports keep them."""
    clock = Clock(scale=0.0)
    payload = os.urandom(1 * MB)
    src_conn = _HoldSrc(seed_memory({"data/big.bin": payload}))
    src_conn.arm_hold(["data/"], 128 * KB)
    dst_conn = MemoryConnector()
    eps = {"src-ep": src_conn, "dst-ep": dst_conn}
    coord = FederatedCoordinator(placement="owner")
    coord.register_site("a", make_site(tmp_path, "a", clock), eps,
                        owns={"src-ep", "dst-ep"})
    coord.register_site("b", make_site(tmp_path, "b", clock), eps,
                        owns=set())
    spec = TransferSpec.new(
        "chrome-1", "src-ep", "data", "dst-ep", "out", tenant="alice",
        options=TransferOptions(startup_cost=0.0, concurrency=1,
                                parallelism=1, blocksize=64 * KB,
                                coalesce_threshold=0),
        n_files=1, nbytes=len(payload))
    task = coord.submit(spec.to_json())
    assert src_conn.engaged.wait(30)
    mgr_a = coord.sites()["a"].manager
    mgr_a.pause("chrome-1")
    src_conn.release()
    assert task.wait_idle(30)
    deadline = time.monotonic() + 30
    while task.status != task.PAUSED and time.monotonic() < deadline:
        time.sleep(0.005)
    assert task.status == task.PAUSED

    moved = coord.handoff("chrome-1", to_site="b")
    assert moved is not None and moved.wait(30)
    assert moved.status == moved.SUCCEEDED
    names = {}
    for site in ("a", "b"):
        p = str(tmp_path / f"chrome-{site}.json")
        coord.sites()[site].manager.tracer.export_chrome(p)
        with open(p) as fh:
            names[site] = {ev["name"] for ev in json.load(fh)["traceEvents"]}
    assert {"adopt", "handoff"} <= names["a"]
    assert "adopt" in names["b"]
    coord.shutdown(wait=False)


def test_fail_site_reports_stranded_without_losing_moved(tmp_path):
    """A failover where one task has no adoptable peer still re-homes
    the others, pauses+checkpoints the stranded one on the dead site's
    durable store, and reports both through StrandedTasksError."""
    clock = Clock(scale=0.0)
    big_a = os.urandom(1 * MB)
    big_b = os.urandom(1 * MB)
    src_conn = _HoldSrc(seed_memory({"data/t0/a.bin": big_a,
                                     "data/t1/b.bin": big_b}))
    src_conn.arm_hold(["data/"], 128 * KB)
    dst_shared = MemoryConnector()
    dst_only_a = MemoryConnector()
    eps_a = {"src-ep": src_conn, "dst-ep": dst_shared,
             "dst-only-a": dst_only_a}
    eps_b = {"src-ep": src_conn, "dst-ep": dst_shared}

    coord = FederatedCoordinator(placement="owner")
    mgr_a = make_site(tmp_path, "a", clock)
    coord.register_site("a", mgr_a, eps_a, owns=set(eps_a))
    coord.register_site("b", make_site(tmp_path, "b", clock), eps_b,
                        owns=set())
    opts = TransferOptions(startup_cost=0.0, concurrency=1, parallelism=1,
                           blocksize=64 * KB, coalesce_threshold=0)
    t0 = coord.submit(TransferSpec.new(
        "ok-1", "src-ep", "data/t0", "dst-ep", "out/t0", tenant="alice",
        options=opts, n_files=1, nbytes=len(big_a)).to_json())
    t1 = coord.submit(TransferSpec.new(
        "stuck-1", "src-ep", "data/t1", "dst-only-a", "out/t1",
        tenant="bob", options=opts, n_files=1,
        nbytes=len(big_b)).to_json())
    assert src_conn.engaged.wait(30)

    caught: list = []

    def do_fail():
        try:
            coord.fail_site("a", timeout=60)
        except StrandedTasksError as e:
            caught.append(e)

    failer = threading.Thread(target=do_fail, daemon=True)
    failer.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if all(t._pause_req.is_set() or t._done.is_set()
               or t.status == t.PAUSED for t in (t0, t1)):
            break
        time.sleep(0.005)
    src_conn.release()
    failer.join(60)
    assert not failer.is_alive()

    assert caught, "StrandedTasksError was not raised"
    err = caught[0]
    assert err.moved == [("ok-1", "b")]
    assert err.stranded == ["stuck-1"]
    # the adoptable task completed on the peer despite the stranding
    task_b = coord.task("ok-1")
    assert task_b.wait(30) and task_b.status == task_b.SUCCEEDED
    assert dst_shared.store.get("out/t0/a.bin") == big_a
    # the stranded one was paused, not left streaming; any checkpoint
    # it made stays readable on the dead site's durable store (empty is
    # legitimate when the pause won the race before bytes landed), and
    # its charge accounting was not corrupted by the teardown
    assert t1.status == t1.PAUSED
    state = mgr_a.service.markers.load("stuck-1")
    assert isinstance(state["files"], dict)
    if t1.stats.bytes_done:  # bytes landed -> they must be resumable
        assert sum(ln for st in state["files"].values()
                   for _, ln in st.get("done", [])) == t1.stats.bytes_done
    assert t1.stats.actual_model_seconds >= 0
    coord.shutdown(wait=False)


def test_unresolvable_spec_is_rejected(tmp_path):
    clock = Clock(scale=0.0)
    coord, _ = _fabricated_sites(tmp_path, clock, depths=(0,))
    spec = TransferSpec.new("p4", "no-such-ep", "data", "dst-ep", "out")
    with pytest.raises(LookupError):
        coord.submit(spec)


# --------------------------------------------------------------------------
# the federated chaos scenario
# --------------------------------------------------------------------------
def test_run_federated_quick(tmp_path):
    runner = ScenarioRunner(str(tmp_path), clock=Clock(scale=0.0))
    res = runner.run_federated(n_sites=2, n_tasks=4, strict=True)
    assert res.ok
    assert res.moved, "the site failure must hand off at least one task"
    assert res.coordinator.metrics.failovers == 1


@pytest.mark.chaos
def test_run_federated_chaos(tmp_path):
    """Acceptance: multi-site fleet under an injected fault schedule,
    one site killed mid-flight — placement, byte-exact handoff (holes
    only), tenant/charge attribution, and third-party semantics all
    assert inside run_federated (strict)."""
    runner = ScenarioRunner(str(tmp_path), clock=Clock(scale=0.0))
    schedule = (FaultSchedule(seed=13)
                .transient(op="read", at=2, times=2)
                .rate_limit(op="send_batch", at=1, times=1,
                            retry_after=0.05))
    res = runner.run_federated(n_sites=3, n_tasks=6, schedule=schedule,
                               strict=True)
    assert res.ok
    assert res.moved
    assert schedule.events, "chaos was live, not a no-op"
    for r in res.results:
        assert r.task.status == r.task.SUCCEEDED
