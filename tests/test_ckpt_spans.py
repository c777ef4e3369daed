"""The checkpoint path's spans: a small-state save and restore through
``CheckpointManager`` on the CPU emits every ``ckpt.*`` span, each under
the trace of its operation, in the manager's tracer and in the process's
host record."""

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.connectors import PosixConnector
from repro.core.clock import host_now
from repro.ckpt import CheckpointManager
from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint
from repro.obs import Tracer, host_spans

SAVE_SPANS = {"ckpt.snapshot", "ckpt.serialize", "ckpt.digest", "ckpt.put",
              "ckpt.verify-read", "ckpt.commit", "ckpt.gc"}
RESTORE_SPANS = {"ckpt.read", "ckpt.digest", "ckpt.place"}


def make_state(step):
    k = jax.random.PRNGKey(step)
    return {
        # over the 4 MiB bundle threshold: its own object, re-read on save
        "params": {"embed": jax.random.normal(k, (1024, 1100)),
                   "b": jnp.full((64,), step, jnp.float32)},
        "opt": {"step": jnp.int32(step)},
    }


def test_manager_save_and_restore_emit_every_ckpt_span(tmp_path):
    conn = PosixConnector(str(tmp_path))
    mgr = CheckpointManager(conn, "run", retain=1)
    t_start = host_now()
    for step in (1, 2):  # the second save garbage-collects the first
        mgr.save_async(make_state(step), step)
        mgr.wait()
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            make_state(0))
    device = jax.devices("cpu")[0]
    shardings = jax.tree.map(lambda _: SingleDeviceSharding(device),
                             abstract)
    restored, step = mgr.restore_latest(abstract, shardings)
    t_end = host_now()
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored["params"]["embed"]),
                                  np.asarray(make_state(2)["params"]["embed"]))

    spans = mgr.tracer.spans()
    by_trace: dict[str, set] = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, set()).add(s.name)
    for step in (1, 2):
        assert by_trace[f"save-{step}"] >= SAVE_SPANS
    assert by_trace["restore-2"] >= RESTORE_SPANS
    # LATEST, read through the connector, whose calls have spans of
    # their own on the thread's bound tracer
    assert by_trace["restore-latest"] == {"ckpt.read", "connector.io"}
    ckpt = [s for s in spans if s.name.startswith("ckpt.")]
    # the D2H runs on the caller's thread, the rest of a save on its own
    snapshot_threads = {s.thread for s in ckpt if s.name == "ckpt.snapshot"}
    assert snapshot_threads == {threading.get_ident()}
    assert {s.thread for s in ckpt if s.name == "ckpt.put"} \
        - snapshot_threads
    for s in ckpt:
        assert t_start <= s.host_t0 <= s.host_t1 <= t_end
    # every trace's model-time tally is forgotten when it ends
    for trace in by_trace:
        assert mgr.tracer.category_seconds(trace) == {}

    hosted = {s.name for s in host_spans().spans
              if t_start <= s.t0 and s.t1 <= t_end}
    assert hosted >= SAVE_SPANS | RESTORE_SPANS


def test_functions_trace_nothing_without_a_tracer(tmp_path):
    conn = PosixConnector(str(tmp_path))
    state = make_state(3)
    before = host_now()
    save_checkpoint(state, conn, "c", step=3)
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
    restore_checkpoint(abstract, conn, "c")
    assert not [s for s in host_spans().spans
                if s.name.startswith("ckpt.") and s.t0 >= before]


def test_functions_take_a_tracer(tmp_path):
    conn = PosixConnector(str(tmp_path))
    tracer = Tracer()
    state = make_state(4)
    with tracer.bind("save-4", "save-4"):
        save_checkpoint(state, conn, "c", step=4, tracer=tracer)
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
    with tracer.bind("restore-4", "restore-4"):
        restore_checkpoint(abstract, conn, "c", tracer=tracer)
    names = {s.name for s in tracer.spans()}
    assert names >= (SAVE_SPANS - {"ckpt.snapshot", "ckpt.gc"}) \
        | (RESTORE_SPANS - {"ckpt.place"})
