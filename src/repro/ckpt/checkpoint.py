"""Distributed checkpointing through the Connector interface.

Design (DESIGN.md §2):

* every pytree leaf becomes one object — except small leaves, which are
  *coalesced* into bundle objects.  The bundle threshold comes straight
  from the paper's performance model: per-file overhead ``t0`` makes
  many-small-files transfers slow (paper §5), so we keep
  ``N * t0 << B / R`` by construction.
* a ``manifest.json`` records the tree structure, shapes, dtypes and a
  per-object **lanesum32 checksum** computed on-device by the Pallas
  checksum kernel (paper §7 strong integrity, source side).
* restore verifies each object's checksum before installing it
  (destination side of §7), and is *mesh-independent*: arrays are
  re-sharded to whatever mesh the restoring job uses (elastic restart).
* saves are atomic: objects land under ``<step>.tmp/`` and the manifest
  write is the commit point, then the directory is renamed.

Each phase runs under a span of the tracer it is given (host time for the
per-layer record): ``ckpt.snapshot`` (D2H), ``ckpt.serialize``,
``ckpt.digest``, ``ckpt.put``, ``ckpt.verify-read``, ``ckpt.commit``,
``ckpt.gc`` on a save; ``ckpt.read``, ``ckpt.digest``, ``ckpt.place``
(H2D) on a restore.
"""

from __future__ import annotations

import json
import threading
from contextlib import contextmanager

import numpy as np

import jax

from ..core.connector import Connector, Credential, Session
from ..core.errors import IntegrityError, NotFound
from ..kernels.checksum.ref import digest_ref
from ..obs.trace import NULL_TRACER, Tracer
from .io import get_bytes, put_bytes

MB = 1024 * 1024


def flatten_state(tree) -> dict:
    """``{"params/embed/table": leaf, ...}``: the manifest's leaf paths."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for keypath, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", "")))
                        for k in keypath)
        out[path] = leaf
    return out


def _leaf_bytes(leaf) -> bytes:
    arr = np.asarray(jax.device_get(leaf))
    return arr.tobytes()


def save_checkpoint(state, connector: Connector, base: str, step: int,
                    credential: Credential | None = None,
                    bundle_threshold: int = 4 * MB,
                    verify: bool = True, tracer: Tracer = NULL_TRACER) -> dict:
    """Writes ``state`` under ``base/step_<n>/``.  Returns the manifest."""
    leaves = flatten_state(state)
    session = connector.start(credential)
    tmp = f"{base}/step_{step}.tmp"
    final = f"{base}/step_{step}"
    manifest = {"step": step, "objects": {}, "bundles": {},
                "checksum_algorithm": "lanesum32"}
    try:
        bundle: list[tuple[str, bytes, str, list, str]] = []
        bundle_size = 0
        bundle_idx = 0

        def flush_bundle():
            nonlocal bundle, bundle_size, bundle_idx
            if not bundle:
                return
            name = f"bundle_{bundle_idx}.bin"
            with tracer.span("ckpt.put"):
                blob = b"".join(b for _, b, _, _, _ in bundle)
                put_bytes(connector, session, f"{tmp}/{name}", blob)
            off = 0
            for path, data, dig, shape, dtype in bundle:
                manifest["bundles"][path] = {
                    "object": name, "offset": off, "length": len(data),
                    "checksum": dig, "shape": shape, "dtype": dtype,
                }
                off += len(data)
            bundle_idx += 1
            bundle = []
            bundle_size = 0

        for path, leaf in sorted(leaves.items()):
            with tracer.span("ckpt.serialize"):
                data = _leaf_bytes(leaf)
                arr = np.asarray(jax.device_get(leaf))
                shape, dtype = list(arr.shape), str(arr.dtype)
            with tracer.span("ckpt.digest"):
                dig = digest_ref(data)
            if len(data) < bundle_threshold:
                bundle.append((path, data, dig, shape, dtype))
                bundle_size += len(data)
                if bundle_size >= 8 * bundle_threshold:
                    flush_bundle()
                continue
            obj = f"{tmp}/{path.replace('/', '.')}.bin"
            with tracer.span("ckpt.put"):
                put_bytes(connector, session, obj, data)
            manifest["objects"][path] = {
                "object": f"{path.replace('/', '.')}.bin",
                "checksum": dig, "shape": shape, "dtype": dtype,
            }
        flush_bundle()

        if verify:  # §7: re-read from storage and compare checksums
            for path, meta in manifest["objects"].items():
                with tracer.span("ckpt.verify-read"):
                    got = get_bytes(connector, session,
                                    f"{tmp}/{meta['object']}")
                with tracer.span("ckpt.digest"):
                    ok = digest_ref(got) == meta["checksum"]
                if not ok:
                    raise IntegrityError(f"post-write verify failed: {path}")

        with tracer.span("ckpt.commit"):
            put_bytes(connector, session, f"{tmp}/manifest.json",
                      json.dumps(manifest).encode())
            connector.command(session, "rename", tmp, to=final)
            # update the "latest" pointer last (atomic-ish commit marker)
            put_bytes(connector, session, f"{base}/LATEST",
                      str(step).encode())
        return manifest
    finally:
        connector.destroy(session)


def latest_step(connector: Connector, base: str,
                credential: Credential | None = None,
                tracer: Tracer = NULL_TRACER) -> int | None:
    session = connector.start(credential)
    try:
        try:
            with tracer.span("ckpt.read"):
                return int(get_bytes(connector, session, f"{base}/LATEST"))
        except NotFound:
            return None
    finally:
        connector.destroy(session)


def restore_checkpoint(abstract_state, connector: Connector, base: str,
                       step: int | None = None,
                       credential: Credential | None = None,
                       shardings=None, verify: bool = True,
                       tracer: Tracer = NULL_TRACER):
    """Restores into the structure of ``abstract_state``; if
    ``shardings`` (a matching pytree of NamedSharding) is given, arrays
    are placed sharded — on a *possibly different* mesh than the saver's
    (elastic restart)."""
    session = connector.start(credential)
    try:
        with tracer.span("ckpt.read"):
            if step is None:
                step = int(get_bytes(connector, session, f"{base}/LATEST"))
            root = f"{base}/step_{step}"
            manifest = json.loads(get_bytes(connector, session,
                                            f"{root}/manifest.json"))
        bundles_cache: dict[str, bytes] = {}

        def load(path: str) -> np.ndarray:
            if path in manifest["objects"]:
                meta = manifest["objects"][path]
                with tracer.span("ckpt.read"):
                    data = get_bytes(connector, session,
                                     f"{root}/{meta['object']}")
            elif path in manifest["bundles"]:
                meta = manifest["bundles"][path]
                obj = meta["object"]
                if obj not in bundles_cache:
                    with tracer.span("ckpt.read"):
                        bundles_cache[obj] = get_bytes(connector, session,
                                                       f"{root}/{obj}")
                data = bundles_cache[obj][meta["offset"]:
                                          meta["offset"] + meta["length"]]
            else:
                raise NotFound(f"checkpoint object for {path}")
            if verify:
                with tracer.span("ckpt.digest"):
                    ok = digest_ref(data) == meta["checksum"]
                if not ok:
                    raise IntegrityError(
                        f"checksum mismatch restoring {path}")
            return np.frombuffer(data, dtype=meta["dtype"]) \
                .reshape(meta["shape"])

        leaves = flatten_state(abstract_state)
        sh_leaves = flatten_state(shardings) if shardings is not None else {}
        restored = {}
        for path, spec in leaves.items():
            arr = load(path)
            if sh_leaves.get(path) is not None:
                with tracer.span("ckpt.place"):
                    arr = jax.device_put(arr, sh_leaves[path])
            restored[path] = arr

        flat = jax.tree_util.tree_flatten_with_path(abstract_state)
        treedef = jax.tree_util.tree_structure(abstract_state)
        ordered = []
        for keypath, _ in flat[0]:
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", "")))
                            for k in keypath)
            ordered.append(restored[path])
        return jax.tree_util.tree_unflatten(treedef, ordered), step
    finally:
        connector.destroy(session)


class CheckpointManager:
    """Async, double-buffered checkpointing for the train loop.

    ``save_async`` snapshots to host (blocking only for D2H), then a
    background thread streams objects through the Connector —
    fire-and-forget, same as the paper's managed transfers.  ``retain``
    old checkpoints are garbage-collected.

    Every save and restore runs under one trace of ``tracer`` (its own
    enabled ``Tracer`` by default), ``save-<step>`` or
    ``restore-<step>``, on each thread it uses; the trace's model-time
    tally is forgotten when the operation ends.
    """

    def __init__(self, connector: Connector, base: str,
                 credential: Credential | None = None, retain: int = 3,
                 tracer: Tracer | None = None):
        self.connector = connector
        self.base = base
        self.credential = credential
        self.retain = retain
        self.tracer = tracer or Tracer()
        self._thread: threading.Thread | None = None
        self._error: Exception | None = None
        self._saved_steps: list[int] = []

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save_async(self, state, step: int):
        self.wait()
        trace = f"save-{step}"
        with self._traced(trace), self.tracer.span("ckpt.snapshot"):
            host_state = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), state)

        def work():
            try:
                with self._traced(trace):
                    save_checkpoint(host_state, self.connector, self.base,
                                    step, credential=self.credential,
                                    tracer=self.tracer)
                    self._saved_steps.append(step)
                    with self.tracer.span("ckpt.gc"):
                        self._gc()
            except Exception as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def _gc(self):
        while len(self._saved_steps) > self.retain:
            victim = self._saved_steps.pop(0)
            session = self.connector.start(self.credential)
            try:
                self.connector.command(session, "delete",
                                       f"{self.base}/step_{victim}")
            except NotFound:
                pass
            finally:
                self.connector.destroy(session)

    def restore_latest(self, abstract_state, shardings=None):
        with self._traced("restore-latest"):
            step = latest_step(self.connector, self.base, self.credential,
                               tracer=self.tracer)
        if step is None:
            return None, None
        with self._traced(f"restore-{step}"):
            return restore_checkpoint(abstract_state, self.connector,
                                      self.base, step,
                                      credential=self.credential,
                                      shardings=shardings,
                                      tracer=self.tracer)

    @contextmanager
    def _traced(self, trace: str):
        """This thread bound to ``trace`` of the manager's tracer while
        entered; the trace's model-time tally is forgotten on exit."""
        try:
            with self.tracer.bind(trace, trace):
                yield
        finally:
            self.tracer.forget(trace)
