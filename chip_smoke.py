"""Smoke run of the main path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # restore under another mesh, four chips

One chip: ``repro.launch.train`` trains qwen1.5-0.5b at its full
published width (random weights from a seed) for a few steps, saves an
async checkpoint with lanesum32 manifests and replicates it to an
emulated S3 store as a third-party transfer.  A second launch resumes
from that checkpoint.  Then the checkpoint is restored onto the chip,
verified against its manifest, and every leaf's lanesum32 digest is
computed on the chip by the compiled Pallas kernel and compared with the
host digest in the manifest.

Four chips: the train state is built and stepped on a (2, 2) mesh and
saved, then restored onto a (4, 1) mesh; every leaf must sit on all
four devices and be bit-equal to a one-device restore of the same
checkpoint, and the restored state takes one step on the new mesh.

Everything runs in this one process: the chip belongs to the first
process that touches JAX.  The timings printed are smoke timings of one
cold run, not benchmark metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every check passed.  Without a TPU the script exits
nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
STEPS = 3  # steps before the checkpoint that the restart restores


class PhaseTimer:
    """Wall seconds per phase, with the seconds JAX spent tracing,
    lowering and compiling in it; listens to JAX while entered."""

    def __init__(self):
        self.compile_s = 0.0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration

    def __enter__(self) -> "PhaseTimer":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @contextmanager
    def __call__(self, name: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        print(f"[smoke timing] {name}: {time.perf_counter() - t0:.1f} s "
              f"wall, {self.compile_s - c0:.1f} s of it compiling",
              flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def _model(scaled: bool):
    """The launcher's model and train-state shapes (fp32 moments)."""
    from repro.configs import get_config
    from repro.models.registry import build
    from repro.optim import OptimizerConfig
    from repro.runtime.steps import abstract_train_state

    cfg = get_config(ARCH)
    if scaled:
        cfg = cfg.scaled_down()
    api = build(cfg)
    opt = OptimizerConfig(state_dtype="float32")
    abstract = abstract_train_state(api, opt)
    n_params = sum(x.size for x in jax.tree.leaves(abstract["params"]))
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(abstract))
    print(f"model {ARCH}{' (scaled down)' if scaled else ''}: "
          f"{n_params / 1e6:.1f}M params, train state {n_bytes} bytes "
          f"({n_bytes / 1e9:.2f} GB: {cfg.param_dtype} params, "
          f"float32 AdamW moments)", flush=True)
    return cfg, api, opt, abstract


def _finite(losses) -> bool:
    return bool(losses) and all(math.isfinite(loss) for _, loss in losses)


def one_chip(workdir: str, phase: PhaseTimer, scaled: bool = False) -> None:
    from jax.sharding import SingleDeviceSharding

    from repro.ckpt import restore_checkpoint
    from repro.ckpt.checkpoint import flatten_state
    from repro.connectors import PosixConnector
    from repro.core.transfer import TransferTask
    from repro.kernels.checksum.ops import checksum_digest
    from repro.launch import train

    _, _, _, abstract = _model(scaled)
    size = (["--batch-size", "2", "--seq-len", "64"] if scaled else
            ["--full-size", "--batch-size", "4", "--seq-len", "1024"])
    argv = ["--arch", ARCH, *size, "--ckpt-dir", workdir,
            "--ckpt-every", str(STEPS), "--replicate-to", "s3"]

    with phase(f"train {STEPS} steps + save + replicate"):
        result, replications = train.main(argv + ["--steps", str(STEPS)])
    check(_finite(result.losses),
          f"logged losses {result.losses} finite")
    statuses = [t.status for t in replications]
    check(statuses == [TransferTask.SUCCEEDED],
          f"replication of step {STEPS}: {statuses}")

    with phase("restart: restore + 2 steps + save"):
        result, _ = train.main(argv + ["--steps", str(STEPS + 2)])
    check(result.restored_from == STEPS,
          f"restored_from == {result.restored_from}")
    check(result.steps_run == 2 and _finite(result.losses),
          f"{result.steps_run} steps after the restore, logged losses "
          f"{result.losses} finite")

    device = jax.devices()[0]
    with phase(f"restore step {STEPS} onto the chip, verified"):
        state, _ = restore_checkpoint(
            abstract, PosixConnector(workdir), "ckpt", step=STEPS,
            shardings=jax.tree.map(
                lambda _: SingleDeviceSharding(device), abstract),
            verify=True)
        jax.block_until_ready(state)
    manifest = json.loads(
        (Path(workdir) / "ckpt" / f"step_{STEPS}" / "manifest.json")
        .read_text())
    digests = {path: meta["checksum"] for part in ("objects", "bundles")
               for path, meta in manifest[part].items()}
    leaves = flatten_state(state)
    check(set(leaves) == set(digests),
          f"restore verified all {len(leaves)} leaves against their "
          f"manifest digests")
    check(all(x.devices() == {device} for x in leaves.values()),
          f"restored leaves on {device.platform}")

    with phase("device lanesum32 of every restored leaf"):
        matched = sum(checksum_digest(x) == digests[path]
                      for path, x in leaves.items())
    check(matched == len(leaves),
          f"device lanesum32 == manifest digest for {matched}/"
          f"{len(leaves)} leaves")


def four_chips(workdir: str, phase: PhaseTimer,
               scaled: bool = False) -> None:
    import numpy as np
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from repro.ckpt import CheckpointManager, restore_checkpoint
    from repro.ckpt.checkpoint import flatten_state
    from repro.connectors import PosixConnector
    from repro.data import (DataPipelineConfig, ShardedTokenDataset,
                            synthetic_corpus)
    from repro.launch.cells import state_sharding_tree
    from repro.launch.mesh import make_test_mesh
    from repro.runtime.steps import make_train_step
    from repro.runtime.train import TrainLoopConfig, run_training
    from repro.sharding.rules import batch_spec, production_rules

    cfg, api, opt, abstract = _model(scaled)
    batch_size, seq_len = 4, (64 if scaled else 1024)
    store = PosixConnector(workdir)
    synthetic_corpus(store, "corpus", vocab_size=cfg.vocab_size,
                     seq_len=seq_len, n_records=64, records_per_shard=16)
    data = ShardedTokenDataset(store, "corpus", DataPipelineConfig(
        seq_len=seq_len, batch_size=batch_size))

    def shardings(mesh):
        return state_sharding_tree(abstract, mesh,
                                   production_rules(False, mesh=mesh))

    src = make_test_mesh(2, 2)
    with phase("train 2 steps on a (2, 2) mesh + save"):
        result = run_training(
            api, opt, TrainLoopConfig(total_steps=2, log_every=1,
                                      ckpt_every=2),
            data, ckpt_mgr=CheckpointManager(store, "ckpt"), mesh=src,
            state_shardings=shardings(src))
    check(result.steps_run == 2 and _finite(result.losses),
          "2 steps on the (2, 2) mesh, losses finite")

    dst = make_test_mesh(4, 1)
    dst_sh = shardings(dst)
    with phase("restore onto a (4, 1) mesh and onto one device"):
        moved, _ = restore_checkpoint(abstract, store, "ckpt", step=2,
                                      shardings=dst_sh)
        # the reference; the restore above verified these same objects
        whole, _ = restore_checkpoint(
            abstract, store, "ckpt", step=2, verify=False,
            shardings=jax.tree.map(
                lambda _: SingleDeviceSharding(jax.devices()[0]), abstract))
    all_devices = set(dst.devices.flat)
    moved_l, whole_l, want_l = (flatten_state(moved), flatten_state(whole),
                                flatten_state(dst_sh))
    split = [p for p, s in want_l.items() if not s.is_fully_replicated]
    check(all(x.sharding.is_equivalent_to(want_l[p], x.ndim)
              for p, x in moved_l.items()),
          f"all {len(moved_l)} leaves carry their (4, 1) shardings")
    check(all({s.device for s in x.addressable_shards} == all_devices
              for x in moved_l.values()),
          "every leaf has shards on all 4 devices")
    check(bool(split) and all(s.data.shape != moved_l[p].shape
                              for p in split
                              for s in moved_l[p].addressable_shards),
          f"{len(split)} sharded leaves split across the devices, none "
          f"whole on one device")
    same = sum(np.asarray(moved_l[p]).tobytes()
               == np.asarray(whole_l[p]).tobytes() for p in moved_l)
    check(same == len(moved_l),
          f"{same}/{len(moved_l)} leaves bit-equal to the one-device "
          f"restore")
    del whole, whole_l

    with phase("one step on the (4, 1) mesh"):
        step = jax.jit(make_train_step(api, opt), donate_argnums=(0,),
                       in_shardings=(dst_sh, None),
                       out_shardings=(dst_sh, None))
        batch = jax.device_put(next(data.batches()), NamedSharding(
            dst, batch_spec(batch_size, dst)))
        _, metrics = step(moved, batch)
        loss = float(metrics["loss"])
    check(math.isfinite(loss), f"loss {loss:.4f} on the (4, 1) mesh")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the restore under another mesh, and nothing "
                         "else")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices; JAX found {len(devices)}")
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {devices[0].device_kind}, {len(devices)} visible; "
          f"compile cache {use_compile_cache()}", flush=True)
    # the checkpoints (GBs) live here and go when the run ends
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir, \
            PhaseTimer() as phase:
        (one_chip if args.chips == 1 else four_chips)(workdir, phase)
    stats = devices[0].memory_stats() or {}
    print(f"device 0 peak_bytes_in_use: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
