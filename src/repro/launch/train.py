"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

End-to-end driver over the full stack on one device: Connector-backed
data, jitted train step, async checkpoints with lanesum32 manifests,
optional third-party replication of each checkpoint.  Run it again with
the same ``--ckpt-dir`` and more ``--steps`` and it resumes from the
latest checkpoint (state and data cursor).
"""

from __future__ import annotations

import argparse
import os


def main(argv: list[str] | None = None):
    """Returns ``(TrainResult, [TransferTask per replicated step])``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--scaled-down", action="store_true", default=True)
    ap.add_argument("--full-size", dest="scaled_down", action="store_false")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoints, corpus and transfer markers; a "
                         "later run with the same directory resumes")
    ap.add_argument("--replicate-to", default=None,
                    help="cloud provider id (s3|gcs|...) for third-party "
                         "checkpoint replication")
    ap.add_argument("--data-records", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args(argv)

    from .compile_cache import use_compile_cache
    use_compile_cache()

    from ..configs import get_config
    from ..connectors import PosixConnector, ObjectStoreConnector, make_cloud
    from ..core import Credential, CredentialStore, Endpoint, TransferService
    from ..ckpt import CheckpointManager, replicate_checkpoint
    from ..data import DataPipelineConfig, ShardedTokenDataset, synthetic_corpus
    from ..models.registry import build
    from ..optim import OptimizerConfig
    from ..runtime.train import TrainLoopConfig, run_training

    cfg = get_config(args.arch)
    if args.scaled_down:
        cfg = cfg.scaled_down()
    api = build(cfg)

    root = os.path.abspath(args.ckpt_dir)
    store = PosixConnector(root)
    # data through the Connector interface
    synthetic_corpus(store, "corpus", vocab_size=cfg.vocab_size,
                     seq_len=args.seq_len, n_records=args.data_records,
                     records_per_shard=64)
    ds = ShardedTokenDataset(store, "corpus", DataPipelineConfig(
        seq_len=args.seq_len, batch_size=args.batch_size))

    ckpt_mgr = CheckpointManager(store, "ckpt")
    replicator = None
    replications = []
    if args.replicate_to:
        cloud = make_cloud(args.replicate_to)
        conn = ObjectStoreConnector(cloud, placement="cloud")
        creds = CredentialStore()
        creds.register("mirror", Credential(conn.credential_scheme, {}))
        svc = TransferService(credential_store=creds,
                              marker_root=os.path.join(root, "markers"))

        def replicator(step):
            task = replicate_checkpoint(
                svc, Endpoint(store, "ckpt"),
                Endpoint(conn, "mirror", "mirror"), step, sync=True)
            replications.append(task)
            print(f"  replicated step {step}: {task.status} "
                  f"({task.stats.bytes_done / 1e6:.1f} MB)")

    opt = OptimizerConfig(peak_lr=args.lr, warmup_steps=20,
                          total_steps=args.steps, state_dtype="float32")
    loop = TrainLoopConfig(total_steps=args.steps, log_every=10,
                           ckpt_every=args.ckpt_every,
                           replicate_every=args.ckpt_every
                           if args.replicate_to else 0)
    result = run_training(api, opt, loop, ds, ckpt_mgr=ckpt_mgr,
                          replicator=replicator)
    print(f"done: {result.steps_run} steps, final loss "
          f"{result.final_loss:.4f}, {result.tokens_per_second:.0f} tok/s"
          + (f", restored from step {result.restored_from}"
             if result.restored_from else ""))
    return result, replications


if __name__ == "__main__":
    main()
