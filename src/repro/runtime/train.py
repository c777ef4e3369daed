"""Fault-tolerant training loop.

Wires together the Connector-backed data pipeline, the jitted train
step, async Connector checkpointing, and third-party checkpoint
replication — the paper's storage abstraction as the framework's
data/ckpt substrate.  Restart is crash-consistent: (model state,
data-iterator cursor) restore from the latest committed checkpoint.
"""

from __future__ import annotations

import functools
import json
import time
from dataclasses import dataclass, field

import numpy as np

import jax
from jax.sharding import NamedSharding

from ..ckpt import CheckpointManager
from ..ckpt.io import get_bytes, put_bytes
from ..core.errors import NotFound
from ..models.registry import ModelApi
from ..optim import OptimizerConfig
from ..sharding.rules import batch_spec
from .steps import abstract_train_state, make_train_state, make_train_step


@dataclass
class TrainLoopConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    replicate_every: int = 0      # 0 = off
    seed: int = 0
    fail_at_step: int = -1        # fault injection for tests


@dataclass
class TrainResult:
    steps_run: int
    final_loss: float
    losses: list = field(default_factory=list)
    restored_from: int | None = None
    tokens_per_second: float = 0.0


def run_training(api: ModelApi, opt_cfg: OptimizerConfig,
                 loop_cfg: TrainLoopConfig, data_iter,
                 ckpt_mgr: CheckpointManager | None = None,
                 replicator=None, mesh=None, state_shardings=None) -> TrainResult:
    """``state_shardings`` places the train state on a mesh: it is built,
    restored and stepped there.  ``mesh`` shards each batch over the
    mesh's data axes; without it batches go to the default device."""
    train_step = make_train_step(api, opt_cfg)
    jit_kwargs = {}
    if state_shardings is not None:
        jit_kwargs = dict(in_shardings=(state_shardings, None),
                          out_shardings=(state_shardings, None))
    step_fn = jax.jit(train_step, donate_argnums=(0,), **jit_kwargs)

    state = None
    start_step = 0
    restored_from = None
    if ckpt_mgr is not None:
        restored, step = ckpt_mgr.restore_latest(
            abstract_train_state(api, opt_cfg), shardings=state_shardings)
        if restored is not None:
            state = (restored if state_shardings is not None
                     else jax.device_put(restored))
            start_step = step
            restored_from = step
            # resume the data cursor
            try:
                session = ckpt_mgr.connector.start(ckpt_mgr.credential)
                cursor = json.loads(get_bytes(
                    ckpt_mgr.connector, session,
                    f"{ckpt_mgr.base}/step_{step}/data_state.json"))
                ckpt_mgr.connector.destroy(session)
                if hasattr(data_iter, "restore"):
                    data_iter.restore(cursor)
            except NotFound:
                pass
    if state is None:
        init = jax.jit(functools.partial(make_train_state, api, opt_cfg),
                       out_shardings=state_shardings)
        state = init(jax.random.PRNGKey(loop_cfg.seed))

    batches = (data_iter.prefetching_batches()
               if hasattr(data_iter, "prefetching_batches") else data_iter)
    losses = []
    t0 = time.time()  # lint: disable=R001(tokens/s is a real training-throughput stat — outside the transfer model entirely)
    tokens = 0
    step = start_step
    for step in range(start_step + 1, loop_cfg.total_steps + 1):
        batch = next(batches) if hasattr(batches, "__next__") \
            else next(iter(batches))
        batch = jax.device_put(batch, None if mesh is None else NamedSharding(
            mesh, batch_spec(len(batch["tokens"]), mesh)))
        if loop_cfg.fail_at_step == step:
            raise RuntimeError(f"injected failure at step {step}")
        state, metrics = step_fn(state, batch)
        tokens += int(np.prod(batch["tokens"].shape))
        if step % loop_cfg.log_every == 0 or step == loop_cfg.total_steps:
            loss = float(metrics["loss"])
            losses.append((step, loss))
            print(f"step {step}: loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f}")
        if ckpt_mgr is not None and (step % loop_cfg.ckpt_every == 0
                                     or step == loop_cfg.total_steps):
            ckpt_mgr.save_async(state, step)
            ckpt_mgr.wait()
            if hasattr(data_iter, "state"):
                session = ckpt_mgr.connector.start(ckpt_mgr.credential)
                put_bytes(ckpt_mgr.connector, session,
                          f"{ckpt_mgr.base}/step_{step}/data_state.json",
                          json.dumps(data_iter.state()).encode())
                ckpt_mgr.connector.destroy(session)
            if replicator is not None and loop_cfg.replicate_every and \
                    step % loop_cfg.replicate_every == 0:
                replicator(step)
    dt = max(time.time() - t0, 1e-9)  # lint: disable=R001(tokens/s is a real training-throughput stat)
    final_loss = losses[-1][1] if losses else float("nan")
    return TrainResult(steps_run=step - start_step, final_loss=final_loss,
                       losses=losses, restored_from=restored_from,
                       tokens_per_second=tokens / dt)
