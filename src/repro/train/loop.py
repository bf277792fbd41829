"""Training driver: data -> jit'd train_step -> metrics/checkpoints."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import shardctx
from repro.launch.steps import make_train_step
from repro.models import api
from repro.models.common import ModelConfig, count_params
from repro.train import checkpoint as ckpt_lib
from repro.train.data import LMBatches, modal_extras
from repro.train.optimizer import AdamW, cosine_schedule


@dataclasses.dataclass
class TrainReport:
    steps: int
    losses: list
    final_loss: float
    initial_loss: float
    wall_s: float
    params_m: float


def train(cfg: ModelConfig, *, steps: int = 100, batch: int = 8, seq: int = 64,
          lr: float = 3e-4, seed: int = 0, mesh=None, log_every: int = 10,
          ckpt_path: str = "", num_micro: int = 1, verbose: bool = True) -> TrainReport:
    opt = AdamW(learning_rate=cosine_schedule(lr, warmup=max(steps // 10, 1),
                                              total=steps))
    params = api.build_params(cfg, seed)
    opt_state = opt.init(params)
    with shardctx.use_mesh(mesh):
        step_fn = jax.jit(make_train_step(cfg, opt, num_micro=num_micro,
                                          mesh=mesh))
    data = LMBatches(cfg.vocab_size, batch, seq, seed=seed)

    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in data(i).items()}
        for k, v in modal_extras(cfg, batch, seed=seed, step=i).items():
            b[k] = jnp.asarray(v, cfg.cdt)
        params, opt_state, m = step_fn(params, opt_state, b)
        loss = float(m["loss"])
        losses.append(loss)
        if verbose and (i % log_every == 0 or i == steps - 1):
            print(f"  step {i:4d} loss {loss:.4f} gnorm "
                  f"{float(m['grad_norm']):.3f}")
        if ckpt_path and (i + 1) % max(steps // 2, 1) == 0:
            ckpt_lib.save(ckpt_path, {"params": params}, step=i + 1)
    wall = time.perf_counter() - t0
    return TrainReport(steps=steps, losses=losses, final_loss=losses[-1],
                       initial_loss=losses[0], wall_s=wall,
                       params_m=count_params(params) / 1e6)
