"""Production training driver: mesh + shardings + fault-tolerant loop.

Usage (CPU demo / real cluster):
  python -m repro.launch.train --arch qwen1.5-0.5b --preset smoke --steps 200
  python -m repro.launch.train --arch qwen3-8b --preset full \
      --mesh single --batch 256 --seq 4096          # on a real 256-chip pod

On ≥256 devices it builds the production mesh and shards params (TP +
ZeRO), batches (DP) and optimizer state exactly as the dry-run proves out;
on fewer devices it falls back to a 1×N data-parallel mesh so the same
code path runs anywhere.  The Trainer provides checkpoint/restart,
straggler tracking, and preemption handling (repro/runtime).
"""

from __future__ import annotations

import argparse
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import models
from repro.configs import get_config, get_smoke_config
from repro.data import TokenPipeline
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import sharding as shd_env
from repro.runtime import Trainer, TrainerConfig


def make_mesh_auto():
    n = len(jax.devices())
    if n >= 512:
        return mesh_lib.make_production_mesh(multi_pod=True)
    if n >= 256:
        return mesh_lib.make_production_mesh(multi_pod=False)
    return mesh_lib.auto_mesh((n, 1), ("data", "model"), jax.devices())


def build(arch: str, preset: str, batch: int, seq: int, lr: float):
    cfg = get_config(arch) if preset == "full" else get_smoke_config(arch)
    mesh = make_mesh_auto()
    env = mesh_lib.axis_env_for(mesh, batch_shardable=True)
    shd_env.set_axis_env(env)
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    params = jax.jit(
        lambda k: models.init(k, cfg, tp=tp),
        out_shardings=mesh_lib.param_shardings(
            mesh,
            jax.eval_shape(
                lambda k: models.init(k, cfg, tp=tp),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            ),
            env,
        ),
    )(jax.random.PRNGKey(0))
    opt = steps_lib.init_opt_state(params)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=batch, seq_len=seq)

    train_step = steps_lib.make_train_step(cfg, lr=lr)

    @jax.jit
    def step_fn(state, batch):
        params, opt = state
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, metrics = train_step(params, opt, batch)
        return (params, opt), metrics

    return cfg, mesh, (params, opt), pipe, step_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg, mesh, state, pipe, step_fn = build(
        args.arch, args.preset, args.batch, args.seq, args.lr
    )
    print(
        f"arch={cfg.name} devices={len(jax.devices())} "
        f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"params={cfg.total_params()/1e6:.1f}M"
    )

    losses = []

    def logging_step(state, batch):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if len(losses) % 20 == 0:
            first = np.mean(losses[:10])
            print(
                f"step {len(losses):5d} loss {losses[-1]:.4f} "
                f"(first10 {first:.4f})",
                flush=True,
            )
        return state, metrics

    trainer = Trainer(
        logging_step,
        pipe.make_batch,
        state,
        TrainerConfig(
            total_steps=args.steps,
            checkpoint_every=args.ckpt_every,
            checkpoint_dir=args.ckpt_dir,
        ),
    )
    out = trainer.run()
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(
        f"done: {out['final_step']} steps, loss {first:.4f} -> {last:.4f} "
        f"({'LEARNED' if last < first - 0.1 else 'no clear drop'}) "
        f"restarts={out['events'].restarts} stragglers={out['events'].stragglers}"
    )


if __name__ == "__main__":
    main()
