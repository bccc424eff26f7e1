"""Training driver: data -> jit(train_step) -> two-tier checkpointing.

Runs anywhere: on the CPU container it trains reduced (--smoke) configs on a
1x1 mesh; on a pod the same code path runs the full config on (16, 16) (the
mesh adapts to whatever devices exist). Features exercised here:

* deterministic step-indexed data (O(1) resume, no iterator state)
* AdamW + warmup/cosine + grad clip (+ optional int8 grad compression)
* crash recovery: restore_latest() from hot or RapidRAID-archived tier
* periodic save; older checkpoints migrate to the coded archival tier
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager, place
from repro.configs import get_config
from repro.core import jitcache
from repro.data import pipeline as data_lib
from repro.launch.mesh import make_local_mesh
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import sharding, steps


def run_training(cfg, ocfg: adamw.OptConfig, dcfg: data_lib.DataConfig,
                 n_steps: int, *, mesh=None, ckpt: CheckpointManager | None
                 = None, save_every: int = 0, log_every: int = 10,
                 log=print) -> dict:
    """Train for n_steps (resuming if a checkpoint exists); returns metrics."""
    mesh = mesh or make_local_mesh(1, 1)
    sharding.set_activation_hints(mesh, batch=dcfg.global_batch)
    source = data_lib.make_source(dcfg)

    params = model_lib.init(jax.random.PRNGKey(dcfg.seed), cfg)
    opt_state = adamw.init_opt(params, ocfg)
    state_like = {"params": params, "opt": opt_state,
                  "step": np.int64(0)}

    sshard = sharding.state_shardings(cfg, mesh, state_like, ocfg)
    pshard, oshard = sshard["params"], sshard["opt"]
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          sharding.batch_specs(cfg, mesh),
                          is_leaf=lambda x: isinstance(x, P))

    start = 0
    if ckpt is not None:
        step_found, restored = ckpt.restore_latest(state_like)
        if step_found is not None:
            log(f"resuming from checkpoint step {step_found} "
                f"(tier={ckpt.tier(step_found)})")
            params = place(restored["params"], pshard)
            opt_state = place(restored["opt"], oshard)
            start = int(restored["step"])
    if start == 0:
        params = place(params, pshard)
        opt_state = place(opt_state, oshard)

    step_fn = jax.jit(steps.build_train_step(cfg, ocfg),
                      in_shardings=(pshard, oshard, bshard),
                      out_shardings=(pshard, oshard, None),
                      donate_argnums=(0, 1))

    history = []
    t0 = time.time()
    for step in range(start, n_steps):
        batch = data_lib.batch_for(cfg, source, step)
        batch = jax.tree.map(
            lambda a, s: jax.device_put(a, s), dict(batch),
            {k: bshard[k] for k in batch})
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == n_steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, **m})
            log(f"step {step:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                f"gnorm={m['grad_norm']:.2f} lr={m['lr']:.2e} "
                f"({time.time()-t0:.1f}s)")
        if ckpt is not None and save_every and (step + 1) % save_every == 0:
            if ckpt.ccfg.device_direct:
                # flatten/pack/encode straight from the device buffers —
                # no host mirror of params/opt is ever built
                state = {"params": params, "opt": opt_state,
                         "step": np.int64(step + 1)}
                ckpt.save_sharded(step + 1, state, mesh=mesh)
            else:
                state = {"params": jax.tree.map(np.asarray, params),
                         "opt": jax.tree.map(np.asarray, opt_state),
                         "step": np.int64(step + 1)}
                ckpt.save(step + 1, state)
            log(f"checkpoint saved at step {step + 1} "
                f"(tiers: {[ckpt.tier(s) for s in ckpt.steps()]})")
    return {"history": history, "final_loss": history[-1]["loss"],
            "params": params, "opt": opt_state}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the same family (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--ckpt-root", default="")
    ap.add_argument("--device-direct", action="store_true",
                    help="erasure-code checkpoints straight from device "
                         "buffers (no host blob, no hot replicas)")
    ap.add_argument("--data", default="", help="binary token corpus path")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    jitcache.enable_persistent_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    ocfg = adamw.OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                           total_steps=args.steps, state_dtype=cfg.param_dtype,
                           compress_grads=args.compress_grads)
    dcfg = data_lib.DataConfig(vocab=cfg.vocab, seq=args.seq,
                               global_batch=args.global_batch,
                               path=args.data or None)
    ckpt = None
    if args.ckpt_root:
        ckpt = CheckpointManager(CheckpointConfig(
            root=args.ckpt_root, device_direct=args.device_direct))
    out = run_training(cfg, ocfg, dcfg, args.steps, ckpt=ckpt,
                       save_every=args.save_every)
    print(f"done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
