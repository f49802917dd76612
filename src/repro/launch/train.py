"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch pointnet2-cls --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together: config registry, synthetic data streams (host-sharded,
restart-exact), AdamW + schedule, async checkpointing, straggler monitor,
restart supervision.  On a real cluster the same driver runs under
multi-host jax.distributed initialisation; here it exercises identical code
paths on the local device (or the host-platform mesh for dry-runs)."""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_config
from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.data.tokens import Prefetcher, token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw_init
from repro.runtime import StragglerMonitor, run_with_restarts


def _policy_override(cfg, args) -> ExecutionPolicy:
    """Config default policy, with --quant applied on top when given."""
    policy = resolve_policy(cfg, None)
    if getattr(args, "quant", None):
        policy = dataclasses.replace(policy, quant=args.quant)
    return policy


def train_pointcloud(cfg, args):
    from repro.core.accelerator import get_accelerator
    from repro.data.pointclouds import sample_batch
    from repro.optim import adamw_update

    # one accelerator = preprocessing engines + policy-driven feature path
    # (quant/backend from the config; --quant overrides without a new config)
    accel = get_accelerator(cfg, _policy_override(cfg, args))
    params = accel.init(jax.random.PRNGKey(args.seed))
    state = adamw_init(params)

    @jax.jit
    def step_fn(params, state, pts, labels):
        (loss, aux), grads = jax.value_and_grad(accel.loss_fn, has_aux=True)(
            params, pts, labels
        )
        params, state, m = adamw_update(
            grads, state, params, lr=args.lr, weight_decay=1e-4
        )
        return params, state, {**aux, **m}

    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) if args.ckpt_dir else None
    mon = StragglerMonitor()
    t0 = time.time()
    for i in range(args.steps):
        pts, cls, seg = sample_batch(
            jax.random.fold_in(jax.random.PRNGKey(args.seed), 10_000 + i),
            args.batch, cfg.n_points,
        )
        labels = cls if cfg.task == "cls" else seg
        mon.step_start()
        params, state, aux = step_fn(params, state, pts, labels)
        dt = mon.step_end(i)
        if mgr:
            mgr.maybe_save(i + 1, {"params": params, "opt": state})
        if i % args.log_every == 0 or i == args.steps - 1:
            print(
                f"step {i}: loss={float(aux['loss']):.4f} acc={float(aux['accuracy']):.3f} "
                f"({dt*1e3:.0f}ms, {time.time()-t0:.0f}s)",
                flush=True,
            )
    if mgr:
        mgr.maybe_save(args.steps, {"params": params, "opt": state}, force=True)
        mgr.wait()
    return params


def train_lm(cfg, args):
    from repro.models.families import get_family_api
    from repro.train.step import make_train_step

    api = get_family_api(cfg)
    step_raw = make_train_step(
        cfg, peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps, policy=_policy_override(cfg, args),
    )
    step_fn = jax.jit(step_raw, donate_argnums=(0, 1))
    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) if args.ckpt_dir else None
    mon = StragglerMonitor()

    def make_state():
        params = api["init"](jax.random.PRNGKey(args.seed), cfg)
        return {"params": params, "opt": adamw_init(params)}

    def loop(state, start_step):
        stream = Prefetcher(
            token_stream(args.seed, args.batch, args.seq, cfg.vocab_size, start_step=start_step)
        )
        t0 = time.time()
        params, opt = state["params"], state["opt"]
        for step, batch in stream:
            if step >= args.steps:
                break
            if cfg.family == "encdec":
                batch = dict(batch)
                batch["enc_embeds"] = jnp.zeros((args.batch, args.seq, cfg.d_model), cfg.dtype)
            if cfg.family == "vlm":
                batch = dict(batch)
                batch["patch_embeds"] = jnp.zeros(
                    (args.batch, cfg.n_patches, cfg.d_model), cfg.dtype
                )
            mon.step_start()
            params, opt, metrics = step_fn(params, opt, batch)
            dt = mon.step_end(step)
            if mgr:
                mgr.maybe_save(step + 1, {"params": params, "opt": opt})
            if step % args.log_every == 0 or step == args.steps - 1:
                print(
                    f"step {step}: loss={float(metrics['loss']):.4f} "
                    f"lr={float(metrics['lr']):.2e} ({dt*1e3:.0f}ms, {time.time()-t0:.0f}s)",
                    flush=True,
                )
        return {"params": params, "opt": opt}, args.steps

    if mgr:
        state, last, n_restarts = run_with_restarts(make_state, loop, ckpt_manager=mgr)
        mgr.maybe_save(last, state, force=True)
        mgr.wait()
    else:
        state, _ = loop(make_state(), 0)
    if mon.events:
        print(f"stragglers detected: {len(mon.events)}")
    return state


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant", default=None, choices=["none", "sc_w16a16", "sc_w8a8"],
                    help="override the config's quant mode (ExecutionPolicy)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if getattr(cfg, "family", None) == "pointcloud" or args.arch.startswith("pointnet2"):
        train_pointcloud(cfg, args)
    else:
        train_lm(cfg, args)


if __name__ == "__main__":
    main()
