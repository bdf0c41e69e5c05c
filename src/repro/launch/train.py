"""End-to-end training driver with Truffle cold-start overlap.

The training job is treated exactly like a paper-§IV function: its cold start
β = (worker provisioning ν, simulated) + (XLA compile η, REAL), and Truffle
overlaps that window with (a) SDP prefetch of the first data batches from the
object store and (b) streaming the checkpoint bytes for restore. Baseline
mode runs the same phases sequentially (state-of-the-art lifecycle, Fig. 2).

Fault tolerance: ``--inject-failure K`` raises at step K; the outer loop
restarts the job (new incarnation -> new cold start, again overlapped) and
resumes from the latest complete checkpoint. ``--elastic`` restarts onto a
different microbatch split to emulate losing part of the DP group.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-every 5 --inject-failure 12
"""
from __future__ import annotations

import argparse
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config
from repro.core.buffer import Buffer
from repro.data.pipeline import TokenDataset, TruffleDataLoader
from repro.distributed.sharding import rules_for_shape
from repro.launch.compile_cache import use_compilation_cache
from repro.launch.mesh import host_device_mesh
from repro.launch.steps import build_train_step, concrete_train_state
from repro.optim.adamw import OptConfig
from repro.runtime.clock import Clock
from repro.storage.base import make_object_store


class SimulatedFailure(RuntimeError):
    pass


def run_incarnation(args, incarnation: int, clock: Clock) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.vision is not None or cfg.encoder is not None:
        raise SystemExit("train driver targets LM archs; use examples/ for others")
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = host_device_mesh(1, 1)
    microbatch = args.microbatch * (2 if (args.elastic and incarnation > 0) else 1)

    storage = make_object_store(clock)
    dataset = TokenDataset(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    loader = TruffleDataLoader(dataset, storage, prefetch_depth=2)
    ckpt = CheckpointManager(args.ckpt_dir)
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps)

    train_step, (state_sds, batch_sds) = build_train_step(
        cfg, mesh, shape, opt_cfg=opt_cfg, microbatch=microbatch)

    t0 = time.monotonic()
    compiled_box, ckpt_box = {}, {}
    ckpt_buffer = Buffer(name="ckpt-buffer")

    def cold_start():  # η: the real XLA compile
        clock.sleep(args.provision_s)  # ν: worker provisioning (simulated)
        with jax.set_mesh(mesh):
            compiled_box["exe"] = jax.jit(train_step).lower(
                state_sds, batch_sds).compile()

    def fetch_ckpt():  # CSP-style: stream restore bytes during cold start
        step = ckpt.latest_step()
        if step is not None:
            ckpt_box["bytes"] = None  # manifest path restore (local disk here)
            ckpt_box["step"] = step

    if args.truffle:
        threads = [threading.Thread(target=cold_start),
                   threading.Thread(target=fetch_ckpt)]
        for th in threads:
            th.start()
        loader.start_prefetch()               # SDP: batches flow during compile
        for th in threads:
            th.join()
    else:  # sequential lifecycle
        cold_start()
        fetch_ckpt()
        loader.start_prefetch()

    exe = compiled_box["exe"]
    with jax.set_mesh(mesh):
        state = concrete_train_state(cfg, mesh, rules_for_shape("train"),
                                     jax.random.PRNGKey(args.seed))
        start_step = 0
        if "step" in ckpt_box:
            state, start_step = ckpt.restore(state, ckpt_box["step"])
            state = jax.tree.map(jnp.asarray, state)
            print(f"[inc {incarnation}] resumed from step {start_step}")

    losses, t_first = [], None
    for step in range(start_step, args.steps):
        batch = loader.get(step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = exe(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if t_first is None:
            t_first = time.monotonic() - t0
        if args.inject_failure == step and incarnation == 0:
            loader.stop()
            raise SimulatedFailure(f"injected node failure at step {step}")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, state)
        if step % args.log_every == 0:
            print(f"[inc {incarnation}] step {step} loss {loss:.4f}")
    ckpt.wait()
    loader.stop()
    assert all(np.isfinite(losses)), "NaN/inf loss"
    return {"time_to_first_step": t_first, "losses": losses,
            "final_step": args.steps, "incarnation": incarnation}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--truffle", action="store_true", default=True)
    ap.add_argument("--no-truffle", dest="truffle", action="store_false")
    ap.add_argument("--provision-s", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--inject-failure", type=int, default=-1)
    ap.add_argument("--elastic", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--time-scale", type=float, default=1.0)
    args = ap.parse_args(argv)

    use_compilation_cache()
    clock = Clock(args.time_scale)
    incarnation = 0
    while True:
        try:
            out = run_incarnation(args, incarnation, clock)
            break
        except SimulatedFailure as e:
            print(f"!! {e} — restarting (checkpoint/restart path)")
            incarnation += 1
            if incarnation > 3:
                raise
    print(f"done: time_to_first_step={out['time_to_first_step']:.2f}s "
          f"final_loss={out['losses'][-1]:.4f} "
          f"loss_drop={out['losses'][0] - out['losses'][-1]:.4f}")
    return out


if __name__ == "__main__":
    main()
