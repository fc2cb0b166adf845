"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

Features exercised: model factory, sharded train step (when a mesh is
requested), deterministic resumable data pipeline, async atomic
checkpoints, SIGTERM clean exit, watchdog, restart/resume.
"""
import argparse
import dataclasses
import sys
import time

import jax

from repro.configs import get_arch
from repro.data.pipeline import DataConfig, DataState, Pipeline
from repro.dist.context import no_dist
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import Watchdog, install_preemption_handler
from repro.train.loop import init_train_state, jit_train_step
from repro.train.optimizer import OptConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-order", type=int, default=2,
                    help="synthetic-data dependency distance (1 = easiest)")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="override layer count (0 = config value)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{cfg.n_layers} layers")
    model = build_model(cfg, no_dist())
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                        total_steps=args.steps)
    step_fn = jit_train_step(model, opt_cfg, grad_accum=args.grad_accum)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      synthetic_order=args.data_order)
    pipe = Pipeline(dcfg)
    state = init_train_state(model, jax.random.key(args.seed), opt_cfg)
    start_step = 0

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)
        if ckpt.latest_step() is not None:
            abstract = jax.eval_shape(lambda: state)
            state, meta = ckpt.restore(abstract)
            state = jax.tree_util.tree_map(jax.numpy.asarray, state)
            start_step = meta["step"]
            pipe.state = DataState.from_dict(meta.get("data", {}))
            print(f"[train] resumed from step {start_step}")

        def on_preempt():
            ckpt.async_save = False
            ckpt.save(cur_step[0], state, {"data": pipe.state.to_dict()})
            print("[train] SIGTERM: checkpointed, exiting")
            sys.exit(0)
        install_preemption_handler(on_preempt)

    cur_step = [start_step]
    wd = Watchdog()
    losses = []
    for step in range(start_step, args.steps):
        cur_step[0] = step
        batch = {k: jax.numpy.asarray(v) for k, v in pipe.next_batch().items()}
        if cfg.family == "audio":
            batch["frames"] = jax.numpy.zeros(
                (args.batch, cfg.enc_dec.n_frames, cfg.d_model),
                jax.numpy.float32)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt_ = time.time() - t0
        trip = wd.observe(dt_)
        if trip:
            print(f"[watchdog] {trip} at step {step} ({dt_:.1f}s)")
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step} loss {loss:.4f} "
                  f"({dt_*1e3:.0f} ms/step)", flush=True)
        if ckpt and step > 0 and step % args.ckpt_every == 0:
            ckpt.save(step, state, {"data": pipe.state.to_dict()})
    if ckpt:
        ckpt.async_save = False
        ckpt.save(args.steps, state, {"data": pipe.state.to_dict()})
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
