"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b --reduced \
      --steps 300 --batch 8 --seq 128 [--self-tune] [--ckpt-dir DIR] [--resume]

Runs real training on the local devices (reduced configs on CPU; full configs
belong on real pods — their distribution plan is what the dry-run validates).
``--self-tune`` turns on the paper's online tuner; otherwise the default
setting runs fixed. Checkpoints every ``--ckpt-every`` steps; ``--resume``
restarts from the latest checkpoint (fault-tolerance path).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eps", type=float, default=0.05,
                    help="convergence threshold on CE loss")
    ap.add_argument("--self-tune", action="store_true")
    ap.add_argument("--tuner-a", type=int, default=8)
    ap.add_argument("--tuner-b", type=int, default=6)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of the run")
    args = ap.parse_args()

    from repro.checkpoint import CheckpointManager
    from repro.configs.registry import get_config
    from repro.core.tuner import TunerConfig, TuningManager
    from repro.obs import NOP_TRACER, Tracer, write_chrome_trace
    from repro.obs.report import format_attribution, time_attribution
    from repro.ps.lm_job import (DEFAULT_LM_SETTING, LMJob, lm_knob_space)
    from repro.launch.cache import enable_compile_cache
    from repro.ps.trainer import SelfTuningLoop

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    job = LMJob(cfg, batch=args.batch, seq=args.seq, seed=args.seed)
    job.eps = args.eps
    print(f"arch={cfg.name} params={cfg.n_params():,} devices="
          f"{len(jax.devices())}", flush=True)

    ckpt = (CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
            if args.ckpt_dir else None)
    setting = dict(DEFAULT_LM_SETTING)
    state = job.init_state(setting, args.seed)
    if args.resume and ckpt is not None:
        try:
            template = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            state, meta = ckpt.restore_latest(template)
            print(f"resumed from step {meta['step']}", flush=True)
        except FileNotFoundError:
            print("no checkpoint found; starting fresh", flush=True)

    tracer = Tracer() if args.trace else None
    t_run0 = time.perf_counter()
    if args.self_tune:
        space = lm_knob_space(len(jax.devices()))
        tuner = TuningManager(space, setting, TunerConfig(
            eps=args.eps, a=args.tuner_a, b=args.tuner_b, seed=args.seed))
        loop = SelfTuningLoop(tuner, job.step_builder, job.state_adapter,
                              checkpoint_manager=ckpt, tracer=tracer)
        res, state = loop.run(state, job.batches(args.seed),
                              max_iters=args.steps, verbose=True)
        print(f"done: iters={res.iterations} wall={res.wall_time_s:.1f}s "
              f"loss={res.final_loss:.4f} converged={res.converged} "
              f"reconfig_s={res.reconfig_total_s:.1f}", flush=True)
        print(f"final setting: {tuner.current}", flush=True)
        rep = tuner.progress_report()
        print(f"progress indicator: remaining ~{rep['remaining_iters']:.0f} "
              f"iters / {rep['remaining_time_s']:.1f}s", flush=True)
    else:
        tr = tracer or NOP_TRACER
        step = jax.jit(job.step_builder(setting))
        bi = job.batches(args.seed)
        losses = []
        t0 = time.perf_counter()
        for it in range(1, args.steps + 1):
            with tr.span("train.step", it=it):
                state, m = step(state, next(bi))
                losses.append(float(m["loss"]))
            if ckpt is not None:
                ckpt.maybe_save(state, it, {"loss": losses[-1]})
            if it % 20 == 0:
                print(f"[{it}] loss={np.mean(losses[-20:]):.4f} "
                      f"({(time.perf_counter()-t0)/it*1000:.0f} ms/it)",
                      flush=True)
            if np.mean(losses[-8:]) <= args.eps and len(losses) >= 8:
                print("converged", flush=True)
                break
    if tracer is not None:
        wall = time.perf_counter() - t_run0
        audit = tuner.audit if args.self_tune else None
        attr = time_attribution(tracer, wall, audit=audit,
                                extra_keys=("train_step",))
        print(format_attribution(attr), flush=True)
        n_ev = write_chrome_trace(args.trace, tracer,
                                  process_name=f"train:{cfg.name}")
        print(f"trace: {n_ev} events -> {args.trace} "
              f"(load in https://ui.perfetto.dev)", flush=True)
    print("OK", flush=True)


if __name__ == "__main__":
    main()
