"""Serving launcher: continuous-batching engine, optionally self-tuning.

  # fixed setting (engine, max_batch=4):
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --reduced \
      --batch 4

  # self-tuning under a Poisson workload (the paper's online loop applied
  # to inference traffic):
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --reduced \
      --selftune

Every decode-capable family runs the engine: attention archs (dense / moe /
vlm) through the paged KV pool (block tables + copy-on-write prefix
sharing), ssm / hybrid archs through the recurrent state pool — one
StatePool interface, no legacy fallback.  Encoder-only archs have no decode
step and are rejected.
"""
from __future__ import annotations

import argparse
import json
import time

import jax


def _engine_main(args, cfg, params):
    from repro.core.tuner import TunerConfig, TuningManager
    from repro.obs import Tracer, write_audit_jsonl, write_chrome_trace
    from repro.obs.report import format_attribution, time_attribution
    from repro.serving import (DEFAULT_SERVING_SETTING,
                               SERVING_RELAYOUT_KNOBS, ServingEngine,
                               ServingObjective, serve_loop,
                               serving_knob_space)
    from repro.serving.workload import make_trace

    if args.prompt_len + args.gen > args.max_seq:
        raise SystemExit(f"--prompt-len + --gen ({args.prompt_len}+{args.gen})"
                         f" must fit in --max-seq ({args.max_seq})")
    trace_kw = {"prompt_lens": (4, args.prompt_len),
                "max_news": (4, args.gen)}
    max_prompt = args.prompt_len
    cap = args.max_seq - args.gen
    if args.scenario == "mixed_lengths":
        # the long mode has its own prompt-length range; cap it so every
        # generated request fits the sequence capacity
        trace_kw["long_lens"] = (min(32, cap), min(56, cap))
        max_prompt = max(max_prompt, trace_kw["long_lens"][1])
    elif args.scenario == "long_prompt":
        trace_kw["prompt_lens"] = (min(40, cap - 1), min(68, cap))
        max_prompt = max(max_prompt, trace_kw["prompt_lens"][1])
    elif args.scenario == "shared_prefix":
        trace_kw["prefix_len"] = min(32, max(cap - 8, 1))
        max_prompt = max(max_prompt, trace_kw["prefix_len"] + 8)
    space = serving_knob_space(max_batch_ceiling=max(8, args.batch),
                               include_batches=(args.batch,),
                               family=cfg.family)
    setting = dict(DEFAULT_SERVING_SETTING, max_batch=args.batch)
    engine = ServingEngine(params, cfg, setting, max_seq=args.max_seq)
    if not args.cold:
        t0 = time.perf_counter()
        # fixed mode never leaves its setting — warm only its executables
        engine.warm_start(space if args.selftune else None,
                          max_prompt=max_prompt)
        print(f"warm-start: {len(engine._steps)} executables in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    trace = make_trace(args.scenario, args.rate, args.duration,
                       vocab=cfg.vocab_size, seed=args.seed, **trace_kw)
    # fleet knowledge store: compute this run's signature, seed the start
    # setting from the golden table (nearest match wins), and hand the
    # store to the tuner so the GP warm-starts from prior posteriors and
    # flushes what it learns back
    store = sig = None
    if args.tuning_store and args.selftune:
        from repro.store import TuningStore, lookup, signature_from_trace
        store = TuningStore(args.tuning_store)
        sig = signature_from_trace(cfg, engine.pool.kind, args.max_seq,
                                   trace, args.duration)
        entry, gkey, gtier = (lookup(store.build_golden(), sig)
                              if store.read_records(kinds=("obs",))
                              else (None, None, None))
        if entry is not None:
            golden = {k: tuple(v) if isinstance(v, list) else v
                      for k, v in entry["incumbent"]["setting"].items()}
            setting = dict(setting, **golden)
            engine.reconfigure(setting)
            print(f"tuning-store: golden incumbent {golden} "
                  f"({gtier} match, {entry['n_obs']} obs) -> start setting",
                  flush=True)
        else:
            print(f"tuning-store: no golden entry for {sig.key}", flush=True)
    # attach the tracer after warm-start so the attribution panel covers
    # the serving run, not startup compilation (a --cold run still shows
    # its compiles: they fire inside ticks/reconfig windows as exec.build)
    tracer = None
    if args.trace:
        tracer = Tracer()
        engine.set_tracer(tracer)
    tuner = None
    if args.selftune:
        tuner = TuningManager(
            space, setting,
            TunerConfig(eps=1e-6, a=args.window, b=args.init_settings,
                        seed=args.seed, drift_z=args.drift_z,
                        window_time_s=2.0,
                        # cost-aware acquisition with the horizon derived
                        # online from observed drift intervals (20s is the
                        # pre-evidence fallback)
                        amortize_horizon_s=20.0, adapt_horizon=True),
            objective=ServingObjective(engine, slo_p99_s=args.slo),
            reconfig_knob_classes={"mesh_knobs": SERVING_RELAYOUT_KNOBS},
            tracer=tracer, store=store, signature=sig)
        if tuner.warm_start_info is not None:
            ws = tuner.warm_start_info
            print(f"tuning-store: warm-start absorbed {ws['absorbed_obs']} "
                  f"obs (tier={ws['tier']}, skipped "
                  f"{ws['init_settings_skipped']} init settings"
                  f"{', READ-ONLY' if ws['read_only'] else ''})", flush=True)

    mode = "selftune" if args.selftune else f"fixed(max_batch={args.batch})"
    print(f"arch={cfg.name} family={cfg.family} pool={engine.pool.kind} "
          f"scenario={args.scenario} rate={args.rate}rps "
          f"duration={args.duration}s mode={mode}")
    stats = serve_loop(engine, trace, tuner, verbose=True)
    print(f"served {stats['completed']}/{stats['requests']} requests, "
          f"{stats['tokens']} tokens in {stats['wall_s']:.1f}s "
          f"({stats['tokens_per_s']:.1f} tok/s)")
    if stats["p50_latency_s"] is not None:
        print(f"latency p50={stats['p50_latency_s']:.2f}s "
              f"p99={stats['p99_latency_s']:.2f}s "
              f"ttft p50={stats['p50_ttft_s']:.2f}s")
    if stats["prefill_tokens_total"]:
        saved = (stats["prefill_tokens_total"]
                 - stats["prefill_tokens_computed"])
        print(f"prefill: {stats['prefill_tokens_computed']}/"
              f"{stats['prefill_tokens_total']} tokens computed "
              f"({saved} shared, {stats['cow_copies']} COW copies)")
    if args.selftune:
        print(f"reconfigurations: {stats['reconfig_count']} "
              f"({stats['reconfig_total_s']:.2f}s total), "
              f"final setting: {stats['final_setting']}")
    if store is not None and tuner is not None:
        # release the shared lock, fold this run's segment in, refresh the
        # golden table — the next process warm-starts from all of it
        tuner.close_store()
        compacted = store.compact()
        table = store.write_golden()
        print(f"tuning-store: {len(table['entries'])} golden entries -> "
              f"{store.golden_path}"
              f"{'' if compacted else ' (compaction skipped: store busy)'}",
              flush=True)
    if tracer is not None:
        audit = tuner.audit if tuner is not None else None
        attr = time_attribution(tracer, stats["wall_s"], audit=audit)
        stats["time_attribution"] = attr
        print(format_attribution(attr), flush=True)
        n_ev = write_chrome_trace(args.trace, tracer,
                                  process_name=f"serve:{cfg.name}")
        print(f"trace: {n_ev} events -> {args.trace} "
              f"(load in https://ui.perfetto.dev)", flush=True)
        if audit is not None and audit.records:
            audit_path = args.trace + ".audit.jsonl"
            n_rec = write_audit_jsonl(audit_path, audit)
            print(f"tuning audit: {n_rec} records -> {audit_path}",
                  flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(stats, f, indent=1, default=str)
    print("OK", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed max_batch ceiling")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    # engine / self-tuning
    ap.add_argument("--selftune", action="store_true",
                    help="tune serving knobs online while serving")
    ap.add_argument("--scenario", default="poisson",
                    choices=("poisson", "bursty", "diurnal", "mixed_lengths",
                             "shared_prefix", "long_prompt"),
                    help="traffic shape")
    ap.add_argument("--rate", type=float, default=40.0,
                    help="mean request arrival rate (req/s)")
    ap.add_argument("--duration", type=float, default=4.0,
                    help="length of the arrival window (s)")
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--window", type=int, default=40,
                    help="tuner iterations per setting window (a)")
    ap.add_argument("--init-settings", type=int, default=5,
                    help="random settings in the tuner init phase (b)")
    ap.add_argument("--slo", type=float, default=3.0,
                    help="p99 latency SLO (s) for the serving objective")
    ap.add_argument("--tuning-store", default=None, metavar="DIR",
                    help="fleet tuning knowledge store directory: with "
                         "--selftune, seed the start setting from its "
                         "golden table, warm-start the BO from the nearest "
                         "signature's history, and persist this run's "
                         "observations/decisions back")
    ap.add_argument("--drift-z", type=float, default=3.0,
                    help="load-drift z-score threshold (0 disables the "
                         "EWMA re-search trigger)")
    ap.add_argument("--cold", action="store_true",
                    help="skip the startup executable warm-up (reconfig "
                         "costs then include cold XLA compiles)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of the run, plus PATH.audit.jsonl with "
                         "the tuner's decision/reconfig audit when "
                         "--selftune is on")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    from repro.configs.registry import get_config
    from repro.launch.cache import enable_compile_cache
    from repro.models import lm

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    _engine_main(args, cfg, params)


if __name__ == "__main__":
    main()
