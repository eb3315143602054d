"""Self-tuned vs fixed-default serving under diverse traffic shapes.

Protocol: for each scenario the same arrival trace is replayed twice —
once with the serving knobs frozen at the pre-engine default (one request
at a time, f32 KV, no sharing), once with the TuningManager +
ServingObjective tuning the knobs online while serving.  The offered load
is calibrated against the machine's measured single-slot service rate so
the fixed default is genuinely overloaded (the regime the north-star cares
about) on any host.  The ``shared_prefix`` scenario adds a sharing
ablation: the paged pool with prefix sharing on vs off at the same fixed
setting, isolating the copy-on-write block reuse from the tuner.  Every
scenario also runs a paged-attention kernel ablation (decode attention
reading KV blocks in place vs the pre-kernel dense-gather path, same
traffic, same fixed setting), and the report carries a decode-step
microbench plus a modeled roofline entry for the kernel.

  PYTHONPATH=src python benchmarks/bench_serving.py [--smoke | --ci]

Writes artifacts/bench/BENCH_serving.json (per-scenario tokens/s, p50/p99
latency, reconfiguration count, prefill-sharing counters, tokens-over-time
trajectory).  ``--ci`` runs one tiny fixed-seed scenario and asserts the
tuned engine completes and emits a well-formed report (the scripts/ci.sh
bit-rot gate); it writes BENCH_serving_smoke.json so the canonical
artifact only ever comes from full runs.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from common import save_artifact

SCENARIO_NAMES = ("poisson", "bursty", "diurnal", "shared_prefix",
                  "long_prompt")
REPORT_KEYS = ("requests", "completed", "tokens", "tokens_per_s",
               "p50_latency_s", "p99_latency_s", "reconfig_count",
               "final_setting", "prefill_tokens_computed",
               "prefill_tokens_total", "decode_tok_per_s")


def make_warm_engine(params, cfg, max_seq, max_prompt):
    """One engine for every arm and scenario: all executables the knob space
    can reach are AOT-compiled up front (server startup warmup), so the
    fixed-vs-tuned comparison isolates the *policy*, not compile luck."""
    from repro.serving import (DEFAULT_SERVING_SETTING, ServingEngine,
                               serving_knob_space)
    engine = ServingEngine(params, cfg, DEFAULT_SERVING_SETTING,
                           max_seq=max_seq)
    engine.warm_start(serving_knob_space(family=cfg.family),
                      max_prompt=max_prompt)
    return engine


def calibrate_service_rate(engine, cfg) -> float:
    """Measured warm tok/s of the fixed default (max_batch=1) on this host."""
    from repro.serving import Request, serve_loop
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (12,))
                    .astype(np.int32),
                    max_new=16, arrival_s=0.0) for i in range(8)]
    return serve_loop(engine, reqs)["tokens_per_s"]


def run_scenario(name, engine, cfg, rate, duration, seed,
                 tuner_a, tuner_b, slo, trace_dir=None, store=None):
    from repro.core.tuner import TunerConfig, TuningManager
    from repro.obs import NOP_TRACER, Tracer, write_chrome_trace
    from repro.obs.report import time_attribution
    from repro.serving import (DEFAULT_SERVING_SETTING,
                               SERVING_RELAYOUT_KNOBS, ServingObjective,
                               serve_loop, serving_knob_space)
    from repro.serving.workload import make_trace

    def trace():
        return make_trace(name, rate, duration, vocab=cfg.vocab_size,
                          seed=seed)

    out = {"rate_rps": rate, "duration_s": duration,
           "n_requests": len(trace())}

    def make_tuner(tracer, absorb, sig, x0=None):
        return TuningManager(
            serving_knob_space(family=cfg.family),
            x0 or DEFAULT_SERVING_SETTING,
            TunerConfig(eps=1e-6, a=tuner_a, b=tuner_b, seed=seed,
                        min_ei_seconds=0.5, ei_rel_threshold=0.1,
                        # heavy-tick traffic (long prompts) must not stretch
                        # the init phase past the workload: cap windows by
                        # time.  Generous cap — windows that close with only
                        # a handful of quanta give the GP hopelessly noisy Y
                        # and the tuner thrashes
                        window_time_s=2.0,
                        # cost-aware acquisition: a candidate must amortize
                        # its predicted switch cost within the horizon or be
                        # pruned before the GP argmax; the horizon itself is
                        # derived online from observed drift intervals (20s
                        # stands in until the first drift)
                        amortize_horizon_s=20.0, adapt_horizon=True),
            objective=ServingObjective(engine, slo_p99_s=slo),
            reconfig_knob_classes={"mesh_knobs": SERVING_RELAYOUT_KNOBS},
            tracer=tracer, store=store, signature=sig,
            absorb_history=absorb)

    sig = None
    if store is not None:
        from repro.store import signature_from_trace
        sig = signature_from_trace(cfg, engine.pool.kind, engine.max_seq,
                                   trace(), duration)

    # every arm starts from the default setting AND a cold prefix cache —
    # one arm's prefills must never serve another arm's admissions.  Each
    # arm gets its own tracer so the time-attribution panel decomposes the
    # arms separately (self-times: nested spans never double-count).
    # Drafters reset alongside, reseeded from the scenario seed: n-gram
    # lookup tables must not leak across arms, and the RNG fallback must
    # be deterministic per run (bit-identical speculation panels).
    engine.reconfigure(DEFAULT_SERVING_SETTING)
    engine.pool.reset_prefix_cache()
    engine.reset_drafters(seed)
    tr_fx = Tracer()
    engine.set_tracer(tr_fx)
    out["fixed_default"] = serve_loop(engine, trace())
    engine.set_tracer(NOP_TRACER)    # the reset below isn't this arm's time

    engine.reconfigure(DEFAULT_SERVING_SETTING)
    engine.pool.reset_prefix_cache()
    engine.reset_drafters(seed)
    tr_tn = Tracer()
    engine.set_tracer(tr_tn)
    # tuned-cold: LHS-from-scratch; with a store attached it records its
    # observations (but absorbs nothing) so the warm arm below — and any
    # later bench run — can warm-start from them
    tuner = make_tuner(tr_tn, absorb=False, sig=sig)
    out["self_tuned"] = serve_loop(engine, trace(), tuner)
    out["self_tuned"]["tuner_windows"] = len(tuner.history)
    out["self_tuned"]["drift_events"] = len(tuner.drift_events)
    tuner.close_store()
    engine.set_tracer(NOP_TRACER)       # ablations below run untraced

    out["time_attribution"] = {
        "fixed_default": time_attribution(
            tr_fx, out["fixed_default"]["wall_s"]),
        "self_tuned": time_attribution(
            tr_tn, out["self_tuned"]["wall_s"], audit=tuner.audit),
    }

    # speculation panel: the tuned arm's drafted/accepted counters plus
    # the spec_k the tuner's incumbent actually landed on — the
    # workload-sensitivity evidence (prompt-lookup thrives on
    # shared_prefix traffic, buys nothing on bursty random traffic)
    out["speculation"] = dict(out["self_tuned"]["speculation"])
    out["speculation"]["spec_k_selected"] = engine._spec_k_of(
        out["self_tuned"]["final_setting"])

    if store is not None:
        # tuned-warm third arm: same trace, same tuner config, but the BO
        # is seeded from the store (the cold arm's observations at minimum)
        # and the start setting comes from the golden table — the
        # fleet-amortization claim, measured
        from repro.store import lookup
        entry, gkey, gtier = lookup(store.build_golden(), sig)
        x0 = dict(DEFAULT_SERVING_SETTING)
        if entry is not None:
            x0.update(entry["incumbent"]["setting"])
        engine.reconfigure(x0)
        engine.pool.reset_prefix_cache()
        engine.reset_drafters(seed)
        tr_wm = Tracer()
        engine.set_tracer(tr_wm)
        tuner_w = make_tuner(tr_wm, absorb=True, sig=sig, x0=x0)
        out["self_tuned_warm"] = serve_loop(engine, trace(), tuner_w)
        out["self_tuned_warm"]["tuner_windows"] = len(tuner_w.history)
        out["self_tuned_warm"]["drift_events"] = len(tuner_w.drift_events)
        tuner_w.close_store()
        engine.set_tracer(NOP_TRACER)
        out["time_attribution"]["self_tuned_warm"] = time_attribution(
            tr_wm, out["self_tuned_warm"]["wall_s"], audit=tuner_w.audit)
        cold, warm = out["self_tuned"], out["self_tuned_warm"]
        attr_c = out["time_attribution"]["self_tuned"]
        attr_w = out["time_attribution"]["self_tuned_warm"]
        out["warm_start_gain"] = {
            "store_key": sig.key,
            "golden_matched_key": gkey, "golden_tier": gtier,
            "golden_incumbent": (dict(entry["incumbent"]["setting"])
                                 if entry else None),
            "absorbed_obs": warm["warm_start"]["absorbed_obs"],
            "init_quanta_cold": cold["tuner_init_quanta"],
            "init_quanta_warm": warm["tuner_init_quanta"],
            "init_time_s_cold": cold["tuner_init_time_s"],
            "init_time_s_warm": warm["tuner_init_time_s"],
            "init_quanta_halved": (2 * warm["tuner_init_quanta"]
                                   <= cold["tuner_init_quanta"]),
            "tokens_per_s_cold": cold["tokens_per_s"],
            "tokens_per_s_warm": warm["tokens_per_s"],
            "gain": (warm["tokens_per_s"]
                     / max(cold["tokens_per_s"], 1e-9)),
            "warm_wins": warm["tokens_per_s"] >= cold["tokens_per_s"],
            # where the saved init quanta went: the tuner/decode split of
            # each arm's attribution panel
            "tuner_fraction_cold": attr_c["fractions"]["tuner"],
            "tuner_fraction_warm": attr_w["fractions"]["tuner"],
            "decode_fraction_cold": attr_c["fractions"]["decode"],
            "decode_fraction_warm": attr_w["fractions"]["decode"],
        }
    if trace_dir is not None:
        import os
        path = os.path.join(trace_dir, f"trace_{name}.json")
        write_chrome_trace(path, tr_tn, process_name=f"bench:{name}:tuned")

    if name == "shared_prefix":
        # sharing ablation at one fixed batched setting: same paged pool,
        # prefix sharing on vs off — the COW block reuse, isolated
        base = dict(DEFAULT_SERVING_SETTING, max_batch=4)
        abl = {}
        for label, share in (("share_off", False), ("share_on", True)):
            engine.reconfigure(dict(base, prefix_share=share))
            engine.pool.reset_prefix_cache()
            engine.reset_drafters(seed)
            st = serve_loop(engine, trace())
            abl[label] = {k: st[k] for k in REPORT_KEYS}
            abl[label]["shared_blocks_hit"] = st["shared_blocks_hit"]
            abl[label]["cow_copies"] = st["cow_copies"]
            abl[label]["prefill_per_request"] = (
                st["prefill_tokens_computed"] / max(st["completed"], 1))
        abl["prefill_reduction"] = (
            1.0 - abl["share_on"]["prefill_per_request"]
            / max(abl["share_off"]["prefill_per_request"], 1e-9))
        out["sharing_ablation"] = abl

    if engine.pool.kind == "paged":
        # paged-attention kernel ablation: identical requests through one
        # fixed batched setting, only the decode attention implementation
        # differs — "gather" (pre-kernel: materialize the block table as a
        # dense cache, attend over the full width) vs "paged" (read KV
        # blocks in place through the table, context-bucketed).  The arms
        # replay the scenario's requests *closed-loop* (all queued at
        # t=0): with timed arrivals an engine that keeps up reports
        # tokens/s == offered rate regardless of decode speed; closed-loop
        # tokens/s is engine *capacity*, which is what the kernel changes.
        # Methodology for a noisy shared host: 7 replays, each replay runs
        # both arms back-to-back (order alternating — a drifting host
        # penalizes whichever arm runs second), the headline speedup is
        # the *median of per-replay paired ratios* of decode-only
        # throughput, and Python GC is disabled inside the timed replays
        # (collector pauses otherwise land randomly inside ~0.5 ms decode
        # windows).  Decode-only throughput is the right numerator: it is
        # what the kernel changes; end-to-end tokens/s (also recorded)
        # folds in identical prefill work and queueing noise.
        import gc

        from repro.serving import Request
        base = dict(DEFAULT_SERVING_SETTING, max_batch=4)
        abl = {}
        arng = np.random.default_rng(seed + 1)

        def closed():
            reqs = trace()
            for r in reqs:
                r.arrival_s = 0.0
            return reqs

        runs = {"gather": [], "paged": []}
        ratios = []
        for rep in range(7):
            order = (("gather", "paged") if rep % 2 == 0
                     else ("paged", "gather"))
            pair = {}
            for impl in order:
                engine.reconfigure(base)
                engine.set_attn_impl(impl)      # warm Type II swap
                engine.pool.reset_prefix_cache()
                engine.reset_drafters(seed)
                if rep == 0:
                    # rehearsal: absorb first-call dispatch overheads so
                    # the first measured arm isn't penalized by arm order
                    serve_loop(engine, [Request(rid=-1 - i,
                                                prompt=arng.integers(
                                                    0, cfg.vocab_size, (12,))
                                                .astype(np.int32),
                                                max_new=8)
                                        for i in range(6)])
                    engine.pool.reset_prefix_cache()
                gc.collect()
                gc.disable()
                try:
                    pair[impl] = serve_loop(engine, closed())
                finally:
                    gc.enable()
                runs[impl].append(pair[impl])
            ratios.append(pair["paged"]["decode_tok_per_s"]
                          / max(pair["gather"]["decode_tok_per_s"], 1e-9))
        engine.set_attn_impl("paged")
        mid = len(ratios) // 2
        for impl, sts in runs.items():
            st = sorted(sts, key=lambda s: s["decode_tok_per_s"])[mid]
            abl[impl] = {k: st[k] for k in REPORT_KEYS}       # median run
            abl[impl]["decode_tok_per_s_runs"] = [
                round(s["decode_tok_per_s"], 1) for s in sts]
        abl["decode_speedup_runs"] = [round(r, 3) for r in sorted(ratios)]
        abl["speedup"] = abl["decode_speedup_runs"][mid]      # paired median
        abl["e2e_speedup"] = (abl["paged"]["tokens_per_s"]
                              / max(abl["gather"]["tokens_per_s"], 1e-9))
        abl["paged_no_slower"] = abl["speedup"] >= 0.98
        out["kernel_ablation"] = abl

    fx, tn = out["fixed_default"], out["self_tuned"]
    out["speedup"] = tn["tokens_per_s"] / max(fx["tokens_per_s"], 1e-9)
    out["tuned_wins"] = tn["tokens_per_s"] >= fx["tokens_per_s"]
    return out


def decode_step_microbench(params, cfg, max_seq, reps=150):
    """Median decode-step latency, gather vs paged, at three context
    depths (the deterministic companion to the end-to-end ablation: same
    executable shapes the engine runs, no traffic noise)."""
    import jax.numpy as jnp

    from repro.models import lm
    from repro.models.lm import ModelKnobs

    bs, n_slots = 16, 4
    mb = -(-max_seq // bs)
    nb = n_slots * mb + 1
    shapes = lm.init_paged_cache_shapes(cfg, nb, bs)
    cache = {k: jnp.zeros(s.shape, jnp.float32) for k, s in shapes.items()}
    cache["block_tables"] = jnp.asarray(
        np.arange(n_slots * mb).reshape(n_slots, mb) % (nb - 1) + 1,
        jnp.int32)
    tok = jnp.zeros((n_slots, 1), jnp.int32)
    out = {"block_size": bs, "batch": n_slots, "contexts": {}}
    g_ctx = -(-mb // 3)
    for ctx in (12, max_seq // 2, max_seq - 6):
        pos = jnp.full((n_slots,), ctx, jnp.int32)
        row = {}
        execs = {}
        for impl in ("gather", "paged"):
            cols = (0 if impl == "gather"
                    else min(mb, g_ctx * (-(-(ctx // bs + 1) // g_ctx))))
            kn = ModelKnobs(attn_impl=impl, attn_ctx=cols)
            execs[impl] = jax.jit(
                lambda p, c, t, po, kn=kn:
                lm.decode_step(p, c, t, po, cfg, None, kn)
            ).lower(params, cache, tok, pos).compile()
            jax.block_until_ready(execs[impl](params, cache, tok, pos)[0])
        ts = {impl: [] for impl in execs}
        for r in range(10):                  # interleaved + alternating
            order = list(execs.items())      # order: cancels host drift
            if r % 2:
                order.reverse()
            for impl, f in order:
                t0 = time.perf_counter()
                for _ in range(reps // 10):
                    logits, _ = f(params, cache, tok, pos)
                jax.block_until_ready(logits)
                ts[impl].append((time.perf_counter() - t0)
                                / (reps // 10) * 1e6)
        for impl in execs:                   # min: noise-robust
            row[impl] = round(float(min(ts[impl])), 1)
        row["speedup"] = round(row["gather"] / max(row["paged"], 1e-9), 3)
        out["contexts"][f"ctx_{ctx}"] = row
    return out


def paged_attention_roofline(cfg, max_seq, bs, batch, ctx_tokens,
                             dtype_bytes=4):
    """Modeled per-decode-tick attention traffic and FLOPs, gather vs
    paged — the roofline-style justification recorded next to the
    measured ablation.  The gather path reads the full-table KV, writes a
    dense copy and reads it back; the paged path reads only live blocks,
    in place."""
    L, K, H, hd = cfg.n_layers, cfg.n_kv_heads, cfg.n_heads, cfg.hd
    mb = -(-max_seq // bs)
    row = K * hd * dtype_bytes
    full = mb * bs
    live = min(-(-ctx_tokens // bs) * bs, full)
    bytes_gather = L * batch * 2 * row * (full + full + full)
    bytes_paged = L * batch * 2 * row * live
    flops = lambda w: L * batch * 2 * (2 * H * hd * w)      # qk + pv
    return {
        "block_size": bs, "batch": batch, "ctx_tokens": ctx_tokens,
        "table_tokens": full, "live_tokens": live,
        "attn_bytes_gather": bytes_gather, "attn_bytes_paged": bytes_paged,
        "traffic_ratio": round(bytes_gather / max(bytes_paged, 1), 2),
        "attn_flops_gather": flops(full), "attn_flops_paged": flops(live),
        "dead_block_fraction": round(1.0 - live / full, 3),
    }


def check_report(results: dict, scenarios) -> None:
    """Well-formedness gate (the --ci contract): every scenario has both
    arms with the full metric set, a completed tuned run, and a
    well-formed time-attribution panel (non-empty spans, fractions that
    account for ~all of wall-clock)."""
    from repro.obs.report import FRACTION_KEYS
    for name in scenarios:
        r = results["scenarios"][name]
        for arm in ("fixed_default", "self_tuned"):
            missing = [k for k in REPORT_KEYS if k not in r[arm]]
            assert not missing, f"{name}/{arm} missing {missing}"
        assert r["self_tuned"]["completed"] == r["self_tuned"]["requests"], \
            f"{name}: tuned engine dropped requests"
        assert "time_attribution" in r, f"{name}: no time_attribution panel"
        for arm in ("fixed_default", "self_tuned"):
            attr = r["time_attribution"][arm]
            assert attr["span_counts"], f"{name}/{arm}: no spans recorded"
            missing = [k for k in FRACTION_KEYS
                       if k not in attr["fractions"]]
            assert not missing, \
                f"{name}/{arm}: attribution missing {missing}"
            assert abs(attr["fractions_sum"] - 1.0) < 0.02, \
                (f"{name}/{arm}: fractions sum to {attr['fractions_sum']}, "
                 f"not ~1.0")
        # speculation panel well-formedness: every arm reports counters
        # with a sane accept rate, and the scenario-level panel carries
        # the tuner-selected spec_k
        for arm in ("fixed_default", "self_tuned"):
            sp = r[arm].get("speculation")
            assert sp is not None, f"{name}/{arm}: no speculation stats"
            assert "accept_rate" in sp, f"{name}/{arm}: no accept_rate"
            assert 0.0 <= sp["accept_rate"] <= 1.0, \
                f"{name}/{arm}: accept_rate {sp['accept_rate']} outside [0,1]"
            assert 0 <= sp["accepted"] <= sp["drafted"], \
                (f"{name}/{arm}: accepted {sp['accepted']} vs drafted "
                 f"{sp['drafted']}")
        assert "speculation" in r and "spec_k_selected" in r["speculation"], \
            f"{name}: no scenario speculation panel"
        tn = r["time_attribution"]["self_tuned"]
        assert "cost_model_calibration" in tn, \
            f"{name}: tuned attribution lacks cost-model calibration"
        for k in ("stall_s_foreground", "stall_fraction",
                  "stall_ms_per_reconfig"):
            assert k in tn, f"{name}: tuned attribution lacks {k}"
        if "self_tuned_warm" in r:
            missing = [k for k in REPORT_KEYS
                       if k not in r["self_tuned_warm"]]
            assert not missing, f"{name}/self_tuned_warm missing {missing}"
            assert (r["self_tuned_warm"]["completed"]
                    == r["self_tuned_warm"]["requests"]), \
                f"{name}: warm arm dropped requests"
            g = r["warm_start_gain"]
            for k in ("store_key", "golden_tier", "absorbed_obs",
                      "init_quanta_cold", "init_quanta_warm",
                      "init_time_s_cold", "init_time_s_warm", "gain",
                      "warm_wins", "tuner_fraction_cold",
                      "tuner_fraction_warm"):
                assert k in g, f"{name}: warm_start_gain missing {k}"
            assert g["absorbed_obs"] > 0, \
                f"{name}: warm arm absorbed no observations — the store " \
                f"round-trip is broken"
            ws = r["self_tuned_warm"].get("warm_start", {})
            assert ws.get("tier") == "exact", \
                f"{name}: warm arm matched tier {ws.get('tier')!r}, not " \
                f"the exact signature the cold arm just wrote"
        if "kernel_ablation" in r:
            for arm in ("gather", "paged"):
                missing = [k for k in REPORT_KEYS
                           if k not in r["kernel_ablation"][arm]]
                assert not missing, f"{name}/ablation/{arm} missing {missing}"
                assert (r["kernel_ablation"][arm]["completed"]
                        == r["kernel_ablation"][arm]["requests"]), \
                    f"{name}: ablation arm {arm} dropped requests"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="short traces / smaller tuner init")
    ap.add_argument("--ci", action="store_true",
                    help="fast gate: one tiny fixed-seed scenario, asserts "
                         "a well-formed report; writes the _smoke artifact")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overload", type=float, default=5.0,
                    help="offered load as a multiple of the fixed-default "
                         "service rate; high enough that host-speed jitter "
                         "cannot un-overload the baseline, and well inside "
                         "the ~8x capacity of a full slot pool")
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="also write a Perfetto-loadable Chrome trace of "
                         "each scenario's tuned arm to DIR/trace_NAME.json")
    ap.add_argument("--warm-start", action="store_true",
                    help="add a tuned-warm third arm per scenario: the "
                         "cold arm persists its observations to a fresh "
                         "tuning store, the warm arm re-runs the trace "
                         "seeded from them (golden x0 + absorbed GP "
                         "history), and a warm_start_gain panel lands in "
                         "the report; the merged golden table is exported "
                         "to artifacts/tuning/")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="tuning-store directory for --warm-start "
                         "(default: a fresh artifacts/bench/tuning_store, "
                         "wiped per run so the cold arm stays cold)")
    args = ap.parse_args()

    from repro.configs.registry import get_config
    from repro.launch.cache import enable_compile_cache
    from repro.models import lm

    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))

    scenarios = ("poisson",) if args.ci else SCENARIO_NAMES
    duration = args.duration or (1.5 if args.ci else
                                 2.5 if args.smoke else 8.0)
    overload = args.overload
    tuner_a, tuner_b = (20, 2) if args.ci else \
        (30, 3) if args.smoke else (40, 3)
    # long_prompt prompts reach 68 tokens; warm those buckets too
    max_prompt = 24 if args.ci else 68

    print("warm-start: compiling the knob space's executables...", flush=True)
    t0 = time.perf_counter()
    engine = make_warm_engine(params, cfg, args.max_seq, max_prompt)
    print(f"warm-start done in {time.perf_counter() - t0:.1f}s "
          f"({len(engine._steps)} executables)", flush=True)
    base_tokps = calibrate_service_rate(engine, cfg)
    avg_tokens_per_req = 16.0     # mean of the traces' max_new range (8, 24)
    rate = overload * base_tokps / avg_tokens_per_req
    print(f"calibration: fixed-default {base_tokps:.1f} tok/s -> "
          f"rate {rate:.1f} req/s ({overload}x overload)", flush=True)

    results = {"arch": cfg.name, "smoke": args.smoke or args.ci,
               "calibrated_base_tokps": base_tokps, "scenarios": {}}
    store = None
    if args.warm_start:
        import os
        import shutil

        from repro.store import TuningStore
        store_dir = args.store_dir or os.path.join(
            "artifacts", "bench", "tuning_store")
        # a fresh store per bench run: the cold arm must be genuinely cold
        shutil.rmtree(store_dir, ignore_errors=True)
        store = TuningStore(store_dir)
    t0 = time.perf_counter()
    if args.trace_dir:
        import os
        os.makedirs(args.trace_dir, exist_ok=True)
    for name in scenarios:
        print(f"--- scenario {name}", flush=True)
        r = run_scenario(name, engine, cfg, rate, duration, args.seed,
                         tuner_a, tuner_b, slo=3.0,
                         trace_dir=args.trace_dir, store=store)
        results["scenarios"][name] = r
        print(f"    fixed   {r['fixed_default']['tokens_per_s']:8.1f} tok/s  "
              f"p99 {r['fixed_default']['p99_latency_s']:.2f}s")
        print(f"    tuned   {r['self_tuned']['tokens_per_s']:8.1f} tok/s  "
              f"p99 {r['self_tuned']['p99_latency_s']:.2f}s  "
              f"({r['self_tuned']['reconfig_count']} reconfigs, "
              f"speedup {r['speedup']:.2f}x)", flush=True)
        ta = r["time_attribution"]["self_tuned"]
        attr_bits = ", ".join(
            f"{k} {ta['fractions'][k]:.0%}"
            for k in ("decode", "prefill", "relayout", "recompile",
                      "migrate_bg", "recompile_bg", "tuner")
            if ta["seconds"][k] > 0)
        print(f"    attr    {attr_bits or 'n/a'} "
              f"(sum {ta['fractions_sum']:.2f})", flush=True)
        print(f"    stall   {ta['stall_fraction']:.1%} of wall foreground "
              f"reconfig stall "
              f"({ta.get('stall_ms_per_reconfig', 0.0):.0f} ms/reconfig)",
              flush=True)
        sp = r["speculation"]
        print(f"    spec    k={sp['spec_k_selected']} "
              f"({sp['drafter']}) accept {sp['accept_rate']:.0%} "
              f"({sp['accepted']}/{sp['drafted']} over "
              f"{sp['spec_ticks']} spec ticks)", flush=True)
        if "warm_start_gain" in r:
            g = r["warm_start_gain"]
            print(f"    warm    {g['tokens_per_s_warm']:8.1f} tok/s "
                  f"({g['gain']:.2f}x vs cold) init "
                  f"{g['init_quanta_warm']}/{g['init_quanta_cold']} quanta "
                  f"{g['init_time_s_warm']:.2f}/{g['init_time_s_cold']:.2f}s "
                  f"({g['absorbed_obs']} obs absorbed, "
                  f"tuner {g['tuner_fraction_cold']:.1%}->"
                  f"{g['tuner_fraction_warm']:.1%})", flush=True)
        if "sharing_ablation" in r:
            abl = r["sharing_ablation"]
            print(f"    sharing {abl['share_on']['prefill_per_request']:.1f} "
                  f"vs {abl['share_off']['prefill_per_request']:.1f} prefill "
                  f"tok/req ({abl['prefill_reduction']:.0%} less, "
                  f"{abl['share_on']['cow_copies']} COW)", flush=True)
        if "kernel_ablation" in r:
            abl = r["kernel_ablation"]
            print(f"    kernel  decode {abl['paged']['decode_tok_per_s']:7.1f}"
                  f" tok/s paged vs {abl['gather']['decode_tok_per_s']:7.1f} "
                  f"gather ({abl['speedup']:.2f}x; e2e "
                  f"{abl['e2e_speedup']:.2f}x)", flush=True)

    if engine.pool.kind == "paged":
        # decode-step microbench + modeled roofline entry: the kernel-level
        # perf delta, recorded alongside the end-to-end ablation
        results["paged_attention_microbench"] = decode_step_microbench(
            params, cfg, args.max_seq, reps=50 if args.ci else 150)
        results["paged_attention_roofline"] = {
            "short_ctx": paged_attention_roofline(cfg, args.max_seq, 16, 4,
                                                  16),
            "long_ctx": paged_attention_roofline(cfg, args.max_seq, 16, 4,
                                                 68),
        }
        mb_rows = results["paged_attention_microbench"]["contexts"]
        print("kernel microbench (decode step, gather -> paged): "
              + ", ".join(f"{k}: {v['gather']:.0f}->{v['paged']:.0f}us"
                          for k, v in mb_rows.items()))
        results["kernel_ablation_wins"] = sum(
            r["kernel_ablation"]["paged_no_slower"]
            for r in results["scenarios"].values() if "kernel_ablation" in r)

    wins = sum(r["tuned_wins"] for r in results["scenarios"].values())
    results["tuned_wins"] = wins
    if store is not None:
        # fold every arm's segments and export the golden-knobs table: the
        # store-root copy is the machine artifact, the artifacts/tuning copy
        # is what ci.sh gates with check_golden and what ships as the seed
        import os

        from repro.store import write_golden
        store.compact()
        table = store.write_golden()
        os.makedirs(os.path.join("artifacts", "tuning"), exist_ok=True)
        gname = ("GOLDEN_smoke.json" if (args.ci or args.smoke)
                 else "GOLDEN.json")
        gpath = os.path.join("artifacts", "tuning", gname)
        write_golden(gpath, table)
        warm_wins = sum(r["warm_start_gain"]["warm_wins"]
                        for r in results["scenarios"].values()
                        if "warm_start_gain" in r)
        results["warm_start_wins"] = warm_wins
        results["golden_path"] = gpath
        print(f"tuned-warm >= tuned-cold on {warm_wins}/{len(scenarios)} "
              f"scenarios; {len(table['entries'])} golden entries -> {gpath}")
    results["wall_s"] = time.perf_counter() - t0
    print(f"self-tuned >= fixed-default on {wins}/{len(scenarios)} "
          f"scenarios ({results['wall_s']:.0f}s total)")

    check_report(results, scenarios)
    # the canonical artifact only ever comes from full runs
    name = ("BENCH_serving_smoke.json" if (args.ci or args.smoke)
            else "BENCH_serving.json")
    save_artifact(name, results)
    print(f"wrote artifacts/bench/{name}")
    if not args.ci and wins < len(scenarios) - 1:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
