"""Knee sweep of an open-loop cell: the offered rate it sustains.

  python3 bench/sweep.py --workload phi4.chat --rates 1,1.5,2,2.5 \
      --seconds 20 --seed 5

Sets the cell up once (weights, engine, the warm-up of every rate's
shapes), then serves the cell's traffic at each rate in turn: the
traffic's lead-in, then ``--seconds`` measured.  For each rate it prints
one JSON line: the queue of submitted but unadmitted requests when the
window opens and when it closes, and the window's end-to-end readings.
The knee is the highest rate whose queue does not grow over the window;
the cell's traffic file then fixes its rate at about 0.8 of it.  This
script found the rates in ``bench/traffic``; the benchmark's runs never
call it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import blocks, harness, workload

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing run", file=sys.stderr)
        return 2
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.find_cell(args.workload, bench)
    conf = harness.load_config(cell["config"])
    base = workload.load_traffic(cell["traffic"])
    m = conf["model"]
    rates = [float(r) for r in args.rates.split(",")]
    plans = []
    for i, r in enumerate(rates):
        t = dict(base, rate_per_s=r)
        plans.append((r, t, workload.generate(t, args.seed + i,
                                              m["vocab_size"])))
    params = blocks.of(conf).make_params(m, args.seed)
    engine = harness.build_engine(conf, harness.model_config(conf), params)
    every = [s for _, _, specs in plans for s in specs]
    harness.warm(engine, harness.warm_plan(engine, every), m["vocab_size"])
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)

    for r, t, specs in plans:
        q = {}

        def on_tick(now, w0):
            if "open" not in q and now >= w0:
                q["open"] = engine.queue_depth

        win = harness.drive(engine, specs, t, args.seconds, on_tick=on_tick)
        q_close = engine.queue_depth
        e = harness.e2e(win)
        print(json.dumps({"rate_per_s": r, "queue_at_open": q.get("open"),
                          "queue_at_close": q_close,
                          **{k: e[k] for k in ("ttft_p95_ms", "itl_p95_ms",
                                               "output_tokens_per_s",
                                               "n_ttft")}}), flush=True)
        engine.queue.clear()                 # drop the backlog and what
        for slot, req in enumerate(engine.slot_req):      # is in flight
            if req is not None:
                engine._complete(slot)
        engine.pool.reset_prefix_cache()
    return 0


if __name__ == "__main__":
    sys.exit(main())
