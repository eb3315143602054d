"""One run of one benchmark cell: set up, serve a timed window, check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json``, the block it names in
``bench/blocks/<block>.py`` (weights, f32 reference and work counts) and
each per-layer metric's reader in ``bench/layer_metrics/<metric>.py``.
Adding a cell, a configuration, a mix, a metric or a block adds files;
this one stays as it is.

A run:
  1. makes the block's bf16 weights on the chip from the seed and builds
     the program's ``ServingEngine`` with the configuration's setting;
  2. warms every shape the seeded traffic can reach, then serves the
     traffic's lead-in, so the window starts warm and busy;
  3. serves the window, stamping every token with the host clock after
     the engine's step returns;
  4. reads the end-to-end metrics (``--trace 0``) or the per-layer ones
     (``--trace 1``: spans, counters and a profiler trace of the window's
     last seconds);
  5. frees the program and compares a seeded sample of the requests that
     finished in the window with the block's f32 reference
     (``bench.check``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import blocks, check, workload

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"                # profiler traces (gitignored)
TRACE_S = 4.0                            # traced seconds at the window's end
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_CACHE = 64                          # executables the engine may hold


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- lookups

def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(name: str, bench: dict) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def load_reader(metric: str):
    """The ``read(run)`` function of ``bench/layer_metrics/<metric>.py``."""
    path = BENCH / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Backend compiles, persistent-cache loads included (JAX's compile
    event wraps both), from any thread; and the cache's hits and misses."""

    HITS, MISSES = ("/jax/compilation_cache/cache_hits",
                    "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax
        self.names: list = []
        self.cache = {self.HITS: 0, self.MISSES: 0}
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, name, secs, fun_name="?", **_):
        if name == COMPILE_EVENT:
            self.names.append(fun_name)

    def _on_event(self, name, **_):
        if name in self.cache:
            self.cache[name] += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        self._jax.monitoring.unregister_event_listener(self._on_event)


# ----------------------------------------------------------- the program

def model_config(conf: dict):
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config(conf["registry"]), **conf["model"])


def build_engine(conf: dict, cfg, params):
    from repro.serving import DEFAULT_SERVING_SETTING, ServingEngine
    setting = dict(DEFAULT_SERVING_SETTING, **conf["engine"]["setting"])
    return ServingEngine(params, cfg, setting,
                         max_seq=conf["engine"]["max_seq"],
                         step_cache_size=STEP_CACHE)


def warm_plan(engine, specs) -> dict:
    """The shapes the seeded traffic can reach, and no others."""
    bs = engine.pool.bs
    lens = sorted({len(s.prompt) for s in specs})
    chunk = set()
    if engine.setting.get("prefix_share"):
        # a cached prefix may be hit whole or in part (blocks evicted)
        for s in specs:
            for k in range(1, s.prefix_len // bs + 1):
                chunk.add(engine._bucket(len(s.prompt) - k * bs))
    last = max(len(s.prompt) + s.max_new - 2 for s in specs)
    cols = sorted({engine._ctx_cols(p) for p in range(min(lens), last + 1)})
    return {"full_lengths": lens,
            "prefill": sorted({engine._bucket(p) for p in lens}),
            "chunk": sorted(chunk), "decode_cols": cols}


def warm(engine, plan: dict, vocab: int) -> dict:
    """Compile every planned executable, then admit one request of each
    full-prompt length through the engine's own admission path (its
    per-length eager ops), and one through the shared-prefix path;
    returns the seconds of each stage."""
    from repro.serving.engine import Request
    t0 = time.perf_counter()
    for c in plan["decode_cols"]:
        engine._decode_exec(c)
    for b in plan["prefill"]:
        engine._prefill_exec(b)
    for b in plan["chunk"]:
        engine._chunk_prefill_exec(b)
    t1 = time.perf_counter()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, p).astype(np.int32)
               for p in plan["full_lengths"]]
    if plan["chunk"]:
        # second, while the first is still in the prefix cache: a prompt
        # that repeats the first's first two blocks
        head = prompts[0][:2 * engine.pool.bs]
        prompts.insert(1, np.concatenate([head, rng.integers(
            0, vocab, plan["chunk"][0]).astype(np.int32)]))
    for i, p in enumerate(prompts):
        # max_new 1: the request completes inside the admission
        if not engine._admit(Request(rid=-1 - i, prompt=p, max_new=1)):
            raise RuntimeError(f"warm-up admission of {len(p)} tokens "
                               f"failed")
    engine.pool.reset_prefix_cache()
    t2 = time.perf_counter()
    return {"executables": t1 - t0, "admissions": t2 - t1}


# ------------------------------------------------------------ the window

@dataclass
class Rec:
    """One request as the client sees it (host clock, perf_counter s)."""
    spec: workload.Spec
    req: object
    due: float
    stamps: list = field(default_factory=list)   # one per token received
    admit_tick: float | None = None              # start of admitting tick
    done: float | None = None
    refused: bool = False                        # submit raised


@dataclass
class Window:
    recs: list
    w0: float
    w1: float
    ticks: list            # trace runs: per-tick host records


def drive(engine, specs, traffic: dict, seconds: float, *, more=(),
          record=False, on_tick=None, annotate=None) -> Window:
    """Serve ``specs`` on the traffic's schedule: the lead-in, then the
    window.  Open loop: each request is submitted once its due time has
    passed.  Closed loop: ``callers`` requests are in flight; each one
    that completes is replaced by the next, due when the caller learns
    of the completion; once every request of ``specs`` has been sent,
    the next round is taken from ``more`` (``workload.rounds``), so the
    callers never run dry however fast the program is.  ``on_tick(now,
    w0)`` runs between steps (the traced run opens its counters and
    starts the profiler there)."""
    from repro.serving.engine import Request
    open_loop = traffic["loop"] == "open"
    pending = deque(specs)
    more = iter(more)
    t0 = time.perf_counter()
    w0 = t0 + traffic["lead_in_s"]
    w1 = w0 + seconds
    recs, inflight, ticks = [], [], []

    def take():
        """The closed loop's next request; None once the supply ends."""
        if not pending:
            pending.extend(next(more, ()))
        return pending.popleft() if pending else None

    def submit(spec, due):
        req = Request(rid=spec.idx, prompt=spec.prompt, max_new=spec.max_new,
                      arrival_s=due - t0)
        rec = Rec(spec=spec, req=req, due=due)
        recs.append(rec)
        try:
            engine.submit(req, now=time.perf_counter() - t0)
        except ValueError:          # the program refused it: a failure
            rec.refused = True
            return
        inflight.append(rec)

    if not open_loop:
        for _ in range(int(traffic["callers"])):
            if (spec := take()) is None:
                break
            submit(spec, t0)
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        if on_tick is not None:
            on_tick(now, w0)
        while open_loop and pending and t0 + pending[0].due_s <= now:
            spec = pending.popleft()
            submit(spec, t0 + spec.due_s)
        if not engine.has_work():
            nxt = t0 + pending[0].due_s if open_loop and pending else w1
            time.sleep(max(0.0, min(nxt, w1) - now))
            continue
        keys = _decode_keys(engine) if record else None
        ts = time.perf_counter()
        if annotate is not None:
            with annotate("bench.tick"):
                engine.step(now=ts - t0)
        else:
            engine.step(now=ts - t0)
        te = time.perf_counter()
        emitted, admitted = 0, []
        for r in inflight:
            n = len(r.req.tokens_out)
            if n > len(r.stamps):
                if not r.stamps:
                    r.admit_tick = ts
                    admitted.append(len(r.spec.prompt))
                emitted += n - len(r.stamps)
                r.stamps += [te] * (n - len(r.stamps))
            if r.req.done_s is not None:
                r.done = te
                if not open_loop and (spec := take()) is not None:
                    submit(spec, te)
        inflight[:] = [r for r in inflight if r.done is None]
        if record:
            # this tick's decodes: the slots live before it, plus each
            # request it admitted (decoded in the same tick at P)
            ticks.append({"ts": ts, "te": te, "tokens": emitted,
                          "decode_keys": keys + [p + 1 for p in admitted]})
    return Window(recs=recs, w0=w0, w1=w1, ticks=ticks)


def _decode_keys(engine) -> list:
    """Keys each live slot's next decode attends (its position + 1)."""
    return [int(engine.slot_pos[i]) + 1
            for i, r in enumerate(engine.slot_req) if r is not None]


# ----------------------------------------------------------- end to end

def e2e(win: Window) -> dict:
    """The cell's end-to-end readings from the client-side stamps."""
    w0, w1 = win.w0, win.w1
    ttft, gaps, tokens = [], [], 0
    for r in win.recs:
        if w0 <= r.due < w1:
            first = r.stamps[0] if r.stamps else None
            # a request still waiting at the window's end counts its wait
            ttft.append((first if first is not None and first <= w1 else w1)
                        - r.due)
        st = [s for s in r.stamps if s <= w1]
        tokens += sum(1 for s in st if s > w0)
        gaps += [b - a for a, b in zip(st, st[1:]) if b > w0]
        if st and (r.done is None or r.done > w1) and len(st) < r.spec.max_new:
            gaps.append(w1 - st[-1])         # the gap still open at the end
    return {
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3 if ttft else None,
        "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3 if gaps else None,
        "output_tokens_per_s": tokens / (w1 - w0),
        "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens": tokens,
    }


# ------------------------------------------------------------------ run

@dataclass
class RunRecord:
    """What a per-layer metric's reader may read."""
    model: dict
    block: object          # the configuration's block module (work counts)
    peaks: dict
    window: Window
    spans: list            # tracer events in the window, absolute "t0"
    counters: dict         # engine counters over the window
    compiles: int          # backend compiles inside the window
    peak_bytes: int | None
    trace: dict | None     # reduced profiler trace of the last seconds


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, bench=None,
             conf=None, traffic=None, peaks=None, program_hook=None,
             control: bool = False, log=print) -> dict:
    """One run; returns the result object (the last stdout line)."""
    import jax
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(name, bench)
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
        if len(devices) < cell["chips"]:
            raise NoChip(f"cell needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    dev = device_info(devices[:cell["chips"]])
    peaks = peaks_for(dev["kind"]) if require_tpu else peaks
    conf = conf or load_config(cell["config"])
    traffic = traffic or workload.load_traffic(cell["traffic"])
    block = blocks.of(conf)
    m = conf["model"]
    compiles = CompileCounter()
    try:
        return _run(name, cell, conf, block, traffic, m, seed, seconds,
                    trace, t_start, dev, peaks, compiles, program_hook,
                    control, bench, log)
    finally:
        compiles.close()


def _run(name, cell, conf, block, traffic, m, seed, seconds, trace,
         t_start, dev, peaks, compiles, program_hook, control, bench, log):
    import jax
    from repro.obs.trace import Tracer

    cfg = model_config(conf)
    supply = workload.rounds(traffic, seed, m["vocab_size"])
    specs = next(supply)                  # round 0 fixes the warmed shapes
    t_init = time.perf_counter()
    params = jax.block_until_ready(block.make_params(m, seed))
    t_params = time.perf_counter()
    engine = build_engine(conf, cfg, params)
    plan = warm_plan(engine, specs)
    stages = warm(engine, plan, m["vocab_size"])
    if program_hook is not None:
        program_hook(engine)
    log(f"# warm: {len(plan['full_lengths'])} prompt lengths, prefill "
        f"buckets {plan['prefill']}, chunk buckets {plan['chunk']}, decode "
        f"cols {plan['decode_cols']}", file=sys.stderr)
    log("# setup s: start " + f"{t_init - t_start:.3f}, weights "
        f"{t_params - t_init:.3f}, " + ", ".join(
            f"{k} {v:.3f}" for k, v in stages.items())
        + f"; compiles {compiles.count}, cache hits "
        f"{compiles.cache[compiles.HITS]}, misses "
        f"{compiles.cache[compiles.MISSES]}", file=sys.stderr)

    tracer = Tracer(enabled=True) if trace else None
    state = {"c0": None, "ctr0": None, "prof": None}
    log_dir = OUT / f"trace-{name}"
    shutil.rmtree(log_dir, ignore_errors=True)      # no stale trace

    def on_tick(now, w0):
        if state["c0"] is None and now >= w0:
            # the window opens: counters from here
            state["c0"] = compiles.count
            state["ctr0"] = _counters(engine)
            if tracer is not None:
                engine.set_tracer(tracer)
        if (trace and state["prof"] is None
                and now >= w0 + max(seconds - TRACE_S, 0.0)):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(log_dir), profiler_options=opts)
            state["prof"] = time.perf_counter()

    win = drive(engine, specs, traffic, seconds, more=supply, record=trace,
                on_tick=on_tick,
                annotate=jax.profiler.TraceAnnotation if trace else None)
    if state["prof"] is not None:
        jax.profiler.stop_trace()
    if state["c0"] is None:             # the window never ticked
        state["c0"], state["ctr0"] = compiles.count, _counters(engine)
    n_compiles = compiles.count - state["c0"]
    if n_compiles:
        log(f"# compiled in the window: {compiles.names[state['c0']:]}",
            file=sys.stderr)
    counters = {k: v - state["ctr0"][k] for k, v in _counters(engine).items()}
    setup_s = win.w0 - t_start
    stats = devices_stats(jax.devices()[:dev["count"]])
    peak = stats.get("peak_bytes_in_use")
    dev = dict(dev, memory_peak_bytes=peak)

    # free the program before the reference runs
    spans = []
    if tracer is not None:
        spans = [dict(e, start=tracer.t0 + e["ts"]) for e in tracer.events]
    del engine, params
    gc.collect()

    verdict = check.check(conf, block, seed, win, control=control, log=log)

    e = e2e(win)
    due = [r for r in win.recs if win.w0 <= r.due < win.w1]
    attempted, failed = len(due), sum(r.refused for r in due)
    log(f"# window: {attempted} requests due, {e['n_ttft']} ttft samples, "
        f"{e['n_gaps']} gaps, {e['tokens']} tokens, {n_compiles} compiles, "
        f"setup {setup_s:.3f} s", file=sys.stderr)
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed}
    if not trace:
        vals = dict(e, setup_s=setup_s)
        result["metrics"] = {
            x["name"]: {"value": vals[x["name"]], "unit": x["unit"]}
            for x in bench["end_to_end"]
            if name in x.get("workloads", [name])
            and vals[x["name"]] is not None}
    else:
        reduced = _reduce_trace(log_dir, log)
        if reduced is not None:
            dev = dict(dev, busy_s=reduced["busy_s"],
                       window_s=reduced["window_s"])
        rec = RunRecord(model=dict(conf["model"]), block=block,
                        peaks=peaks, window=win, spans=spans,
                        counters=counters,
                        compiles=n_compiles, peak_bytes=peak, trace=reduced)
        metrics = {}
        for pm in bench["per_layer"]:
            if name not in pm.get("workloads", [name]):
                continue
            v = load_reader(pm["name"])(rec)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            result["breakdown"] = {"device_ops": reduced["top_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result["device"] = dev
    result["checks"] = verdict["checks"]
    return result


def _counters(engine) -> dict:
    return {"prefill_tokens_computed": engine.prefill_tokens_computed,
            "prefill_tokens_total": engine.prefill_tokens_total}


def devices_stats(devices) -> dict:
    """memory_stats of the fullest chip ({} where the backend has none)."""
    best = {}
    for d in devices:
        s = d.memory_stats() or {}
        if s.get("peak_bytes_in_use", -1) > best.get("peak_bytes_in_use", -1):
            best = s
    return best


def _reduce_trace(log_dir: Path, log=print) -> dict | None:
    """Device busy time, top ops and idle gaps of the traced seconds;
    None, with the reason on stderr, where the trace has nothing to read."""
    from bench import trace_reduce as tr

    def missing(why):
        log(f"# trace: {why}; busy_s, window_s and the breakdown are left "
            f"out", file=sys.stderr)

    try:
        path = tr.find_xplane(str(log_dir))
    except FileNotFoundError:
        missing(f"no .xplane.pb under {log_dir}")
        return None
    data = tr.read_xplane(path)
    shutil.rmtree(log_dir, ignore_errors=True)     # traces are large
    ticks = [h for h in data["host"] if h[0] == "bench.tick"]
    if not ticks:
        missing("no bench.tick in the traced seconds")
        return None
    if not data["devices"]:
        missing("no TPU plane in the trace")
        return None
    lo, hi = ticks[0][1], ticks[-1][2]
    planes = sorted(data["devices"])
    busy = [tr.busy_ns(data["devices"][p], lo, hi) for p in planes]
    ops0 = data["devices"][planes[0]]
    return {"lo": lo, "hi": hi, "ticks": ticks, "ops": ops0,
            "busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9,
            "top_ops": tr.top_ops(ops0, lo, hi),
            "idle_gaps": tr.idle_gaps(ops0, data["host"], lo, hi)}


# ------------------------------------------------------------------ CLI

def print_result(result: dict):
    """Compared numbers as the last lines of stderr, then the result."""
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
