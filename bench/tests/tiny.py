"""A tiny cell for the CPU tests: the harness end to end in seconds."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256,
         "tie_embeddings": False, "norm_eps": 1e-5, "rope_theta": 10000.0}

CONF = {"name": "tiny", "registry": "starcoder2-3b", "model": MODEL,
        "engine": {"max_seq": 64,
                   "setting": {"max_batch": 4, "block_size": 16,
                               "cache_dtype": "bf16", "prefill_chunk": 32,
                               "k_chunk": 128, "prefix_share": True,
                               "quant": "none", "spec_k": 0.0,
                               "admit_budget": 1.0,
                               "block_overcommit": 1.0}},
        "check": {"logit_gap": 0.1, "pack": 256, "tokens": 40}}

TRAFFIC = {"loop": "open", "requests": 40, "rate_per_s": 12.0,
           "lead_in_s": 0.5, "levels": 8,
           "sessions": {"count": 3, "zipf_s": 1.1,
                        "prefix": {"median": 24, "sigma": 0.5, "min": 16,
                                   "max": 32}},
           "prompt": {"median": 10, "sigma": 0.6, "min": 4, "max": 16},
           "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


# the v5e table's numbers, so the readers' arithmetic runs; a CPU run's
# shares are never reported as device metrics
PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def bench() -> dict:
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": "tiny", "config": "tiny", "traffic": "tiny",
                       "chips": 1, "why": "tests"}]
    for pm in b["per_layer"] + b["end_to_end"]:
        pm["workloads"] = ["tiny"]
    return b


def conf(**check) -> dict:
    c = copy.deepcopy(CONF)
    c["check"].update(check)
    return c


def run(trace=False, seconds=1.5, seed=3, traffic=None, **kw):
    import time
    from bench import harness
    return harness.run_cell("tiny", seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            bench=bench(), conf=kw.pop("conf", conf()),
                            traffic=traffic or TRAFFIC, peaks=PEAKS,
                            log=lambda *a, **k: None, **kw)
