"""Bring-up smoke test on a TPU: the main paths at published widths.

  python chip_smoke.py             # one chip: serve starcoder2-3b
  python chip_smoke.py --chips 4   # four chips: train + relocate the state

One chip: starcoder2-3b at its published widths (random bf16 weights from
``--seed``) serves requests through ``ServingEngine`` / ``serve_loop`` with
a paged bf16 KV pool, prefix sharing on, and the Pallas paged-attention
kernel on every decode step.  Then one Type II reconfiguration
(speculation: ``spec_k`` 2 with the n-gram drafter, so the S=3 verify
kernel runs) and one Type I-b relayout (``max_batch`` 8 -> 4 with requests
in flight), each followed by more traffic.  Two numeric checks run on the
chip: the kernel against its f32 jnp oracle, and one engine decode step's
logits against the model's dense forward pass.

Four chips: ``LMJob`` trains starcoder2-3b's published widths, cut in
depth, on a 4x1 (data x model) mesh, then relocates its state to 2x2 by
ODMR and, separately, by checkpoint + restore.  Both must give bitwise
equal parameters and the same next-step loss.

Every phase checks its own result; any failure exits non-zero.  The last
line of a passing run is one JSON object naming the device.  Without a TPU
the script exits non-zero at once: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "starcoder2-3b"
MAX_SEQ = 1024
MAX_NEW = 32
SPEC_WAIT_S = 300.0     # bound on waiting for the background verify build
# the serving setting of every phase; only the two reconfigurations move it
SERVE_SETTING = {"max_batch": 8, "block_size": 16, "cache_dtype": "bf16",
                 "prefix_share": True, "prefill_chunk": 16}
# four-chip phase: depth cut so the 2x2 train step needs about half of each
# chip's 16 GiB (memory_analysis on v5e:2x2, batch 8 x 512: 8.28 GiB)
TRAIN_LAYERS = 8
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3
TRAIN_STEP_BUDGET = 8.5 * 2**30          # bytes per chip, checked on chip


class CompileClock:
    """Sums XLA backend compiles (persistent-cache loads included) as JAX
    reports them, from every thread: eager ops, jit, AOT and the engine's
    background builds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def say(**kv):
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def require(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------- checks

def check_kernel(seed: int, *, B=8, H=24, K=2, hd=128, bs=16, MB=64):
    """Pallas paged attention vs its jnp oracle (gather + f32 softmax at
    HIGHEST matmul precision) on bf16 pools, S=1 (decode) and S=3 (the
    spec_k=2 verify step)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import (paged_attention_op,
                                               paged_attention_ref)
    rng = np.random.default_rng(seed)
    NB = B * MB + 1
    out = {}
    for S in (1, 3):
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((NB, K, bs, hd)), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((NB, K, bs, hd)), jnp.bfloat16)
        bt = jnp.asarray(rng.permutation(NB - 1)[:B * MB].reshape(B, MB) + 1,
                         jnp.int32)
        pos = jnp.asarray(rng.integers(0, MB * bs - S, (B,)), jnp.int32)
        got = paged_attention_op(q, kp, vp, bt, pos)
        ref = paged_attention_ref(q, kp, vp, bt, pos)
        got, ref = (np.asarray(x, np.float32) for x in (got, ref))
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        # The kernel reads the same bf16 inputs and keeps its softmax
        # state in f32, but its two dots run on the MXU at default
        # precision, so the probabilities enter p.v rounded to bf16
        # (relative 2^-9 per weight, on |v| of a few units), and the output
        # is rounded to bf16.  The oracle runs at HIGHEST precision.
        # 2^-7 (0.0078) of the largest output admits that and still
        # catches a wrong block, head or mask, which moves outputs by O(1).
        require(err <= 2 ** -7, f"kernel S={S}: max err {err} > 2^-7")
        out[f"S{S}"] = err
    return out


def check_decode_logits(engine, params, cfg, seed: int, plen: int = 100):
    """One engine decode step (paged pool, kernel) against the dense
    forward pass over the same tokens."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    req = Request(rid=-1, prompt=prompt, max_new=2)
    require(engine._admit(req), "decode check: admission failed")
    slot = engine.slot_req.index(req)
    engine.pool.prepare_step_writes([slot], engine.slot_pos)
    step = engine._decode_exec(engine._ctx_cols(plen))
    logits, cache = step(engine.params, engine.pool.decode_cache(),
                         jnp.asarray(engine.slot_tok[:, None]),
                         jnp.asarray(engine.slot_pos))
    engine.pool.set_cache(cache)
    got = np.asarray(logits[slot, -1], np.float32)
    engine._complete(slot)

    toks = np.concatenate([prompt, [req.tokens_out[0]]])[None]

    @jax.jit
    def dense(params, toks):
        hidden, _, _ = lm.forward(params, {"tokens": toks}, cfg,
                                  mode="prefill")
        return lm.logits_fn(params, hidden[:, -1:], cfg)[0, 0]

    ref = np.asarray(dense(params, jnp.asarray(toks, jnp.int32)), np.float32)
    err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    # Both paths run the same bf16 weights and bf16 activations; they
    # differ in the attention schedule (paged kernel over a bf16 KV pool
    # against chunked dense attention) and in XLA's fusion of the rest,
    # so each of the 30 layers adds bf16 rounding (relative 2^-9 per
    # op) in a different order.  A 5% relative L2 error over the 49152
    # logits admits that accumulation; a wrong cache row, position or
    # mask decorrelates the logits (error ~1).
    require(err <= 0.05, f"decode logits: rel L2 err {err} > 0.05")
    return {"rel_l2": err, "argmax_agrees": bool(got.argmax() == ref.argmax())}


# ------------------------------------------------------------- traffic

def make_requests(rng, vocab: int, lens, rid0: int, prefix=None,
                  max_new: int = MAX_NEW):
    """Requests with the given prompt lengths; with ``prefix`` each prompt
    starts with it (the rest is drawn fresh)."""
    from repro.serving.engine import Request
    reqs = []
    for i, n in enumerate(lens):
        p = rng.integers(0, vocab, n).astype(np.int32)
        if prefix is not None:
            p[:len(prefix)] = prefix
        reqs.append(Request(rid=rid0 + i, prompt=p, max_new=max_new))
    return reqs


def check_served(reqs, vocab: int, stats: dict | None, what: str):
    for r in reqs:
        require(len(r.tokens_out) == r.max_new and r.done_s is not None,
                f"{what}: request {r.rid} gave {len(r.tokens_out)} of "
                f"{r.max_new} tokens")
        require(all(0 <= t < vocab for t in r.tokens_out),
                f"{what}: request {r.rid} has out-of-vocab tokens")
    if stats is not None:
        require(stats["failed_builds"] == 0,
                f"{what}: {stats['failed_builds']} background builds failed")


def serve_phase(cfg, params, seed: int, max_seq: int = MAX_SEQ,
                lens=(512, 64, 128, 512, 64, 128, 512, 64, 128, 512, 64, 128),
                shared=(192, 64, 4), spec_lens=(64, 64, 64, 64),
                relayout_lens=(256, 128, 64, 128)):
    """Serve, reconfigure (Type II, then Type I-b), serve again."""
    import jax
    from repro.core.reconfig import plan
    from repro.serving import (DEFAULT_SERVING_SETTING,
                               SERVING_RELAYOUT_KNOBS, ServingEngine,
                               serve_loop)
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    setting = dict(DEFAULT_SERVING_SETTING, **SERVE_SETTING)
    engine = ServingEngine(params, cfg, setting, max_seq=max_seq)
    out = {"decode_check": check_decode_logits(engine, params, cfg, seed)}
    wall = 0.0

    # 1. the base setting: unique prompts of 64-512 tokens, plus a group
    #    sharing one prefix (its blocks are prefilled once)
    n_pre, n_sfx, n_grp = shared
    prefix = rng.integers(0, V, n_pre).astype(np.int32)
    reqs = make_requests(rng, V, lens, 0)
    reqs += make_requests(rng, V, [n_pre + n_sfx] * n_grp, len(reqs), prefix)
    stats = serve_loop(engine, reqs)
    check_served(reqs, V, stats, "base")
    require(stats["shared_blocks_hit"] > 0, "base: no prefix block shared")
    wall += stats["wall_s"]
    out["base"] = {"requests": len(reqs), "wall_s": stats["wall_s"],
                   "tokens": stats["tokens"],
                   "shared_blocks_hit": stats["shared_blocks_hit"]}

    # 2. Type II: speculation on.  The S=3 verify executable builds in the
    #    background (async_precompile stays on), so waves of traffic run
    #    until speculative ticks have verified drafts on the chip
    engine.reconfigure(dict(engine.setting, spec_k=2.0, drafter="ngram"))
    rid = 100
    spec_ticks, waves = 0, 0
    deadline = time.perf_counter() + SPEC_WAIT_S
    while spec_ticks == 0:
        require(time.perf_counter() < deadline, "speculation never ran")
        reqs = make_requests(rng, V, spec_lens, rid)
        rid += len(reqs)
        stats = serve_loop(engine, reqs)
        check_served(reqs, V, stats, "spec")
        spec_ticks += stats["speculation"]["spec_ticks"]
        wall += stats["wall_s"]
        waves += 1
        if spec_ticks == 0:
            time.sleep(1.0)
    out["spec"] = {"waves": waves, "spec_ticks": spec_ticks,
                   "accept_rate": stats["speculation"]["accept_rate"]}

    # 3. Type I-b: max_batch 8 -> 4 while four requests are in flight
    live = make_requests(rng, V, relayout_lens, rid)
    live[0].prompt[:n_pre] = prefix       # one reuses the cached prefix
    for r in live:
        engine.submit(r)
    for _ in range(3):
        engine.step()
    require(engine.n_active == len(live), "relayout: requests not in flight")
    kinds = plan(engine.setting, dict(engine.setting, max_batch=4),
                 mesh_knobs=SERVING_RELAYOUT_KNOBS).kinds
    require("I-b" in kinds, f"max_batch change planned as {kinds}")
    t0 = time.perf_counter()
    engine.reconfigure(dict(engine.setting, max_batch=4))
    relayout_s = time.perf_counter() - t0
    require(engine.pool.n_slots == 4, "relayout: pool not resized")
    more = make_requests(rng, V, relayout_lens, rid + len(live))
    stats = serve_loop(engine, more)
    check_served(live + more, V, stats, "relayout")
    wall += stats["wall_s"]
    out["relayout"] = {"reconfigure_s": relayout_s,
                       "blocks_moved": engine.pool.last_relayout_blocks,
                       "wall_s": stats["wall_s"]}

    engine.join_builds()                  # no build left running at exit
    require(engine.failed_builds == 0,
            f"{engine.failed_builds} background builds failed")
    # every decode executable the engine compiled calls the Pallas kernel
    if jax.default_backend() == "tpu":
        for key, ex in engine._steps._d.items():
            if key[0] == "decode":
                require("tpu_custom_call" in ex.as_text(),
                        f"decode executable {key} has no Pallas kernel")
    out["serve_wall_s"] = wall
    out["executables"] = engine._steps.stats()
    return out


# ------------------------------------------------------------ one chip

def one_chip(args, clock: CompileClock):
    import jax
    from repro.configs.registry import get_config
    from repro.models import lm

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(phase="init", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
        heads=f"{cfg.n_heads}/{cfg.n_kv_heads}", head_dim=cfg.hd,
        vocab=cfg.vocab_size, params=n,
        init_s=round(time.perf_counter() - t0, 3))

    kerr = check_kernel(args.seed)
    say(phase="kernel_check", **{f"max_rel_err_{k}": v for k, v in kerr.items()},
        tol=2 ** -7)

    res = serve_phase(cfg, params, args.seed)
    say(phase="decode_check", tol=0.05, **res["decode_check"])
    say(phase="serve", **res["base"])
    say(phase="type_ii_spec", **res["spec"])
    say(phase="type_ib_relayout", **res["relayout"])
    ex = res["executables"]
    say(phase="summary", compile_s=round(clock.seconds, 3),
        compiles=clock.count, engine_executables_built=ex["misses"],
        engine_build_s=ex["build_time_s"],
        serve_wall_s=round(res["serve_wall_s"], 3),
        peak_bytes_in_use=jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------- four chips

def _bitwise_equal(a, b):
    import jax
    import jax.numpy as jnp
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}

    def same(x, y):
        t = uint[x.dtype.itemsize]
        return jnp.all(jax.lax.bitcast_convert_type(x, t)
                       == jax.lax.bitcast_convert_type(y, t))

    pairs = zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    return bool(jax.jit(lambda xs: jnp.all(jnp.stack(
        [same(x, y) for x, y in xs])))(list(pairs)))


def four_chips(args, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.core.reconfig import plan
    from repro.distributed.sharding import batch_pspec
    from repro.ps.lm_job import DEFAULT_LM_SETTING, LMJob, \
        setting_to_stepknobs
    from repro.ps.stepfn import jit_train_step

    require(len(jax.devices()) == 4, f"need 4 chips, have {len(jax.devices())}")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    job = LMJob(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=args.seed,
                n_devices=4)
    s_a = {**DEFAULT_LM_SETTING, "mesh_split": "4x1"}
    s_b = {**DEFAULT_LM_SETTING, "mesh_split": "2x2"}

    def compiled_step(setting):
        ms = job.meshspec(setting)
        fn, shapes, _ = jit_train_step(cfg, TrainConfig(), ms,
                                       setting_to_stepknobs(setting))
        rows = NamedSharding(ms.mesh, batch_pspec(ms, 2))
        batch = {k: jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                                         sharding=rows)
                 for k in ("tokens", "labels")}
        step = fn.lower(shapes, batch).compile()
        return lambda st, b: step(st, jax.device_put(b, rows)), step

    (step_a, _), (step_b, compiled_b) = compiled_step(s_a), compiled_step(s_b)
    m = compiled_b.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    say(phase="train_cut", arch=cfg.name, layers=f"{TRAIN_LAYERS}/30",
        d_model=cfg.d_model, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        step_bytes_per_chip_2x2=need, budget=int(TRAIN_STEP_BUDGET))
    require(need <= TRAIN_STEP_BUDGET, "train step exceeds its budget")

    state = job.init_state(s_a, seed=args.seed)
    batches = job.batches(seed=args.seed)
    losses = []
    for _ in range(TRAIN_STEPS):
        state, met = step_a(state, next(batches))
        losses.append(float(met["loss"]))
    require(all(np.isfinite(losses)), f"non-finite loss {losses}")
    say(phase="train_4x1", losses=losses)

    def relocate(method):
        p = plan(s_a, s_b, use_odmr=method == "odmr")
        require("I-b" in p.kinds, f"mesh_split change planned as {p.kinds}")
        t0 = time.perf_counter()
        out = jax.block_until_ready(job.state_adapter(state, p))
        return out, time.perf_counter() - t0

    st_ckpt, t_ckpt = relocate("baseline")
    st_odmr, t_odmr = relocate("odmr")
    del state
    equal = _bitwise_equal(st_odmr["params"], st_ckpt["params"])
    require(equal, "ODMR and checkpoint+restore parameters differ")
    nxt = next(batches)
    _, m_odmr = step_b(st_odmr, nxt)
    loss_odmr = float(m_odmr["loss"])
    del st_odmr
    _, m_ckpt = step_b(st_ckpt, nxt)
    loss_ckpt = float(m_ckpt["loss"])
    # same program, bitwise-equal inputs: the losses must be identical
    require(loss_odmr == loss_ckpt,
            f"next-step loss differs: odmr {loss_odmr} ckpt {loss_ckpt}")
    say(phase="relocate_4x1_to_2x2", odmr_s=t_odmr, ckpt_restore_s=t_ckpt,
        params_bitwise_equal=equal, next_loss_odmr=loss_odmr,
        next_loss_ckpt=loss_ckpt)
    say(phase="summary", compile_s=round(clock.seconds, 3),
        compiles=clock.count,
        peak_bytes_in_use=max(d.memory_stats()["peak_bytes_in_use"]
                              for d in jax.devices()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    say(phase="start", chips=args.chips, compile_cache=cache)
    clock = CompileClock()
    (four_chips if args.chips == 4 else one_chip)(args, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
