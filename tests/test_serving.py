"""Serving-engine invariants: request accounting, slot reclamation,
batched-output correctness vs the unbatched reference decode, online
re-layout, the bounded executable cache, and the per-step counts and
request stamps the engine reports."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.lru import LRUCache
from repro.core.reconfig import plan
from repro.models import lm
from repro.models.lm import ModelKnobs
from repro.obs import Tracer
from repro.serving import (DEFAULT_SERVING_SETTING, SERVING_RELAYOUT_KNOBS,
                           Request, ServingEngine, serve_loop)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("starcoder2-3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, lens, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (p,))
                    .astype(np.int32),
                    max_new=max_new, arrival_s=0.0)
            for i, p in enumerate(lens)]


def _setting(**kw):
    return dict(DEFAULT_SERVING_SETTING, **kw)


def _reference_generate(params, cfg, prompt, max_new, *, max_seq=48,
                        prefill_chunk=16, k_chunk=128, cache_dtype="f32"):
    """Unbatched greedy decode mirroring the engine's prefill padding, so
    any engine mismatch is a slot/batching bug, not a numeric artifact."""
    P = len(prompt)
    bucket = -(-P // prefill_chunk) * prefill_chunk
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :P] = prompt
    kn = ModelKnobs(k_chunk=k_chunk)
    hidden, _, pcache = lm.forward(params, {"tokens": jnp.asarray(padded)},
                                   cfg, None, kn, mode="prefill")
    logits = lm.logits_fn(params, hidden[:, P - 1:P], cfg, None)
    tok = int(jnp.argmax(logits[0, 0]))
    out = [tok]
    dt = jnp.float32 if cache_dtype == "f32" else jnp.bfloat16
    cache = {k: jnp.zeros(s.shape, dt)
             for k, s in lm.init_cache_shapes(cfg, 1, max_seq).items()}
    for k in ("k", "v"):
        cache[k] = cache[k].at[:, 0, :P].set(
            pcache[k][:, 0, :P].astype(dt))
    for i in range(max_new - 1):
        pos = jnp.full((1,), P + i, jnp.int32)
        logits, cache = lm.decode_step(
            params, cache, jnp.asarray([[tok]], jnp.int32), pos, cfg)
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


def test_no_drop_no_duplicate(model):
    cfg, params = model
    engine = ServingEngine(params, cfg, _setting(max_batch=4), max_seq=48)
    reqs = _requests(cfg, [5, 12, 17, 3, 9, 21, 7, 14], max_new=5)
    stats = serve_loop(engine, reqs)
    assert stats["completed"] == len(reqs)
    assert sorted(engine.submitted) == sorted(r.rid for r in reqs)
    finished_ids = [r.rid for r in engine.finished]
    assert sorted(finished_ids) == sorted(engine.submitted)
    assert len(set(finished_ids)) == len(finished_ids)          # no dups
    for r in engine.finished:
        assert len(r.tokens_out) == r.max_new


def test_slots_reclaimed(model):
    cfg, params = model
    engine = ServingEngine(params, cfg, _setting(max_batch=2), max_seq=48)
    for r in _requests(cfg, [6, 6, 6, 6, 6], max_new=3):
        engine.submit(r)
    peak = 0
    while engine.has_work():
        engine.step()
        assert engine.n_active <= 2                # admission respects knob
        peak = max(peak, engine.n_active)
    assert peak == 2                               # batching actually engaged
    assert all(r is None for r in engine.slot_req)  # every slot reclaimed
    assert len(engine.finished) == 5


def test_engine_matches_unbatched_reference(model):
    cfg, params = model
    lens, max_new = [5, 12, 17], 6
    engine = ServingEngine(params, cfg, _setting(max_batch=4), max_seq=48)
    serve_loop(engine, _requests(cfg, lens, max_new=max_new))
    by_rid = {r.rid: r for r in engine.finished}
    for i, p in enumerate(lens):
        ref = _reference_generate(params, cfg, by_rid[i].prompt, max_new)
        assert by_rid[i].tokens_out == ref, f"request {i} diverged"


def test_shrink_while_busy_stays_on_warmed_geometry(model):
    """Shrinking max_batch below the live count must not allocate a pool
    sized to the live set: that transient geometry is outside the knob
    space, so its decode executables were never warm-started and the
    reconfig window pays cold compiles.  The slot count holds at the old
    (warmed) value until the backlog drains, then the deferred shrink in
    step() lands directly on the target geometry."""
    cfg, params = model
    lens, max_new = [5, 9, 12], 8
    engine = ServingEngine(params, cfg, _setting(max_batch=4), max_seq=48)
    for r in _requests(cfg, lens, max_new=max_new):
        engine.submit(r)
    for _ in range(3):
        engine.step()
    assert engine.n_active == 3
    p = plan(engine.setting, _setting(max_batch=2),
             mesh_knobs=SERVING_RELAYOUT_KNOBS)
    assert "I-b" in p.kinds
    engine.apply_plan(p)
    assert engine.n_slots == 4          # held, not shrunk to len(live)=3
    while engine.has_work():
        engine.step()
    assert engine.n_slots == 2          # deferred shrink completed on drain
    by_rid = {r.rid: r for r in engine.finished}
    for i, pl in enumerate(lens):
        ref = _reference_generate(params, cfg, by_rid[i].prompt, max_new)
        assert by_rid[i].tokens_out == ref, f"request {i} diverged"


def test_relayout_preserves_live_requests(model):
    """Type I-b pool re-layout mid-flight: live slots relocate, outputs
    stay identical to the never-reconfigured reference."""
    cfg, params = model
    lens, max_new = [5, 12], 8
    engine = ServingEngine(params, cfg, _setting(max_batch=2), max_seq=48)
    for r in _requests(cfg, lens, max_new=max_new):
        engine.submit(r)
    for _ in range(3):                     # both requests mid-generation
        engine.step()
    assert engine.n_active == 2
    p = plan(engine.setting, _setting(max_batch=4),
             mesh_knobs=SERVING_RELAYOUT_KNOBS)
    assert "I-b" in p.kinds
    engine.apply_plan(p)
    assert engine.n_slots >= 4
    while engine.has_work():
        engine.step()
    by_rid = {r.rid: r for r in engine.finished}
    for i, pl in enumerate(lens):
        ref = _reference_generate(params, cfg, by_rid[i].prompt, max_new)
        assert by_rid[i].tokens_out == ref, f"request {i} diverged"


def test_rejects_oversized_request(model):
    cfg, params = model
    engine = ServingEngine(params, cfg, _setting(), max_seq=32)
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0,
                              prompt=np.zeros(30, np.int32), max_new=8))


def test_encoder_family_raises():
    """Every decode-capable family is served through the StatePool
    interface now; only encoder-only models (no decode step) are rejected."""
    cfg = get_config("hubert-xlarge").reduced()
    with pytest.raises(NotImplementedError):
        ServingEngine({}, cfg, _setting())


def test_lru_cache_bounds_and_recency():
    cache = LRUCache(capacity=3)
    for i in range(5):
        cache.put(i, str(i))
    assert len(cache) == 3 and cache.evictions == 2
    assert 0 not in cache and 1 not in cache
    cache.get(2)                                    # refresh 2
    cache.put(5, "5")                               # evicts 3, not 2
    assert 2 in cache and 3 not in cache
    made = []
    cache.get_or_create("k", lambda: made.append(1) or "v")
    cache.get_or_create("k", lambda: made.append(1) or "v")
    assert made == [1]                              # factory ran once


def test_engine_step_cache_bounded(model):
    cfg, params = model
    engine = ServingEngine(params, cfg, _setting(), max_seq=48,
                           step_cache_size=2)
    reqs = _requests(cfg, [5, 17, 33], max_new=2)   # 3 prefill buckets
    serve_loop(engine, reqs)
    assert len(engine._steps) <= 2
    assert len(engine.finished) == 3                # eviction never corrupts


def test_aot_compile_raises_compile_errors():
    """A program the compiler refuses fails where it is built; it never
    comes back as a jit that would fail (or recompile) at first call."""
    from repro.core.lru import aot_compile
    bad = jax.ShapeDtypeStruct((2, 2), jnp.float32)
    with pytest.raises(TypeError):
        aot_compile(lambda x: x @ jnp.ones((3, 3)), bad)


def test_failed_background_build_is_counted(model):
    """A speculative-verify executable whose background build fails is
    counted and warned about; its key stays parked (no rebuild per tick)
    and the engine keeps serving on the one-token path."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _setting(max_batch=2, spec_k=2.0),
                        max_seq=48)

    plain_build = eng._decode_spec

    def refusing_build(cols, s=1, geom=None):
        if s == 1:                  # the one-token path still builds
            return plain_build(cols, s, geom)

        def build():
            raise RuntimeError("compiler refused the verify step")
        return ("refused", cols, s), build

    eng._decode_spec = refusing_build
    cols = eng._ctx_cols(15)        # the one bucket the traffic below uses
    assert not eng._spec_exec_ready(cols, 3)       # kicks the worker
    with pytest.warns(RuntimeWarning, match="compiler refused"):
        eng.join_builds()                          # waits, then counts
    assert eng.failed_builds == 1
    assert not eng._spec_exec_ready(cols, 3)       # parked: no new thread
    assert not eng._spec_warm_done and eng.failed_builds == 1
    reqs = _requests(cfg, [5, 9], max_new=4)
    stats = serve_loop(eng, reqs)
    assert stats["completed"] == 2 and stats["failed_builds"] == 0
    assert all(len(r.tokens_out) == 4 for r in reqs)


def test_decode_span_counts_live_kv_blocks(model):
    """``serve.decode`` carries ``kv_blocks``: the blocks the paged kernel
    fetches, each live slot's prefix ``pos // block_size + 1``."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _setting(max_batch=3, block_size=16),
                        max_seq=48)
    tr = Tracer()
    eng.set_tracer(tr)
    reqs = _requests(cfg, [20, 9], max_new=3)
    serve_loop(eng, reqs)
    # both admitted in the first step, decoding at 20/9, 21/10: 2 + 1
    # blocks; each stops after 2 decodes (its prefill made the first token)
    got = [(e["args"]["batch"], e["args"]["kv_blocks"])
           for e in tr.events if e["name"] == "serve.decode"]
    assert got == [(2, 3), (2, 3)]


def test_tick_counts_and_request_stamps(model):
    """``serve.tick`` carries the step's queue and admission counts, equal
    to what the engine did; each request's stamps are ordered, and its
    first token is stamped after the prefill that made it."""
    cfg, params = model
    # 5 usable blocks of 16: two 2-block requests fit, a third is refused
    # and the 1-block request behind it is admitted past it
    eng = ServingEngine(params, cfg, _setting(max_batch=3), max_seq=48,
                        block_overcommit=0.5)
    tr = Tracer()
    eng.set_tracer(tr)
    refused = []
    try_admit = eng.pool.try_admit

    def counting_try_admit(prompt, max_new):
        res = try_admit(prompt, max_new)
        refused[-1] += res is None
        return res

    eng.pool.try_admit = counting_try_admit
    reqs = _requests(cfg, [20, 20, 20, 9], max_new=6)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r, now=0.0)
    steps = []
    while eng.has_work():
        refused.append(0)
        queued = len(eng.queue)
        now = time.perf_counter() - t0
        eng.step(now=now)
        steps.append({"now": now, "queued": queued,
                      "admitted": queued - len(eng.queue),
                      "admit_refused": refused[-1]})
    ticks = [e for e in tr.events if e["name"] == "serve.tick"]
    decodes = [e for e in tr.events if e["name"] == "serve.decode"]
    assert len(ticks) == len(steps)
    assert sum(s["admit_refused"] for s in steps) > 0
    assert steps[0]["admitted"] == 3 and steps[0]["admit_refused"] == 1
    for tick, want in zip(ticks, steps):
        a = tick["args"]
        for k in ("queued", "admitted", "admit_refused"):
            assert a[k] == want[k], (k, a, want)
        # live slots after admission: the batch its decode ran
        dec = [d for d in decodes
               if tick["ts"] <= d["ts"] <= tick["ts"] + tick["dur"]]
        assert a["active"] == (dec[0]["args"]["batch"] if dec else 0)
    for r in reqs:
        assert r.submit_s <= r.admit_s <= r.first_token_s <= r.done_s
        step_now = max(s["now"] for s in steps if s["now"] <= r.admit_s)
        assert r.first_token_s > step_now      # after its prefill ran
    admits = [e for e in tr.events if e["name"] == "serve.admit"]
    assert [e["args"]["shared"] for e in admits
            if "shared" in e["args"]] == [0] * len(reqs)


def test_admission_after_decode_compiles_nothing(model):
    """An admission of a prompt length warm-up already admitted compiles
    nothing after decode steps have replaced the pool's arrays: the
    decode executable's outputs are placed like the arrays it was lowered
    for, so admission's eager ops hit the warm-up's compilations."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _setting(max_batch=2,
                                              cache_dtype="bf16"),
                        max_seq=48)
    for c in sorted({eng._ctx_cols(p) for p in range(9, 40)}):
        eng._decode_exec(c)
    eng._prefill_exec(16)
    for i, plen in enumerate((9, 12)):         # max_new 1: no decode
        assert eng._admit(_requests(cfg, [plen], max_new=1, seed=i)[0])
    compiled = []

    def on_event(name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        serve_loop(eng, _requests(cfg, [9], max_new=4, seed=2))
        n0 = len(compiled)
        serve_loop(eng, _requests(cfg, [12], max_new=4, seed=3))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiled[n0:] == []
