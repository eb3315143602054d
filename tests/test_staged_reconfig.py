"""Zero-downtime staged reconfiguration: background pool migration under
concurrent mutation, async executable precompile, atomic commit, and the
tuner's pending-plan protocol.

The migration property under test: interleaving ``begin_migration`` /
``migration_step`` batches with live serving traffic (admissions, COW
writes, decode writes, releases) and then committing must produce a pool
whose *logical* per-slot KV content equals what it was the instant before
the commit — i.e. exactly what the stop-the-world relayout would have
produced — with refcount/table/free-list invariants intact.  Physical
block ids are allowed to differ; logical content is not.
"""
import copy

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.knobs import Knob, KnobSpace
from repro.core.reconfig import plan
from repro.core.tuner import TunerConfig, TuningManager
from repro.models import lm
from repro.serving import (DEFAULT_SERVING_SETTING, SERVING_RELAYOUT_KNOBS,
                           Request, ServingEngine, serve_loop)


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_config("starcoder2-3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _setting(**kw):
    return dict(DEFAULT_SERVING_SETTING, **kw)


def _requests(cfg, lens, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (p,))
                    .astype(np.int32),
                    max_new=max_new, arrival_s=0.0)
            for i, p in enumerate(lens)]


def _reference_tokens(params, cfg, req, max_seq=48):
    eng = ServingEngine(params, cfg, _setting(), max_seq=max_seq)
    serve_loop(eng, [Request(rid=0, prompt=req.prompt.copy(),
                             max_new=req.max_new)])
    return eng.finished[0].tokens_out


def _logical_kv(engine):
    """{slot: {leaf: rows}} — each live slot's KV gathered dense through
    its block table for logical rows [0, written).  This is the content a
    migration must preserve, independent of physical block placement."""
    pool = engine.pool
    out = {}
    for s, req in enumerate(engine.slot_req):
        if req is None:
            continue
        written = int(engine.slot_pos[s])
        if written == 0:
            out[s] = {}
            continue
        bt = np.asarray(pool.tables[s])
        rows = {}
        for k, v in pool.kv.items():
            a = np.asarray(v)                    # (L, nb, K, bs, hd)
            g = a[:, bt].transpose(0, 1, 3, 2, 4).reshape(
                a.shape[0], -1, a.shape[2], a.shape[4])
            rows[k] = np.asarray(g[:, :written], np.float32)
        out[s] = rows
    return out


# -------------------------------------------------- pool-level migration

def test_background_migration_preserves_logical_kv(dense_model):
    """Interleave background-migration batches with live decode traffic
    (every tick dirties the tail blocks the copies race against), then
    commit: the new pool's logical content must equal the pre-commit
    content exactly, and equal what a stop-the-world relayout of a
    deep-copied pool produces."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg,
                        _setting(max_batch=2, block_size=8,
                                 prefix_share=True),
                        max_seq=48)
    for r in _requests(cfg, [5, 12, 17, 9], max_new=10, seed=3):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    assert eng.n_active == 2

    target = _setting(max_batch=4, block_size=8, prefix_share=True)
    assert eng.pool.begin_migration(target)
    # background copies race the decode writes for several ticks: a copied
    # tail block is dirtied again (via _mig_mark) and must be re-copied
    for _ in range(4):
        eng.step()
        eng.pool.migration_step(max_blocks=2)
    while eng.pool.migration_pending() > 0:
        eng.pool.migration_step(max_blocks=4)

    before = _logical_kv(eng)
    prefix_keys = set(eng.pool.prefix)
    shadow = copy.deepcopy(eng.pool)          # stop-the-world witness
    shadow.abort_migration()

    mapping = eng.pool.finish_migration(eng._live_extents())
    assert mapping is not None
    old_req, old_pos, old_tok = eng.slot_req, eng.slot_pos, eng.slot_tok
    eng._reset_slots()
    for old, new in mapping.items():
        eng.slot_req[new] = old_req[old]
        eng.slot_pos[new] = old_pos[old]
        eng.slot_tok[new] = old_tok[old]

    eng.pool.check_invariants()
    assert eng.pool.n_slots == 4
    after = _logical_kv(eng)
    slot_map = {s: mapping[s] for s in before}
    for s, rows in before.items():
        moved = after[slot_map[s]]
        assert set(rows) == set(moved)
        for k in rows:
            np.testing.assert_array_equal(rows[k], moved[k])
    # the stop-the-world relayout of the shadow pool agrees leaf-for-leaf
    shadow_map = shadow.relayout(target,
                                 {s: (int(old_pos[s]),
                                      min(len(old_req[s].prompt)
                                          + old_req[s].max_new, 48))
                                  for s in before})
    for s, rows in before.items():
        bt = np.asarray(shadow.tables[shadow_map[s]])
        for k in rows:
            a = np.asarray(shadow.kv[k])
            g = a[:, bt].transpose(0, 1, 3, 2, 4).reshape(
                a.shape[0], -1, a.shape[2], a.shape[4])
            np.testing.assert_array_equal(
                rows[k], np.asarray(g[:, :rows[k].shape[1]], np.float32))
    # prefix-cache keys survive the migration (same block geometry)
    assert prefix_keys <= set(eng.pool.prefix)

    # the migrated engine keeps serving to completion with correct tokens
    while eng.has_work():
        eng.step()
    assert len(eng.finished) == 4
    for r in eng.finished:
        assert len(r.tokens_out) == r.max_new
        assert r.tokens_out == _reference_tokens(params, cfg, r), \
            f"request {r.rid} diverged across staged migration"


def test_migration_refuses_undrained_shrink(dense_model):
    """finish_migration must refuse (not corrupt) when the live set still
    exceeds the staged slot count; abort restores the old geometry."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, _setting(max_batch=3, block_size=8),
                        max_seq=48)
    for r in _requests(cfg, [8, 8, 8], max_new=8, seed=1):
        eng.submit(r)
    eng.step()
    assert eng.n_active == 3
    assert eng.pool.begin_migration(_setting(max_batch=1, block_size=8))
    while eng.pool.migration_pending() > 0:
        eng.pool.migration_step(max_blocks=8)
    assert eng.pool.finish_migration(eng._live_extents()) is None
    eng.pool.abort_migration()
    eng.pool.check_invariants()
    while eng.has_work():
        eng.step()
    assert all(len(r.tokens_out) == r.max_new for r in eng.finished)


def test_migration_rejects_block_size_change(dense_model):
    """Re-blocking cannot run incrementally; begin_migration says so and
    the caller falls back to the (host-side) stop-the-world relayout."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, _setting(max_batch=2, block_size=8),
                        max_seq=48)
    assert not eng.pool.begin_migration(_setting(max_batch=2,
                                                 block_size=16))


# ----------------------------------------------- engine-level staged path

def test_engine_staged_reconfig_no_token_loss(dense_model):
    """A staged reconfiguration driven through the engine's own pipeline
    (begin_reconfig -> per-tick advance -> commit) mid-serving: every
    request completes with exactly its tokens, the commit event carries
    the background accounting, and outputs match an untouched engine."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg,
                        _setting(max_batch=2, block_size=8,
                                 prefix_share=True),
                        max_seq=48)
    eng.async_precompile = False      # deterministic single-threaded test
    eng.migrate_batch_blocks = 2      # force several interleaved batches
    reqs = _requests(cfg, [5, 12, 17, 9, 21, 7], max_new=8, seed=3)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()

    p = plan(eng.setting,
             _setting(max_batch=4, block_size=8, prefix_share=True),
             mesh_knobs=SERVING_RELAYOUT_KNOBS)
    assert "I-b" in p.kinds
    eng.begin_reconfig(p)
    ticks = 0
    while eng._staged is not None and ticks < 100:
        eng.step()
        ticks += 1
    assert eng._staged is None, "staged reconfig never committed"
    events = eng.take_reconfig_events()
    assert len(events) == 1
    ev = events[0]
    assert ev["plan"] is p and ev["cost_s"] >= 0.0
    assert ev["bg_blocks"] > 0        # migration really ran in batches
    assert eng.setting["max_batch"] == 4 and eng.pool.n_slots == 4
    eng.pool.check_invariants()

    while eng.has_work():
        eng.step()
    assert len(eng.finished) == 6
    for r in eng.finished:
        assert len(r.tokens_out) == r.max_new
        assert r.tokens_out == _reference_tokens(params, cfg, r), \
            f"request {r.rid} diverged across staged reconfig"


def test_engine_staged_shrink_drains_then_commits(dense_model):
    """A staged shrink caps admissions at the target max_batch and waits
    for the live set to drain below it before committing."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, _setting(max_batch=3, block_size=8),
                        max_seq=48)
    eng.async_precompile = False
    for r in _requests(cfg, [8, 8, 8, 8, 8], max_new=6, seed=2):
        eng.submit(r)
    eng.step()
    assert eng.n_active == 3
    p = plan(eng.setting, _setting(max_batch=1, block_size=8),
             mesh_knobs=SERVING_RELAYOUT_KNOBS)
    eng.begin_reconfig(p)
    assert eng._max_batch_cap() == 1        # admissions capped immediately
    ticks = 0
    while eng._staged is not None and ticks < 150:
        eng.step()
        ticks += 1
    assert eng._staged is None
    assert eng.pool.n_slots == 1
    while eng.has_work():
        eng.step()
    assert len(eng.finished) == 5
    assert all(len(r.tokens_out) == r.max_new for r in eng.finished)


def test_engine_cancel_staged_restores_incumbent(dense_model):
    """Cancelling an in-flight staged plan leaves the incumbent pool
    authoritative and serving unaffected."""
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, _setting(max_batch=2, block_size=8),
                        max_seq=48)
    eng.async_precompile = False
    eng.migrate_batch_blocks = 1      # several cold blocks per slot: one
    for r in _requests(cfg, [20, 20], max_new=6, seed=4):   # step cannot
        eng.submit(r)                                       # finish
    eng.step()
    p = plan(eng.setting, _setting(max_batch=4, block_size=8),
             mesh_knobs=SERVING_RELAYOUT_KNOBS)
    eng.begin_reconfig(p)
    eng.step()                                # partially migrated
    assert eng._staged is not None
    got = eng.cancel_staged()
    assert got is p and eng._staged is None
    assert eng.pool._mig is None and eng.pool.n_slots == 2
    eng.pool.check_invariants()
    while eng.has_work():
        eng.step()
    assert all(len(r.tokens_out) == r.max_new for r in eng.finished)


# ------------------------------------------------- tuner pending protocol

def test_tuner_holds_plan_pending_until_commit():
    """maybe_advance() returns no new plan while one is staged; the
    commit report (record_reconfig) confirms it and switches the
    incumbent; abandon_reconfig reopens the window without switching."""
    space = KnobSpace((Knob("a", "ordinal", (1, 2, 4, 8)),))
    cfgs = TunerConfig(eps=1e-9, a=4, b=2, seed=0)

    def drive_until_plan(tuner):
        """Next plan that actually *moves* (init samples can re-propose
        the incumbent; those are committed trivially and skipped)."""
        for _ in range(400):
            tuner.record_iteration(1.0, 0.05)
            p = tuner.maybe_advance()
            if p is not None:
                if p.new == tuner.current:
                    tuner.record_reconfig(p, 0.001)
                    continue
                return p
        raise AssertionError("tuner never proposed")

    tuner = TuningManager(space, {"a": 1}, cfgs)
    p = drive_until_plan(tuner)
    incumbent = dict(tuner.current)
    assert incumbent != p.new               # not adopted yet: pending
    # while pending, iterations keep landing but no second plan appears
    for _ in range(30):
        tuner.record_iteration(1.0, 0.05)
        assert tuner.maybe_advance() is None
    tuner.record_reconfig(p, 0.01)          # commit confirms the switch
    assert tuner.current == p.new

    tuner2 = TuningManager(space, {"a": 1}, cfgs)
    p2 = drive_until_plan(tuner2)
    tuner2.abandon_reconfig(p2)             # driver gave up (run ended)
    assert tuner2.current == {"a": 1}       # incumbent unchanged
    # the tuner resumes proposing after the abandon
    assert drive_until_plan(tuner2) is not None
