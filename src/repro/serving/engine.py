"""Continuous-batching inference engine with online-reconfigurable knobs.

Architecture (the serving half of the paper's Fig. 3):

  * a FIFO request queue with a block-aware admission policy: at most
    ``max_batch`` requests are in flight; while decodes are running, the
    continuous ``admit_budget`` knob meters prefills per scheduling quantum
    (fractional budgets accumulate across quanta); a request is admitted
    only when its *blocks* fit, and a short bounded lookahead lets small
    requests pass a long prompt stuck at the head of the queue;
  * a pluggable ``StatePool`` (repro.serving.pool) holding decode state for
    every model family: paged KV blocks + per-request block tables with
    copy-on-write prompt-prefix sharing for attention families, per-slot
    recurrent state for ssm/hybrid — one engine, no family fallback;
  * interleaved prefill/decode: prefill runs per request at batch 1, padded
    to a multiple of ``prefill_chunk`` (bounds the number of prefill
    executables); a prompt whose prefix is already cached only computes its
    suffix (one multi-token paged decode step against the shared blocks);
    decode advances *all* live slots one token per quantum through the
    pool's indirection — paged attention reads KV blocks in place through
    the block table (kernels/paged_attention on TPU; context-bucketed
    executables on CPU, so short batches never touch dead tail blocks);
  * online reconfiguration: Type II = swap the AOT-compiled decode/prefill
    executables (bounded LRU, shared policy with the training loop); Type
    I-b = ODMR-style pool re-layout — allocate the pool for the new
    ``max_batch``/``block_size``/``cache_dtype``, relocate only the *live*
    blocks/slots, never quiesce the queue.

The engine is knob-driven but tuner-agnostic: ``serve_loop`` wires it to a
TuningManager exactly the way repro.ps.trainer wires the training job.
"""
from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lru import LRUCache, aot_compile
from repro.core.reconfig import (ReconfigPlan, classify as rc_classify,
                                 plan as rc_plan)
from repro.kernels.quant import dequantize_ref, quantize_ref
from repro.obs.trace import NOP_TRACER
from repro.models import lm
from repro.models.lm import ModelKnobs
from repro.serving.knobs import (DEFAULT_SERVING_SETTING,
                                 SERVING_RELAYOUT_KNOBS)
from repro.serving.pool import make_state_pool, pool_dtype


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32 token ids
    max_new: int                  # tokens to generate (>= 1)
    arrival_s: float = 0.0        # virtual arrival time (trace replay)
    # engine-filled, on the engine's clock (``ServingEngine._now``):
    submit_s: float | None = None
    admit_s: float | None = None      # its own admission began
    first_token_s: float | None = None  # first token read to the host
    done_s: float | None = None       # last token read to the host
    tokens_out: list = field(default_factory=list)

    @property
    def latency_s(self) -> float | None:
        return None if self.done_s is None else self.done_s - self.arrival_s

    @property
    def ttft_s(self) -> float | None:
        return (None if self.first_token_s is None
                else self.first_token_s - self.arrival_s)


def decode_fn(cfg, ms, kn: ModelKnobs):
    """The engine's decode step, one function for every decode executable
    (its name names the compiled module: ``jit_serve_decode``)."""
    def serve_decode(params, cache, tok, pos):
        logits, new_cache = lm.decode_step(params, cache, tok, pos, cfg, ms,
                                           kn)
        # pin state dtypes to the pool's (ssm conv windows come back in
        # compute dtype) so the AOT signature is a fixed point
        new_cache = jax.tree_util.tree_map(lambda n, o: n.astype(o.dtype),
                                           new_cache, cache)
        return logits, new_cache

    return serve_decode


class ServingEngine:
    SUPPORTED_FAMILIES = ("dense", "moe", "vlm", "ssm", "hybrid")
    ADMIT_LOOKAHEAD = 4           # queue positions scanned past a head
                                  # request whose blocks don't fit yet

    def __init__(self, params, cfg, setting: dict | None = None, *,
                 max_seq: int = 96, ms=None, step_cache_size: int = 24,
                 block_overcommit: float | None = None,
                 attn_impl: str = "paged", tracer=None):
        if cfg.family not in self.SUPPORTED_FAMILIES:
            raise NotImplementedError(
                f"serving engine supports {self.SUPPORTED_FAMILIES}; "
                f"got family={cfg.family!r} (encoder-only models have no "
                f"decode step)")
        self.params = params
        self.cfg = cfg
        self.ms = ms
        self.max_seq = max_seq
        # paged decode implementation: "paged" reads KV blocks through the
        # block table (kernels/paged_attention; context-bucketed on CPU),
        # "gather" is the pre-kernel dense-gather path (bench ablation arm)
        self.attn_impl = attn_impl
        self.setting = dict(DEFAULT_SERVING_SETTING)
        self.setting.update(setting or {})
        if block_overcommit is not None:    # explicit override of the knob
            self.setting["block_overcommit"] = block_overcommit
        # observability: nested spans on the hot paths (default: the
        # shared zero-overhead no-op tracer)
        self.tr = tracer or NOP_TRACER
        # compiled executables, bounded-LRU (same policy as the trainer):
        # decode per (pool layout, context bucket), prefill per (bucket,
        # k_chunk), chunked shared-prefix prefill per (bucket, pool layout)
        self._steps = LRUCache(step_cache_size)
        self._steps.tracer = self.tr
        self.queue: deque[Request] = deque()
        self.pool = make_state_pool(cfg, self.setting, max_seq, ms)
        self._reset_slots()
        self.clock = 0.0              # driver-supplied wall time
        self._step_t0 = time.perf_counter()   # host clock at step start
        self._admit_acc = 0.0         # fractional admit_budget carry
        # accounting (invariants are tested against these)
        self.submitted: list[int] = []
        self.finished: list[Request] = []
        self.total_tokens = 0
        self.ticks = 0
        self.prefill_tokens_computed = 0   # tokens actually prefilled
        self.prefill_tokens_total = 0      # tokens the prompts contained
        self.decode_time_s = 0.0           # wall time inside decode execs
        self.decode_tokens = 0             # tokens those execs produced
        # speculative decoding (spec_k / drafter are Type II knobs: the
        # drafter holds host token histories only, never device state)
        self.spec_drafted = 0              # draft tokens proposed
        self.spec_accepted = 0             # draft tokens verified-accepted
        self.spec_ticks = 0                # speculative decode quanta
        self._drafters: dict = {}          # drafter name -> instance
        self._drafter_seed = 0
        # speculative-verify executables warm lazily off the tick path:
        # speculation is an optimisation, so a cold S > 1 executable must
        # neither stall a tick nor gate a reconfig commit — the engine
        # serves the plain one-token path until the background build folds
        self._spec_warm_pending: set = set()   # keys building (or failed)
        self._spec_warm_done: list = []        # (key, exec|error, build_s)
        self._spec_threads: list = []          # their build threads
        # background builds the compiler refused: counted and warned, never
        # swallowed — a refused verify executable means speculation is off
        self.failed_builds = 0
        self.last_reconfig_breakdown = {}  # measured per-kind s, last plan
        self.last_reconfig_scales = {}     # units migrated, last plan
        # staged (zero-downtime) reconfiguration — begin_reconfig stages a
        # plan, ticks precompile + migrate in the background, and a commit
        # event is queued for the driver (serve_loop) to report to the tuner
        self._staged: dict | None = None
        self._reconfig_events: list[dict] = []
        self.async_precompile = True       # False: build inline (tests)
        self.migrate_batch_blocks = 8      # bg blocks copied per tick
        self.migrate_drain_ticks = 200     # shrink-drain bail-out to the
                                           # stop-the-world relayout

    def _reset_slots(self):
        n = self.pool.n_slots
        self.slot_req: list[Request | None] = [None] * n
        self.slot_pos = np.zeros(n, np.int32)   # next KV/state write position
        self.slot_tok = np.zeros(n, np.int32)   # last sampled token

    # ----------------------------------------------------------- properties
    @property
    def n_slots(self) -> int:
        return self.pool.n_slots

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def load(self) -> int:
        return self.n_active + self.queue_depth

    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    # ----------------------------------------------------------- lifecycle
    def set_tracer(self, tracer):
        """Attach (or, with NOP_TRACER, detach) a tracer.  The executable
        cache shares it so compile time is attributed wherever it
        actually fires — inside a reconfiguration window when warmed,
        inside a tick when a cold path slips through."""
        self.tr = tracer
        self._steps.tracer = tracer

    def _now(self) -> float:
        """The engine's clock: the step's ``now`` plus the host time
        elapsed in the step so far (request stamps)."""
        return self.clock + (time.perf_counter() - self._step_t0)

    def submit(self, req: Request, now: float | None = None):
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        if len(req.prompt) + req.max_new > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt({len(req.prompt)}) + "
                f"max_new({req.max_new}) exceeds max_seq({self.max_seq})")
        req.submit_s = self.clock if now is None else now
        self.queue.append(req)
        self.submitted.append(req.rid)

    # ----------------------------------------------------- compiled steps
    def _ctx_buckets(self) -> tuple:
        """Context buckets for the paged decode step: numbers of visible
        block-table columns the decode executable is specialized on (the
        same shape-bucketing the engine applies to prefill lengths).  The
        engine knows every slot's write position on the host, so each tick
        runs the smallest executable whose bucket covers the batch — the
        paged-attention kernel's only-live-blocks property with zero
        runtime control flow.  At most 6 buckets per pool geometry bounds
        the executable count; 0 = full table (ssm pools, gather path)."""
        if self.pool.kind != "paged" or self.attn_impl == "gather":
            return (0,)
        return self._ctx_buckets_for(self.pool.mb)

    def _ctx_buckets_for(self, mb: int) -> tuple:
        if self.attn_impl == "gather":
            return (0,)
        g = -(-mb // 6)
        return tuple(sorted({min(t * g, mb) for t in range(1, 7)}))

    def _ctx_cols(self, last_pos: int) -> int:
        """Smallest context bucket covering logical position ``last_pos``.
        Submit-time validation keeps decode positions below max_seq - 1,
        so the full table always covers; the clamp is defense in depth."""
        buckets = self._ctx_buckets()
        if buckets == (0,):
            return 0
        need = min(last_pos // self.pool.bs + 1, self.pool.mb)
        return next(c for c in buckets if c >= need)

    def _decode_exec(self, ctx_cols: int = 0, s: int = 1):
        """Decode executable: ``s`` query tokens per slot per call (s = 1 is
        the classic decode step; s = spec_k + 1 is the speculative verify
        step — one batched multi-token paged decode over draft tokens)."""
        key = ("decode", self.attn_impl, ctx_cols, s) + self.pool.exec_key()
        # AOT: compile inside the reconfig window, not mid-tick
        return self._steps.get_or_create(
            key, lambda: self._decode_spec(ctx_cols, s)[1]())

    def _target_geometry(self, setting: dict) -> dict:
        """The canonical paged-pool geometry ``make_state_pool(setting)``
        lands on (n_slots = max_batch, dense-worst-case block count) —
        what a staged migration double-buffers into and what the async
        precompile builds executables against, so the committed pool hits
        exactly the warmed executable keys."""
        bs = int(setting["block_size"])
        mb = -(-self.max_seq // bs)
        n_slots = max(int(setting["max_batch"]), 1)
        return {"bs": bs, "mb": mb, "n_slots": n_slots,
                "nb": n_slots * mb + 1, "dtype": pool_dtype(setting),
                "cache_dtype": setting.get("cache_dtype")}

    def _decode_spec(self, cols: int, s: int = 1, geom: dict | None = None):
        """(LRU key, build fn) of the decode executable for the live pool
        or, given ``geom``, for a *future* paged-pool geometry.  The build
        closes over shapes only (ShapeDtypeStructs, snapshotted here on
        the caller's thread), never the live pool — which is what makes it
        safe to run on a background thread while the tick path keeps
        decoding.  Every decode path compiles ``serve_decode`` under a
        key of the same form, so a staged or background build is the
        executable the tick path would have built."""
        if geom is None:
            pool_key = self.pool.exec_key()
            n = self.pool.n_slots
            # no sharding: lowered as the pool's uncommitted arrays are, so
            # the step's outputs stay uncommitted too and the eager ops of
            # admission (write_kv's scatter) hit what warm-up compiled
            cache = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.pool.decode_cache())
        else:
            pool_key = ("paged", geom["n_slots"], geom["nb"], geom["bs"],
                        geom["cache_dtype"])
            n = geom["n_slots"]
            shapes = lm.init_paged_cache_shapes(self.cfg, geom["nb"],
                                                geom["bs"])
            cache = {k: jax.ShapeDtypeStruct(sh.shape, geom["dtype"])
                     for k, sh in shapes.items()}
            cache["block_tables"] = jax.ShapeDtypeStruct((n, geom["mb"]),
                                                         jnp.int32)
        key = ("decode", self.attn_impl, cols, s) + pool_key
        fn = decode_fn(self.cfg, self.ms,
                       ModelKnobs(attn_impl=self.attn_impl, attn_ctx=cols))
        params = self.params
        tok = jax.ShapeDtypeStruct((n, s), jnp.int32)
        pos = jax.ShapeDtypeStruct((n,), jnp.int32)
        return key, lambda: aot_compile(fn, params, cache, tok, pos)

    def _prefill_exec(self, bucket: int):
        key = ("prefill", bucket, self.setting["k_chunk"])

        def build():
            cfg, ms = self.cfg, self.ms
            kn = ModelKnobs(k_chunk=self.setting["k_chunk"])

            def serve_prefill(params, tokens, last_idx):
                # valid_len: SSM families must not fold right-pad tokens
                # into the recurrent state (attention ignores it)
                hidden, _, cache = lm.forward(params, {"tokens": tokens},
                                              cfg, ms, kn, mode="prefill",
                                              valid_len=last_idx + 1)
                last = jax.lax.dynamic_slice_in_dim(hidden, last_idx, 1,
                                                    axis=1)
                return lm.logits_fn(params, last, cfg, ms)[:, 0], cache

            tk = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            ix = jax.ShapeDtypeStruct((), jnp.int32)
            return aot_compile(serve_prefill, self.params, tk, ix)

        return self._steps.get_or_create(key, build)

    def _chunk_prefill_exec(self, bucket: int):
        """Chunked prefill against shared prefix blocks: the suffix of a
        prompt whose prefix is shared runs one multi-token paged decode
        step — queries attend the prior blocks *through the block table*
        (models.attention.paged_decode_attention; the Pallas kernel's
        multi-token form on TPU) and write their own KV straight into the
        slot's blocks.  No dense prior is materialized; COW for shared
        blocks in the write range is resolved by the caller *before* the
        step runs."""
        key = ("chunkpf", bucket, self.attn_impl) + self.pool.exec_key()

        def build():
            cfg, ms = self.cfg, self.ms
            kn = ModelKnobs(attn_impl=self.attn_impl)

            def serve_chunk_prefill(params, cache, tokens, start, last_idx):
                # project only the last real suffix position to logits —
                # a full (bucket, vocab) projection would cost bucket x
                # the FLOPs for one usable row (same trick as _prefill_exec)
                hidden, _, new_cache = lm.forward(
                    params, {"tokens": tokens}, cfg, ms, kn, mode="decode",
                    cache=cache, pos=start)
                last = jax.lax.dynamic_slice_in_dim(hidden, last_idx, 1,
                                                    axis=1)
                return lm.logits_fn(params, last, cfg, ms)[:, 0], new_cache

            pool_kv = self.pool.decode_cache()
            cache = {"k": pool_kv["k"], "v": pool_kv["v"],
                     "block_tables":
                         jax.ShapeDtypeStruct((1, self.pool.mb), jnp.int32)}
            tk = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            st = jax.ShapeDtypeStruct((1,), jnp.int32)
            ix = jax.ShapeDtypeStruct((), jnp.int32)
            return aot_compile(serve_chunk_prefill, self.params, cache, tk,
                               st, ix)

        return self._steps.get_or_create(key, build)

    # -------------------------------------------------------------- admit
    def _bucket(self, plen: int, chunk: int | None = None) -> int:
        chunk = chunk or self.setting["prefill_chunk"]
        return min(-(-plen // chunk) * chunk, self.max_seq)

    def _quant_exec(self, n: int):
        """int8 KV storage: per-(layer,position) blockwise quantization via
        the kernels/quant schedule (jnp oracle on CPU).  Compiled per row
        count — a variable-length eager version would trigger per-prompt
        XLA op compiles on every admission."""
        key = ("quant", n)

        def build():
            block = max(self.cfg.n_kv_heads * self.cfg.hd, 1)

            def serve_quant(kv):             # (L, n, K, hd)
                flat = kv.reshape(-1).astype(jnp.float32)
                half = jnp.full(flat.shape, 0.5, jnp.float32)  # det. rounding
                q, scales = quantize_ref(flat, half, block=block)
                return dequantize_ref(q, scales, block=block).reshape(kv.shape)

            return jax.jit(serve_quant)

        return self._steps.get_or_create(key, build)

    def _try_admit(self, req: Request) -> bool:
        with self.tr.span("serve.admit", rid=req.rid, plen=len(req.prompt)):
            return self._admit(req)

    def _admit(self, req: Request) -> bool:
        t_admit = self._now()
        res = self.pool.try_admit(req.prompt, req.max_new)
        if res is None:
            return False
        slot, shared = res
        req.admit_s = t_admit
        self.tr.tag(shared=shared)
        P = len(req.prompt)
        if shared > 0:
            # shared-prefix fast path: prefill only the suffix as one
            # multi-token *paged* decode step — queries attend the shared
            # blocks through the block table and write their own KV in
            # place.  COW runs first: it covers in-range writes into
            # shared blocks, including the case where the whole prompt
            # matched and the last token re-lands in a shared block.
            # (Bucket-pad positions write into the slot's reserved/trash
            # blocks; decode re-writes them before any query can see them.)
            sfx = req.prompt[shared:]
            n = len(sfx)
            bucket = self._bucket(n)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = sfx
            self.pool.prepare_write(slot, shared, P)
            pool_kv = self.pool.decode_cache()
            cache = {"k": pool_kv["k"], "v": pool_kv["v"],
                     "block_tables": jnp.asarray(
                         self.pool.tables[slot:slot + 1], jnp.int32)}
            with self.tr.span("serve.chunk_prefill", bucket=bucket,
                              suffix=n, shared=shared):
                logits, newc = self._chunk_prefill_exec(bucket)(
                    self.params, cache, jnp.asarray(padded),
                    jnp.asarray([shared], jnp.int32),
                    jnp.asarray(n - 1, jnp.int32))
                self.pool.set_cache(newc)
                tok = int(jnp.argmax(logits[0]))
            if self.setting["quant"] == "int8":
                # re-quantize the freshly written suffix rows in place, at
                # bucket granularity (blockwise per-position quant, so
                # quant-then-slice == slice-then-quant) to hit the warmed
                # ("quant", bucket) executables instead of per-length
                # compiles; rows past the cache boundary are zero-padded
                # back to the bucket — pad positions form their own quant
                # blocks and are discarded by the bounded write below
                with self.tr.span("serve.quant", bucket=bucket):
                    m = min(bucket, self.max_seq - shared)
                    pos = np.arange(shared, shared + m)
                    blk = jnp.asarray(
                        self.pool.tables[slot, pos // self.pool.bs])
                    off = jnp.asarray(pos % self.pool.bs)
                    kv = {k: self.pool.kv[k][:, blk, :, off]
                          .transpose(1, 0, 2, 3)         # (L, m, K, hd)
                          for k in ("k", "v")}
                    if m < bucket:
                        kv = {k: jnp.pad(v, ((0, 0), (0, bucket - m),
                                             (0, 0), (0, 0)))
                              for k, v in kv.items()}
                    kv = {k: self._quant_exec(bucket)(v)
                          for k, v in kv.items()}
                    self.pool.write_kv(slot,
                                       {k: v[:, :n] for k, v in kv.items()},
                                       start=shared)
            self.prefill_tokens_computed += n
        else:
            bucket = self._bucket(P)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :P] = req.prompt
            with self.tr.span("serve.prefill", bucket=bucket, plen=P):
                logits, pcache = self._prefill_exec(bucket)(
                    self.params, jnp.asarray(padded),
                    jnp.asarray(P - 1, jnp.int32))
                if self.pool.kind == "paged":
                    with self.tr.span("pool.write_kv", tokens=P):
                        kv = {k: pcache[k][:, 0] for k in ("k", "v")}
                        if self.setting["quant"] == "int8":
                            with self.tr.span("serve.quant", bucket=bucket):
                                kv = {k: self._quant_exec(bucket)(v)
                                      for k, v in kv.items()}
                        self.pool.write_kv(slot, {k: v[:, :P]
                                                  for k, v in kv.items()},
                                           start=0)
                else:
                    self.pool.write_prefill(slot, pcache, P)
                tok = int(jnp.argmax(logits[0]))
            self.prefill_tokens_computed += P
        self.prefill_tokens_total += P
        req.tokens_out = [tok]
        req.first_token_s = self._now()
        self.total_tokens += 1
        self.slot_req[slot] = req
        self.slot_pos[slot] = P
        self.slot_tok[slot] = tok
        if len(req.tokens_out) >= req.max_new:
            self._complete(slot)
        return True

    def _complete(self, slot: int):
        req = self.slot_req[slot]
        req.done_s = self._now()
        self.finished.append(req)
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0       # stale positions must not inflate the
        self.pool.release(slot)       # next tick's decode context bucket
        for d in self._drafters.values():
            d.release(slot)

    # ------------------------------------------------- speculative decoding
    @staticmethod
    def _spec_k_of(setting: dict) -> int:
        """Resolve the continuous ``spec_k`` knob to a draft length: the
        tuner proposes floats in [0, 4]; the engine rounds and clamps.
        0 = speculation off (the plain one-token decode path)."""
        return max(0, min(int(round(float(setting.get("spec_k", 0.0)
                                          or 0.0))), 4))

    def _spec_k(self) -> int:
        return self._spec_k_of(self.setting)

    def _drafter(self):
        name = self.setting.get("drafter", "ngram")
        d = self._drafters.get(name)
        if d is None:
            from repro.serving.drafter import make_drafter
            d = make_drafter(name, self.params, self.cfg, self.ms,
                             vocab=self.cfg.vocab_size,
                             seed=self._drafter_seed)
            self._drafters[name] = d
        return d

    def reset_drafters(self, seed: int = 0):
        """Drop all drafter state and reseed.  Bench arms call this next to
        reset_prefix_cache() so n-gram lookup tables never leak across arms
        and RNG-fallback draws are deterministic per scenario seed."""
        self._drafter_seed = int(seed)
        self._drafters = {}

    def _spec_exec_ready(self, cols: int, s: int) -> bool:
        """True when the S = ``s`` speculative-verify executable for this
        context bucket is warm.  On a miss: build inline when
        ``async_precompile`` is off (tests), else kick one daemon build
        thread per key and report not-ready — the tick falls back to the
        plain one-token decode until the build folds, so a spec_k flip
        commits instantly (Type II) and never pays a mid-tick compile.  A
        failed build is counted in ``failed_builds`` and warned about, and
        its key stays parked in ``_spec_warm_pending`` so a deterministic
        compile failure is not retried every tick."""
        key = ("decode", self.attn_impl, cols, s) + self.pool.exec_key()
        if key in self._steps:
            return True
        if not self.async_precompile:
            self._decode_exec(cols, s)
            return True
        if key not in self._spec_warm_pending:
            self._spec_warm_pending.add(key)
            _, build = self._decode_spec(cols, s)
            out = self._spec_warm_done

            def worker():
                t0 = time.perf_counter()
                try:
                    ex = build()
                except Exception as e:      # reported by _fold_spec_warm
                    ex = e
                out.append((key, ex, time.perf_counter() - t0))

            th = threading.Thread(target=worker, daemon=True)
            self._spec_threads = [t for t in self._spec_threads
                                  if t.is_alive()] + [th]
            th.start()
        return False

    def join_builds(self):
        """Wait for the verify builds still running in the background and
        fold them in, failures counted: before reading ``failed_builds``
        as final, and before the process exits (a daemon thread killed
        inside the compiler aborts the process)."""
        for th in self._spec_threads:
            th.join()
        self._spec_threads = []
        self._fold_spec_warm()

    def _fold_spec_warm(self):
        """Absorb finished background spec-executable builds (tick path;
        list.append/pop are atomic under the GIL)."""
        while self._spec_warm_done:
            key, ex, dur = self._spec_warm_done.pop()
            if isinstance(ex, Exception):
                self._build_failed(key, ex)
                continue
            self._spec_warm_pending.discard(key)
            self._steps.absorb(key, ex, dur)
            self.tr.record("exec.precompile_bg", dur, key=str(key))

    def _build_failed(self, key, err: Exception):
        self.failed_builds += 1
        warnings.warn(f"background compile of {key} failed: {err!r}",
                      RuntimeWarning, stacklevel=2)

    # ---------------------------------------------------------------- tick
    def step(self, now: float | None = None) -> dict:
        """One scheduling quantum.  Returns tick metrics for the driver.
        ``now``: the caller's wall time; without it the engine's clock
        runs on from the previous step by host time."""
        self.clock = self._now() if now is None else now
        self._step_t0 = time.perf_counter()
        with self.tr.span("serve.tick", queued=len(self.queue)):
            return self._tick()

    def _tick(self) -> dict:
        t0 = time.perf_counter()
        self.ticks += 1
        tokens = 0

        # admission: fill an idle engine greedily; while decodes run, the
        # continuous admit_budget knob meters prefills per quantum
        admitted = refused = 0
        had_decodes = self.n_active > 0
        if had_decodes:
            ab = float(self.setting.get("admit_budget", 1.0))
            self._admit_acc = min(self._admit_acc + ab, max(ab, 4.0))
            budget = int(self._admit_acc)
            self._admit_acc -= budget
        else:
            self._admit_acc = 0.0
            budget = self._max_batch_cap()
        while (self.queue and budget > 0
               and self.n_active < self._max_batch_cap()):
            ok = False
            # block-aware lookahead: a long prompt whose blocks don't fit
            # yet must not strand free slots for the small requests behind it
            for i in range(min(len(self.queue), self.ADMIT_LOOKAHEAD)):
                if self._try_admit(self.queue[i]):
                    del self.queue[i]
                    ok = True
                    break
                refused += 1
            if not ok:
                break
            admitted += 1
            tokens += 1
            budget -= 1
        self.tr.tag(admitted=admitted, admit_refused=refused,
                    active=self.n_active)

        # decode: advance every live slot.  With spec_k == 0 each slot
        # moves one token per quantum; with spec_k > 0 the drafter proposes
        # k tokens per slot and ONE multi-token paged decode verifies them
        # (speculative greedy decoding — output is token-for-token the
        # plain greedy output).  The executable is picked per context
        # bucket: the batch's highest write position (host state) decides
        # how many block-table columns the paged attention reads — short
        # batches never touch dead tail blocks
        if self.n_active > 0:
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
            self._fold_spec_warm()
            k = self._spec_k()
            if k > 0:
                # speculate only once the verify executable is warm; a
                # cold one builds in the background while this tick (and
                # the next few) take the plain path below
                cols = self._ctx_cols(int(self.slot_pos[active].max()) + k)
                if not self._spec_exec_ready(cols, k + 1):
                    k = 0
            if k > 0:
                tokens += self._spec_decode(active, k)
            else:
                self.pool.prepare_step_writes(active, self.slot_pos)
                tok = jnp.asarray(self.slot_tok[:, None])
                pos = jnp.asarray(self.slot_pos)
                cols = self._ctx_cols(int(self.slot_pos[active].max()))
                with self.tr.span("serve.decode", batch=len(active),
                                  cols=cols):
                    t_dec = time.perf_counter()
                    logits, new_cache = self._decode_exec(cols)(
                        self.params, self.pool.decode_cache(), tok, pos)
                    jax.block_until_ready(logits)
                    self.decode_time_s += time.perf_counter() - t_dec
                    self.decode_tokens += len(active)
                with self.tr.span("serve.sample", batch=len(active)):
                    self.pool.set_cache(new_cache)
                    nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                     np.int32)
                    for slot, req in enumerate(self.slot_req):
                        if req is None:
                            continue
                        self.slot_pos[slot] += 1
                        self.slot_tok[slot] = nxt[slot]
                        req.tokens_out.append(int(nxt[slot]))
                        tokens += 1
                        self.total_tokens += 1
                        if (len(req.tokens_out) >= req.max_new
                                or self.slot_pos[slot] >= self.max_seq - 1):
                            self._complete(slot)

        # staged reconfiguration: fold finished precompiles, copy one
        # background-migration batch, commit when warm + fully copied
        if self._staged is not None:
            self._advance_staged()

        # a shrink that had to wait for live slots (relayout keeps every
        # in-flight request) completes once the backlog drains; otherwise
        # decode keeps paying for an oversized pool.  Deferred while a
        # staged reconfiguration is in flight — its commit lands the pool
        # on the target geometry itself.
        if (self._staged is None
                and self.pool.n_slots > self.setting["max_batch"]
                and self.n_active <= self.setting["max_batch"]):
            self._relayout_pool()

        dt = time.perf_counter() - t0
        return {"dt": dt, "tokens": tokens, "active": self.n_active,
                "queued": self.queue_depth, "load": self.load,
                "idle": tokens == 0 and not self.has_work()}

    def _spec_decode(self, active: list, k: int) -> int:
        """One speculative decode quantum: draft k tokens per live slot,
        verify all of them in ONE batched S = k+1 paged decode against the
        target model, commit the accepted prefix plus the target's own
        next token, and roll the rejected tail back.

        Greedy parity by construction: token j is emitted only if it is
        the target argmax at its position given the previously committed
        tokens (the accept loop stops at the first draft mismatch, and the
        token emitted there is the target argmax itself).  KV rows for
        rejected positions were written during verify, but decode always
        writes rows in-step before attention reads them and masking is
        kvp <= qp, so stale rows are overwritten before any query can see
        them — rollback only has to restore *pool bookkeeping*: for paged
        pools the deferred-COW records (shared blocks must not be copied
        away from their prefix-cache key by a rejected write), for ssm
        pools the recurrent state (snapshot + replay of accepted tokens).
        """
        S = k + 1
        drafter = self._drafter()
        tok = np.zeros((self.n_slots, S), np.int32)
        with self.tr.span("decode.draft", batch=len(active), k=k,
                          drafter=drafter.name):
            for s in active:
                req = self.slot_req[s]
                drafter.update(s, req.rid, req.prompt, req.tokens_out)
                tok[s, 0] = self.slot_tok[s]
                tok[s, 1:] = drafter.propose(s, k)
        self.spec_ticks += 1
        self.spec_drafted += k * len(active)

        pos0 = self.slot_pos.copy()          # pre-tick write positions
        recs = {}
        state_old = None
        if self.pool.kind == "paged":
            # COW over the whole speculative write range [P, P+S), with
            # shared-block releases DEFERRED so the rollback can restore
            # the original block when the write turns out rejected
            for s in active:
                p = int(pos0[s])
                recs[s] = self.pool.prepare_spec_write(
                    s, p, min(p + S, self.max_seq))
        else:
            state_old = self.pool.decode_cache()   # functional snapshot

        cols = self._ctx_cols(int(pos0[active].max()) + k)
        with self.tr.span("decode.verify", batch=len(active), cols=cols,
                          s=S):
            t_dec = time.perf_counter()
            logits, new_cache = self._decode_exec(cols, S)(
                self.params, self.pool.decode_cache(), jnp.asarray(tok),
                jnp.asarray(pos0))
            jax.block_until_ready(logits)
            self.decode_time_s += time.perf_counter() - t_dec

        emitted = 0
        accepted_len = {}                    # slot -> tokens emitted (a+1)
        done = []
        with self.tr.span("serve.sample", batch=len(active)):
            self.pool.set_cache(new_cache)
            nxt = np.asarray(jnp.argmax(logits, axis=-1),
                             np.int32)                        # (n, S)
            for s in active:
                req = self.slot_req[s]
                p = int(pos0[s])
                # emission cap: never emit past max_new, and keep the
                # next write position below max_seq - 1 (the submit-time
                # contract)
                cap = min(req.max_new - len(req.tokens_out),
                          self.max_seq - 1 - p)
                a = 0
                while a < k and a + 1 < cap and tok[s, a + 1] == nxt[s, a]:
                    a += 1
                for j in range(a + 1):
                    req.tokens_out.append(int(nxt[s, j]))
                self.spec_accepted += a
                emitted += a + 1
                self.total_tokens += a + 1
                self.decode_tokens += a + 1
                accepted_len[s] = a + 1
                self.slot_pos[s] = p + a + 1
                self.slot_tok[s] = nxt[s, a]
                if (len(req.tokens_out) >= req.max_new
                        or self.slot_pos[s] >= self.max_seq - 1):
                    done.append(s)

        with self.tr.span("decode.rollback", batch=len(active)):
            if self.pool.kind == "paged":
                # must run before _complete: release() frees the slot's
                # blocks, and the deferred-COW decrements settle refcounts
                for s in active:
                    self.pool.commit_spec_write(
                        s, recs[s], int(pos0[s]) + accepted_len[s])
            else:
                self._ssm_replay(active, accepted_len, state_old, tok,
                                 pos0, S)
        for s in done:
            self._complete(s)
        return emitted

    def _ssm_replay(self, active, accepted_len, state_old, tok, pos0, S):
        """Recurrent-state rollback: snapshot + replay.  Slots that
        accepted the full draft keep the verify step's final state; every
        other slot's state is recomputed from the pre-tick snapshot by
        re-running exactly its accepted tokens, batched per distinct
        accepted length (ssm pools bucket context at 0, so each length is
        at most one extra executable, L in 1..k)."""
        partial = sorted({accepted_len[s] for s in active
                          if accepted_len[s] < S})
        if not partial:
            return
        cur = self.pool.decode_cache()
        pos = jnp.asarray(pos0)
        for L in partial:
            slots = [s for s in active if accepted_len[s] == L]
            _, st = self._decode_exec(0, L)(
                self.params, state_old, jnp.asarray(tok[:, :L]), pos)
            idx = jnp.asarray(slots)
            for leaf in cur:      # every ssm/hybrid leaf has slot on axis 1
                cur[leaf] = cur[leaf].at[:, idx].set(
                    st[leaf][:, idx].astype(cur[leaf].dtype))
        self.pool.set_cache(cur)

    # ------------------------------------------------------------ reconfig
    def warm_start(self, space=None, max_prompt: int | None = None):
        """Pre-compile the executables the knob space can reach (server
        startup warmup, standard serving practice): decode per pool layout
        (max_batch, cache_dtype, block_size), prefill per (bucket, k_chunk),
        chunked shared-prefix prefill per (bucket, cache_dtype).  After
        this, online Type II reconfigurations are warm executable swaps —
        the regime the decaying ReconfigCostModel is built to track.
        ``space=None`` warms only the current (frozen) setting."""
        assert self.n_active == 0, "warm_start before serving, not during"
        if space is None:
            values = {k: (v,) for k, v in self.setting.items()}
        else:
            # continuous knobs (admit_budget) never change an executable
            values = {k.name: (k.values if k.kind != "continuous"
                               else (self.setting.get(k.name),))
                      for k in space.knobs}
        save_setting = dict(self.setting)
        paged = self.pool.kind == "paged"
        chunks = values.get("prefill_chunk", (save_setting["prefill_chunk"],))
        hi = min(max_prompt or self.max_seq, self.max_seq)
        buckets = sorted({self._bucket(p, c)
                          for c in chunks for p in range(1, hi + 1)})
        mbs = values.get("max_batch", (save_setting["max_batch"],))
        cds = values.get("cache_dtype", (save_setting["cache_dtype"],))
        bss = (values.get("block_size", (save_setting["block_size"],))
               if paged else (None,))
        kcs = values.get("k_chunk", (save_setting["k_chunk"],))
        share = paged and any(values.get("prefix_share", (False,)))
        # everything warmed must fit, or we would evict what we just built
        # (decode is warmed per context bucket, <= 6 per pool geometry;
        # shared-prefix chunk prefill per (pool geometry, length bucket))
        geoms = len(mbs) * len(cds) * len(bss)
        # spec_k is continuous (current-value-only here); a nonzero current
        # value needs the S = k+1 verify executable per context bucket too
        spec_s = self._spec_k_of(save_setting) + 1
        planned = (geoms * 6 * (2 if spec_s > 1 else 1)
                   + len(kcs) * len(buckets)
                   + (geoms * len(buckets) if share else 0)
                   + (len(buckets) if "int8" in values.get("quant", ())
                      else 0))
        self._steps.capacity = max(self._steps.capacity, planned + 2)
        for mb in mbs:
            for cd in cds:
                for bsz in bss:
                    self.setting.update(max_batch=mb, cache_dtype=cd)
                    if bsz is not None:
                        self.setting["block_size"] = bsz
                    self.pool = make_state_pool(
                        self.cfg, self.setting, self.max_seq, self.ms)
                    for cols in self._ctx_buckets():
                        self._decode_exec(cols)
                        if spec_s > 1:
                            self._decode_exec(cols, spec_s)
                    if share:
                        for b in buckets:
                            self._chunk_prefill_exec(b)
        for kc in kcs:
            self.setting["k_chunk"] = kc
            for b in buckets:
                self._prefill_exec(b)
        if "int8" in values.get("quant", ()):
            for b in buckets:
                self._quant_exec(b)
        self.setting = save_setting
        self.pool = make_state_pool(self.cfg, self.setting, self.max_seq,
                                    self.ms)
        self._reset_slots()

    def reconfigure(self, new_setting: dict) -> float:
        """Plan + execute a switch to ``new_setting`` (classifying the
        engine's pool knobs as Type I-b).  Returns the observed cost."""
        p = rc_plan(self.setting, dict(new_setting),
                    mesh_knobs=SERVING_RELAYOUT_KNOBS)
        return self.apply_plan(p)

    def apply_plan(self, plan: ReconfigPlan) -> float:
        """Execute a reconfiguration; returns its observed cost (seconds).

        Type I-b: ODMR-style pool re-layout (new ``max_batch`` /
        ``block_size`` / ``cache_dtype``) — only live blocks/slots relocate
        into the new pool, the queue keeps filling, nothing is dropped.
        Type II: the decode executable for the new setting is AOT-compiled
        inside this window (policy-only knobs like ``admit_budget`` and
        ``prefix_share`` take effect immediately).

        The relayout decision is re-derived here with the engine's own knob
        classes rather than trusted from ``plan.kinds`` — a tuner wired
        without them would otherwise leave the pool behind the setting.
        """
        with self.tr.span("reconfig.apply", kinds=",".join(plan.kinds)):
            t0 = time.perf_counter()
            kinds = rc_classify(self.setting, plan.new,
                                mesh_knobs=SERVING_RELAYOUT_KNOBS)
            self.setting.update(plan.new)
            relayout_s = 0.0
            if "I-b" in kinds:
                r0 = time.perf_counter()
                self._relayout_pool()
                relayout_s = time.perf_counter() - r0
            else:
                self.pool.update_policy(self.setting)    # policy knobs
            # warm the hot-path executables for the new setting (SSR): every
            # context bucket, so no decode tick pays a cold compile (the
            # speculative-verify width warms lazily via _spec_exec_ready —
            # it must not stretch the synchronous reconfig window)
            for cols in self._ctx_buckets():
                self._decode_exec(cols)
            jax.block_until_ready(self.pool.decode_cache())
            # measured per-kind breakdown: the I-b portion is the timed
            # relayout, everything else (executable swap, warmup, barrier)
            # is Type II work.  ReconfigCostModel.observe takes this over
            # prior-proportional apportionment — without it, all-mixed
            # plans can never correct a backwards prior (the seeds say II
            # >> I-b; warm serving is the opposite).
            self.last_reconfig_breakdown = (
                {"I-b": relayout_s} if "I-b" in kinds else {})
            # units the relayout actually migrated, for the cost model's
            # load-aware per-unit I-b average
            self.last_reconfig_scales = (
                {"I-b": self.pool.last_relayout_blocks}
                if "I-b" in kinds else {})
            return time.perf_counter() - t0

    def set_attn_impl(self, impl: str):
        """Switch the paged-attention implementation ("paged" | "gather").
        Executables are keyed on it, so this is a plain Type II swap; the
        bench ablation uses it to A/B the kernel path against the
        pre-kernel dense-gather path on identical traffic."""
        assert impl in ("paged", "gather"), impl
        self.attn_impl = impl
        for cols in self._ctx_buckets():     # warm before the next tick
            self._decode_exec(cols)

    # ------------------------------------ staged (zero-downtime) reconfig
    def _max_batch_cap(self) -> int:
        """Admission ceiling.  While a staged shrink is in flight the cap
        is the *target* max_batch, not the incumbent's — otherwise new
        admissions keep refilling the slots the migration is waiting to
        drain and the commit never becomes legal."""
        cap = int(self.setting["max_batch"])
        if self._staged is not None:
            cap = min(cap, int(self._staged["target"]["max_batch"]))
        return max(cap, 1)

    def _live_extents(self) -> dict:
        """{slot: (written, reserved)} for every live request — what both
        relayout and staged-migration commit preserve."""
        out = {}
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            written = int(self.slot_pos[slot])    # state valid for [0, w)
            reserved = min(len(req.prompt) + req.max_new, self.max_seq)
            out[slot] = (written, reserved)
        return out

    def _hot_blocks(self) -> set:
        """Blocks the very next decode tick will write: each live slot's
        current tail block.  Background-copying them is wasted device
        traffic — they are dirtied again one tick later — so the migration
        loop skips them and they ride the commit-time delta instead."""
        hot: set = set()
        if self.pool.kind != "paged":
            return hot
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            col = min(int(self.slot_pos[s]) // self.pool.bs,
                      self.pool.mb - 1)
            hot.add(int(self.pool.tables[s, col]))
        hot.discard(0)
        return hot

    def begin_reconfig(self, plan: ReconfigPlan):
        """Stage a zero-downtime switch to ``plan.new``.  The incumbent
        setting keeps serving; between ticks the engine (1) folds decode
        executables for the target geometry built by an async worker and
        (2) copies cold held blocks into a double-buffered pool, then
        commits atomically once both are done (``_advance_staged``).  The
        driver learns the outcome through ``take_reconfig_events`` — the
        tuner's pending plan is only confirmed at commit.  One staged plan
        at a time; a newer one supersedes (drops) an in-flight one."""
        if self._staged is not None:
            self.cancel_staged()
        target = dict(self.setting)
        target.update(plan.new)
        kinds = rc_classify(self.setting, plan.new,
                            mesh_knobs=SERVING_RELAYOUT_KNOBS)
        st = {"plan": plan, "target": target, "kinds": kinds,
              "t0": time.perf_counter(),
              "builds": [], "folded": 0, "done_building": False,
              "thread": None, "cancelled": False,
              "incremental": None, "drain_ticks": 0,
              "bg_migrate_s": 0.0, "bg_precompile_s": 0.0}
        specs = []
        if self.pool.kind == "paged" and self.attn_impl != "gather":
            geom = self._target_geometry(target)
            # only the S=1 executables gate the commit; a speculating
            # target's S = k+1 verify executables warm lazily *after* the
            # flip (_spec_exec_ready) — a spec_k change is Type II and
            # must never hold a plan pending behind cold compiles
            for cols in self._ctx_buckets_for(geom["mb"]):
                key, build = self._decode_spec(cols, geom=geom)
                if key not in self._steps:
                    specs.append((key, build))
        self._staged = st
        if not specs:
            st["done_building"] = True
        elif self.async_precompile:
            th = threading.Thread(target=self._precompile_worker,
                                  args=(st, specs), daemon=True)
            st["thread"] = th
            th.start()
        else:
            self._precompile_worker(st, specs)

    def _precompile_worker(self, st: dict, specs: list):
        """Build the staged target's missing executables off the tick
        path.  The worker only measures and appends to ``st["builds"]``
        (list.append is atomic under the GIL) — it never touches the LRU
        or the tracer's span stack; the main thread folds results in
        ``_advance_staged`` via ``LRUCache.absorb`` + ``Tracer.record``."""
        for key, build in specs:
            if st["cancelled"]:
                return
            t0 = time.perf_counter()
            try:
                ex = build()
            except Exception as e:   # counted at fold; the commit's own
                ex = e               # foreground build raises it again
            st["builds"].append((key, ex, time.perf_counter() - t0))
        st["done_building"] = True

    def _advance_staged(self):
        """One between-ticks quantum of the staged pipeline: fold finished
        background builds, copy one bounded batch of cold blocks, commit
        when warm + copied + (for a shrink) drained."""
        st = self._staged
        builds = st["builds"]
        while st["folded"] < len(builds):
            key, ex, dur = builds[st["folded"]]
            st["folded"] += 1
            st["bg_precompile_s"] += dur
            if isinstance(ex, Exception):
                self._build_failed(key, ex)
            else:
                self._steps.absorb(key, ex, dur)
                self.tr.record("exec.precompile_bg", dur, key=str(key))
        warm = st["done_building"] and st["folded"] == len(st["builds"])

        if st["incremental"] is None:
            st["incremental"] = (self.pool.kind == "paged"
                                 and "I-b" in st["kinds"]
                                 and self.pool.begin_migration(st["target"]))
        elif (st["incremental"]
              and getattr(self.pool, "_mig", None) is None):
            st["incremental"] = False    # externally relaid out mid-flight

        pending = 0
        if st["incremental"]:
            skip = self._hot_blocks()
            if self.pool.migration_pending(skip=skip) > 0:
                with self.tr.span("reconfig.migrate_bg",
                                  batch=self.migrate_batch_blocks):
                    t0 = time.perf_counter()
                    pending = self.pool.migration_step(
                        self.migrate_batch_blocks, skip=skip)
                    st["bg_migrate_s"] += time.perf_counter() - t0

        if not warm or pending > 0:
            return
        if (st["incremental"]
                and self.n_active > int(st["target"]["max_batch"])):
            # shrink: wait for the admission cap to drain the live set
            # below the target slot count; a backlog that refuses to
            # drain bails out to the stop-the-world fallback (whose
            # shrink-deferral keeps the old geometry until it can)
            st["drain_ticks"] += 1
            if st["drain_ticks"] < self.migrate_drain_ticks:
                return
        self._commit_staged()

    def _commit_staged(self):
        """Atomic adoption of the staged reconfiguration.  The only
        foreground work left is the delta copy (blocks dirtied since
        their background copy) + table swap + warmup barrier — the
        stall the overlapped pipeline exists to minimize."""
        st = self._staged
        plan = st["plan"]
        with self.tr.span("reconfig.commit", kinds=",".join(st["kinds"])):
            t0 = time.perf_counter()
            self.setting.update(plan.new)
            relayout_s = 0.0
            committed = False          # True = incremental commit succeeded
            if "I-b" in st["kinds"]:
                r0 = time.perf_counter()
                if (st["incremental"]
                        and getattr(self.pool, "_mig", None) is not None):
                    with self.tr.span("reconfig.relayout",
                                      live=self.n_active, staged=True):
                        mapping = self.pool.finish_migration(
                            self._live_extents())
                    if mapping is not None:
                        old_req, old_pos, old_tok = (
                            self.slot_req, self.slot_pos, self.slot_tok)
                        self._reset_slots()
                        for old, new in mapping.items():
                            self.slot_req[new] = old_req[old]
                            self.slot_pos[new] = old_pos[old]
                            self.slot_tok[new] = old_tok[old]
                        committed = True
                    else:
                        self.pool.abort_migration()
                if not committed:          # fallback: stop-the-world
                    self._relayout_pool()
                relayout_s = time.perf_counter() - r0
            else:
                if st["incremental"]:      # defensive: II-only plans never
                    self.pool.abort_migration()  # stage a pool migration
                self.pool.update_policy(self.setting)
            for cols in self._ctx_buckets():   # warm (absorbed) or build
                self._decode_exec(cols)
            jax.block_until_ready(self.pool.decode_cache())
            cost = time.perf_counter() - t0
            self.last_reconfig_breakdown = (
                {"I-b": relayout_s} if "I-b" in st["kinds"] else {})
            # the I-b scale the cost model learns from is the number of
            # blocks the *foreground* actually copied: the commit delta
            # for a staged migration, the full keep set for the fallback.
            # Teaching it delta-cost/keep-blocks would poison the per-unit
            # average — the next non-stageable (re-block) switch would be
            # predicted ~free and blow the calibration gate.
            fg_blocks = (getattr(self.pool, "last_migration_delta_blocks", 0)
                         if committed
                         else self.pool.last_relayout_blocks)
            self.last_reconfig_scales = (
                {"I-b": max(int(fg_blocks), 1)}
                if "I-b" in st["kinds"] else {})
            self._reconfig_events.append({
                "plan": plan, "cost_s": cost,
                "measured": dict(self.last_reconfig_breakdown),
                "scales": dict(self.last_reconfig_scales),
                "bg_migrate_s": st["bg_migrate_s"],
                "bg_precompile_s": st["bg_precompile_s"],
                "bg_blocks": getattr(self.pool,
                                     "last_migration_bg_blocks", 0),
                "delta_blocks": getattr(self.pool,
                                        "last_migration_delta_blocks", 0),
                "staged_wall_s": time.perf_counter() - st["t0"],
            })
        self._staged = None

    def take_reconfig_events(self) -> list[dict]:
        """Drain committed-reconfiguration events (driver → tuner)."""
        ev, self._reconfig_events = self._reconfig_events, []
        return ev

    def cancel_staged(self):
        """Drop an in-flight staged reconfiguration (run teardown, or a
        newer proposal superseding it).  Returns the abandoned plan so
        the driver can tell the tuner to reopen its window, or None."""
        st = self._staged
        if st is None:
            return None
        st["cancelled"] = True
        th = st["thread"]
        if th is not None and th.is_alive():
            th.join(timeout=60.0)
        if st["incremental"] and getattr(self.pool, "_mig", None) is not None:
            self.pool.abort_migration()
        self._staged = None
        return st["plan"]

    def _relayout_pool(self):
        with self.tr.span("reconfig.relayout",
                          live=self.n_active,
                          block_size=self.setting.get("block_size"),
                          max_batch=self.setting.get("max_batch")):
            live_extents = self._live_extents()
            old_req, old_pos, old_tok = (self.slot_req, self.slot_pos,
                                         self.slot_tok)
            # a shrink below the live set must not land the pool on a
            # transient geometry (n_slots = live count): such geometries
            # are outside the knob space, so warm_start never compiled
            # their decode executables and apply_plan's warm loop pays
            # ~6 cold XLA compiles inside the reconfig window (then the
            # drain shrink discards them).  Keep the current slot count
            # instead; the drain check in step() finishes the shrink on
            # the warmed target geometry once the backlog clears.
            min_slots = (self.pool.n_slots
                         if len(live_extents) > self.setting["max_batch"]
                         else 0)
            mapping = self.pool.relayout(self.setting, live_extents,
                                         min_slots=min_slots)
            self._reset_slots()
            for old, new in mapping.items():
                self.slot_req[new] = old_req[old]
                self.slot_pos[new] = old_pos[old]
                self.slot_tok[new] = old_tok[old]


def serve_loop(engine: ServingEngine, trace, tuner=None, *,
               max_wall_s: float | None = None, idle_sleep_s: float = 0.001,
               verbose: bool = False) -> dict:
    """Replay an arrival trace through the engine, optionally self-tuning.

    Mirrors repro.ps.trainer.SelfTuningLoop: per busy quantum the driver
    records (context value = offered load, execution time) into the tuner
    and executes any ReconfigPlan it emits, reporting the observed cost.
    """
    pending = deque(sorted(trace, key=lambda r: r.arrival_s))
    n_req = len(pending)
    tok0 = engine.total_tokens          # deltas: engines may be re-used
    fin0 = len(engine.finished)
    pf0 = engine.prefill_tokens_computed
    pt0 = engine.prefill_tokens_total
    dt0 = engine.decode_time_s
    dk0 = engine.decode_tokens
    sd0 = engine.spec_drafted
    sa0 = engine.spec_accepted
    st0 = engine.spec_ticks
    sh0 = engine.pool.shared_blocks_hit
    cow0 = engine.pool.cow_copies
    fb0 = engine.failed_builds
    t_start = time.perf_counter()
    reconfigs = []
    reconfig_total_s = 0.0
    timeline = []                 # (t, total_tokens, load) every ~50 quanta
    busy_ticks = 0

    def _drain_reconfig_events():
        """Report staged commits to the tuner (confirming its pending
        plan) and log them; the cost it learns is the *foreground* commit
        stall — background migrate/precompile seconds ride along for the
        bench panel but never enter the cost model."""
        nonlocal reconfig_total_s
        for ev in engine.take_reconfig_events():
            tuner.record_reconfig(
                ev["plan"], ev["cost_s"], measured=ev["measured"],
                scales=ev["scales"])
            reconfig_total_s += ev["cost_s"]
            reconfigs.append({
                "t": round(time.perf_counter() - t_start, 3),
                "kinds": list(ev["plan"].kinds),
                "cost_s": round(ev["cost_s"], 4),
                "bg_migrate_s": round(ev["bg_migrate_s"], 4),
                "bg_precompile_s": round(ev["bg_precompile_s"], 4),
                "bg_blocks": ev["bg_blocks"],
                "delta_blocks": ev["delta_blocks"],
                "staged_wall_s": round(ev["staged_wall_s"], 4),
                "setting": dict(ev["plan"].new)})
            if verbose:
                print(f"[reconfig@{reconfigs[-1]['t']:.1f}s] "
                      f"{ev['plan'].kinds} -> {ev['plan'].new} "
                      f"(commit {ev['cost_s']:.3f}s, "
                      f"bg {ev['bg_migrate_s'] + ev['bg_precompile_s']:.2f}s)",
                      flush=True)

    while pending or engine.has_work():
        now = time.perf_counter() - t_start
        if max_wall_s is not None and now > max_wall_s:
            break
        while pending and pending[0].arrival_s <= now:
            engine.submit(pending.popleft(), now=now)
        tick = engine.step(now=now)
        if tuner is not None:
            # commits can land on any tick (idle ones included) — report
            # them before deciding whether to skip the tuner bookkeeping
            _drain_reconfig_events()
        if tick["idle"]:
            # nothing in flight and nothing arrived: wait for traffic
            if pending:
                time.sleep(min(idle_sleep_s,
                               max(pending[0].arrival_s - now, 0.0)))
            continue
        busy_ticks += 1
        if busy_ticks % 50 == 1:
            timeline.append((round(now, 3), engine.total_tokens - tok0,
                             tick["load"]))
        if tuner is not None:
            tuner.record_iteration(float(tick["load"]), tick["dt"])
            plan = tuner.maybe_advance()
            if plan is not None:
                # stage, don't stall: the engine keeps serving while the
                # target's executables precompile and its pool migrates in
                # the background; the tuner holds the plan pending until
                # the commit event confirms it
                engine.begin_reconfig(plan)
    wall = time.perf_counter() - t_start
    # a plan still staged at run end never committed: tear it down and
    # let the tuner reopen the window it froze for the proposal
    leftover = engine.cancel_staged()
    if tuner is not None:
        _drain_reconfig_events()
        if leftover is not None:
            tuner.abandon_reconfig(leftover)
    done = engine.finished[fin0:]
    tokens = engine.total_tokens - tok0
    lats = [r.latency_s for r in done]
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    stats = {
        "requests": n_req,
        "completed": len(done),
        "wall_s": wall,
        "tokens": tokens,
        "tokens_per_s": tokens / max(wall, 1e-9),
        "p50_latency_s": float(np.percentile(lats, 50)) if lats else None,
        "p99_latency_s": float(np.percentile(lats, 99)) if lats else None,
        "p50_ttft_s": float(np.percentile(ttfts, 50)) if ttfts else None,
        "reconfigs": reconfigs,
        "reconfig_count": len(reconfigs),
        "reconfig_total_s": reconfig_total_s,
        "final_setting": dict(engine.setting),
        "timeline": timeline,
        # prefix-sharing / paging effectiveness (pool counters, deltas)
        "prefill_tokens_computed": engine.prefill_tokens_computed - pf0,
        "prefill_tokens_total": engine.prefill_tokens_total - pt0,
        "shared_blocks_hit": engine.pool.shared_blocks_hit - sh0,
        "cow_copies": engine.pool.cow_copies - cow0,
        # decode-only throughput: wall time spent inside the compiled
        # decode steps vs tokens they produced (isolates the paged-
        # attention hot path from prefill/admission/queueing)
        "decode_s": engine.decode_time_s - dt0,
        "decode_tok_per_s": ((engine.decode_tokens - dk0)
                             / max(engine.decode_time_s - dt0, 1e-9)),
        # observability: end-of-run pool occupancy and executable-cache
        # state (hit/miss/build-time — Type II swap warmth in one line)
        "pool": engine.pool.snapshot(),
        "exec_cache": engine._steps.stats(),
        "failed_builds": engine.failed_builds - fb0,
    }
    drafted = engine.spec_drafted - sd0
    stats["speculation"] = {
        "drafted": drafted,
        "accepted": engine.spec_accepted - sa0,
        "spec_ticks": engine.spec_ticks - st0,
        "accept_rate": ((engine.spec_accepted - sa0) / drafted
                        if drafted else 0.0),
        "spec_k": engine._spec_k(),
        "drafter": engine.setting.get("drafter", "ngram"),
    }
    if tuner is not None:
        # init-phase spend + fleet-store warm-start provenance: the bench's
        # warm_start_gain panel compares these across cold/warm arms
        stats["tuner_init_quanta"] = tuner.init_quanta
        stats["tuner_init_time_s"] = round(tuner.init_time_s, 4)
        stats["tuner_horizon_s"] = tuner.effective_horizon()
        if tuner.warm_start_info is not None:
            stats["warm_start"] = dict(tuner.warm_start_info)
    return stats
