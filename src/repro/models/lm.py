"""Unified LM for all assigned families.

dense / moe / vlm / encoder : [attn + (mlp|moe)] x L, scan-over-layers
ssm                         : [mamba1] x L
hybrid (zamba2)             : [mamba2] x L + one *shared* attention block
                              applied every ``shared_attn_every`` layers

Everything is pure-functional: ``init_params`` builds the pytree (only ever
materialized for reduced configs — full configs go through ``param_shapes``
and ShapeDtypeStructs). Layer params are stacked on a leading L axis and the
forward is a ``lax.scan``, so the HLO stays small at any depth.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import MeshSpec, constrain, path_str
from repro.models import common
from repro.models.attention import (chunked_attention, decode_attention,
                                    gather_blocks, paged_decode_attention)
from repro.models.mamba import mamba1_block, mamba2_block
from repro.models.moe import moe_block


@dataclass(frozen=True)
class ModelKnobs:
    """Per-step *system* knobs (paper: Type II settings — they change only the
    compiled step, never the learning problem)."""
    remat: str = "none"        # none | dots | full
    q_chunk: int = 512
    k_chunk: int = 1024
    scan_unroll: int = 1       # -1 = python for-loop (no scan; cost probes)
    ce_chunk: int = 0          # chunked cross-entropy (0 = off)
    ssm_chunk: int = 0         # >0: chunk-blocked selective scan (the Pallas
                               # mamba_scan execution schedule; state stays
                               # VMEM-resident within a chunk)
    attn_skip_masked: bool = False  # causal-block skipping (Pallas flash
                                    # kernel schedule; halves attention FLOPs)
    seq_shard: bool = False    # Megatron-style sequence parallelism on the
                               # residual stream: the per-layer saved carry is
                               # sharded over the model axis (16x less HBM for
                               # remat-saved activations; adds per-layer
                               # reshard collectives)
    attn_impl: str = "paged"   # paged-decode attention: "paged" reads KV
                               # blocks in place through the block table
                               # (kernels/paged_attention schedule; the pool's
                               # block_size knob is the kernel kv tile);
                               # "gather" is the pre-kernel path — gather the
                               # table into a dense cache, then full-softmax
                               # attention (kept for the bench ablation)
    attn_ctx: int = 0          # paged decode: visible block-table columns
                               # (0 = all).  The serving engine tracks write
                               # positions on the host and compiles per
                               # context bucket, so short batches only read
                               # (and pay for) their live blocks


def _pdt(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ===========================================================================
# Parameter construction
# ===========================================================================

def _attn_param_shapes(cfg: ModelConfig):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {"wq": (D, H * hd), "wk": (D, K * hd), "wv": (D, K * hd),
         "wo": (H * hd, D)}
    if cfg.qkv_bias:
        p.update({"bq": (H * hd,), "bk": (K * hd,), "bv": (K * hd,)})
    return p


def _layer_param_shapes(cfg: ModelConfig):
    D, F = cfg.d_model, cfg.d_ff
    if cfg.family in ("dense", "vlm", "encoder", "moe"):
        p = {"ln1": {"scale": (D,)}, "ln2": {"scale": (D,)},
             "attn": _attn_param_shapes(cfg)}
        if cfg.uses_moe:
            p["moe"] = {"router": (D, cfg.n_experts),
                        "wi": (cfg.n_experts, D, F),
                        "wg": (cfg.n_experts, D, F),
                        "wo": (cfg.n_experts, F, D)}
        else:
            p["mlp"] = {"wi": (D, F), "wg": (D, F), "wo": (F, D)}
        return p
    # ssm / hybrid
    Di, N = cfg.d_inner, cfg.ssm_state
    ssm = {"in_proj": (D, 2 * Di), "conv_w": (Di, cfg.ssm_conv),
           "conv_b": (Di,), "out_proj": (Di, D)}
    if cfg.ssm_version == 1:
        ssm.update({"x_proj": (Di, cfg.dt_rank + 2 * N),
                    "dt_w": (cfg.dt_rank, Di), "dt_b": (Di,),
                    "A_log": (Di, N), "Dskip": (Di,)})
    else:
        nh = cfg.n_ssm_heads
        ssm.update({"BC_proj": (D, 2 * N), "dt_proj2": (D, nh),
                    "dt_bias2": (nh,), "A_log2": (nh,), "Dskip2": (nh,),
                    "gnorm": (Di,)})
    return {"ln1": {"scale": (D,)}, "ssm": ssm}


def param_shapes(cfg: ModelConfig):
    """Pytree of ShapeDtypeStruct for the full model (no allocation)."""
    dt = _pdt(cfg)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size

    def as_sds(shapes, stack=False):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(((L,) + s) if stack else s, dt),
            shapes, is_leaf=lambda x: isinstance(x, tuple))

    tree = {
        "embed": {"tokens": jax.ShapeDtypeStruct((V, D), dt)},
        "layers": as_sds(_layer_param_shapes(cfg), stack=True),
        "final_norm": {"scale": jax.ShapeDtypeStruct((D,), dt)},
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = {"w": jax.ShapeDtypeStruct((D, V), dt)}
    if cfg.frontend != "none":
        tree["frontend"] = {"proj": jax.ShapeDtypeStruct((cfg.frontend_dim, D), dt)}
    if cfg.shared_attn_every:
        tree["shared"] = as_sds(
            {"ln1": {"scale": (D,)}, "ln2": {"scale": (D,)},
             "attn": _attn_param_shapes(cfg),
             "mlp": {"wi": (D, cfg.d_ff), "wg": (D, cfg.d_ff),
                     "wo": (cfg.d_ff, D)}})
    return tree


# one fused program per leaf: the f32 draw never materializes beside the
# bf16 result (eagerly, a published-width MLP stack's f32 temporaries alone
# outgrow a 16 GB chip)
_dense_init_fused = jax.jit(common.dense_init, static_argnums=(1, 2, 3))


def init_params(cfg: ModelConfig, key):
    """Materialize parameters (reduced configs / real runs only)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    flat = []
    for sds, k in zip(leaves, keys):
        if len(sds.shape) <= 1:
            flat.append(jnp.zeros(sds.shape, sds.dtype))
        else:
            flat.append(_dense_init_fused(
                k, sds.shape, max(0, len(sds.shape) - 2), sds.dtype))
    params = jax.tree_util.tree_unflatten(treedef, flat)

    def fix(path, x):
        s = path_str(path)
        if (s.endswith("scale") or "/b" == s[-3:-1] or s.endswith("/bq")
                or s.endswith("/bk") or s.endswith("/bv")
                or s.endswith("conv_b") or s.endswith("dt_b")
                or s.endswith("dt_bias2") or s.endswith("gnorm")):
            return jnp.zeros_like(x)
        if s.endswith("A_log"):
            N = x.shape[-1]
            a = jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), x.shape)
            return a.astype(x.dtype)
        if s.endswith("A_log2"):
            return jnp.zeros_like(x)
        if s.endswith("Dskip") or s.endswith("Dskip2"):
            return jnp.ones_like(x)
        return x

    return jax.tree_util.tree_map_with_path(fix, params)


# ===========================================================================
# Blocks
# ===========================================================================

def _attn_apply(x, p, cfg: ModelConfig, ms, knobs: ModelKnobs, positions,
                cache=None, pos=None, block_tables=None):
    """Returns (out, new_kv): new_kv = (k, v) activations for train/prefill or
    the updated cache pair for decode.

    Decode caches come in two layouts:
      * dense (B, Smax, K, hd): position p of request b is row (b, p);
      * paged (NB, K, bs, hd) + ``block_tables`` (B, MB): position p of
        request b lives at physical block block_tables[b, p // bs], row
        p % bs of every head — the KV-pool indirection of the serving
        engine's PagedKVPool.  Blocks are head-major so one head's
        (bs, hd) slab is the paged-attention kernel's tile.
    Both accept S >= 1 new tokens (S > 1 = chunked prefill against a prior
    cache, e.g. a shared prompt prefix)."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cdt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)

    # Attention parallelism (DESIGN.md §5): shard query heads over the model
    # axis when the head count divides it; otherwise shard the *query
    # sequence* (context parallelism with replicated KV). KV heads are only
    # sharded when they divide the axis themselves (MHA-style archs).
    msz = ms.model_size if ms is not None else 1
    if H % msz == 0:
        q = constrain(q, ms, "D", None, "M", None)
    elif S % msz == 0 and S > 1:
        q = constrain(q, ms, "D", "M", None, None)
    kv_sym = "M" if K % msz == 0 else None
    k = constrain(k, ms, "D", None, kv_sym, None)
    v = constrain(v, ms, "D", None, kv_sym, None)

    if cache is None:                       # train / prefill
        out = chunked_attention(q, k, v, causal=cfg.causal,
                                q_positions=positions, kv_positions=positions,
                                q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk)
        new_kv = (k, v)
    elif block_tables is not None:          # decode: paged (NB, K, bs, hd)
        k_cache, v_cache = cache
        bs = k_cache.shape[2]
        MB = block_tables.shape[1]
        blk = jnp.take_along_axis(block_tables,
                                  jnp.minimum(positions // bs, MB - 1), axis=1)
        # positions past the table (bucket padding in chunked prefill) must
        # not clamp onto the last live column — their (block, offset) rows
        # would collide with real suffix KV.  Physical block 0 is the
        # pool's reserved trash block (serving.pool.TRASH_BLOCK), so they
        # land there and are never read.
        blk = jnp.where(positions >= MB * bs, 0, blk)
        off = positions % bs                                # (B, S)
        # [blk, :, off] indexes (B, S) rows across all heads: (B, S, K, hd)
        k_cache = k_cache.at[blk, :, off].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[blk, :, off].set(v.astype(v_cache.dtype))
        if knobs.attn_impl == "gather":     # pre-kernel path (ablation arm)
            out = decode_attention(q, gather_blocks(k_cache, block_tables),
                                   gather_blocks(v_cache, block_tables),
                                   pos=pos)
        else:                               # read blocks in place (kernel)
            # host-chosen context bucket: the kernel's kv grid axis spans
            # only the visible table prefix (attn_ctx columns; 0 = all)
            out = paged_decode_attention(q, k_cache, v_cache, block_tables,
                                         pos=pos, ctx_cols=knobs.attn_ctx)
        new_kv = (k_cache, v_cache)
    else:                                   # decode: dense (B, Smax, K, hd)
        k_cache, v_cache = cache
        b_idx = jnp.arange(B)[:, None]
        s_idx = jnp.minimum(positions, k_cache.shape[1] - 1)
        k_cache = k_cache.at[b_idx, s_idx].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, s_idx].set(v.astype(v_cache.dtype))
        out = decode_attention(q, k_cache, v_cache, pos=pos)
        new_kv = (k_cache, v_cache)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                     p["wo"].astype(cdt))
    return out, new_kv


def _mlp_apply(x, p, cdt):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(cdt))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cdt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["wo"].astype(cdt))


def _shared_block(x, p, cfg, ms, knobs, positions, cache=None, pos=None):
    """Zamba2 shared attention+MLP block (one weight set, many call sites)."""
    cdt = x.dtype
    h, new_kv = _attn_apply(common.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps),
                            p["attn"], cfg, ms, knobs, positions, cache, pos)
    x = x + h
    x = x + _mlp_apply(common.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps),
                       p["mlp"], cdt)
    return x, new_kv


# ===========================================================================
# Forward
# ===========================================================================

def _embed(params, cfg: ModelConfig, batch, ms):
    cdt = jnp.bfloat16
    emb = params["embed"]["tokens"]
    if cfg.frontend == "frame":             # audio: whole sequence is frames
        x = jnp.einsum("bsf,fd->bsd", batch["frontend"].astype(cdt),
                       params["frontend"]["proj"].astype(cdt))
    elif cfg.frontend == "patch" and "frontend" in batch:
        pat = jnp.einsum("bsf,fd->bsd", batch["frontend"].astype(cdt),
                         params["frontend"]["proj"].astype(cdt))
        tok = jnp.take(emb, batch["tokens"], axis=0).astype(cdt)
        x = jnp.concatenate([pat, tok], axis=1)
    else:
        x = jnp.take(emb, batch["tokens"], axis=0).astype(cdt)
    return constrain(x, ms, "D", None, None)


def _maybe_remat(fn, knobs: ModelKnobs):
    if knobs.remat == "none":
        return fn
    if knobs.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)               # "full": save nothing


def forward(params, batch, cfg: ModelConfig, ms: MeshSpec | None = None,
            knobs: ModelKnobs = ModelKnobs(), mode: str = "train",
            cache=None, pos=None, valid_len=None):
    """Returns (hidden (B,S,D), aux_loss, new_cache or None).

    ``valid_len`` (scalar, prefill only): number of non-pad tokens in a
    right-padded batch.  Attention families ignore it (the causal mask plus
    caller-side slicing already isolate pads); SSM families need it so the
    returned recurrent state is the state *after token valid_len*, not after
    the pads."""
    x = _embed(params, cfg, batch, ms)
    B, S, D = x.shape
    if mode == "decode":
        positions = pos[:, None] + jnp.arange(S)[None, :]   # (B, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    if cfg.family in ("dense", "moe", "vlm", "encoder"):
        return _forward_attn(params, x, positions, cfg, ms, knobs, mode,
                             cache, pos)
    return _forward_ssm(params, x, positions, cfg, ms, knobs, mode,
                        cache, pos, valid_len)


def _forward_attn(params, x, positions, cfg, ms, knobs, mode, cache, pos):
    B, S, D = x.shape
    bt = cache.get("block_tables") if cache is not None else None

    def body(x, inp):
        lp = inp["lp"]
        cdt = x.dtype
        c = inp.get("kv")
        h, new_kv = _attn_apply(
            common.rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps),
            lp["attn"], cfg, ms, knobs, positions, c, pos, block_tables=bt)
        x = x + h
        xn = common.rms_norm(x, lp["ln2"]["scale"], cfg.norm_eps)
        if cfg.uses_moe:
            y, aux = moe_block(xn.reshape(B * S, D), lp["moe"], cfg, ms)
            x = x + y.reshape(B, S, D)
        else:
            x = x + _mlp_apply(xn, lp["mlp"], cdt)
            aux = jnp.zeros((), jnp.float32)
        x = constrain(x, ms, "D", "M" if knobs.seq_shard else None, None)
        out_kv = None if mode == "train" else new_kv
        return x, (out_kv, aux)

    body = _maybe_remat(body, knobs)
    xs = {"lp": params["layers"]}
    if mode == "decode":
        xs["kv"] = (cache["k"], cache["v"])
    if knobs.scan_unroll == -1:              # python loop (cost probes)
        ys = []
        for i in range(cfg.n_layers):
            xi = jax.tree_util.tree_map(lambda t: t[i], xs)
            x, y = body(x, xi)
            ys.append(y)
        kvs, auxs = jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *ys)
    else:
        x, (kvs, auxs) = jax.lax.scan(body, x, xs, unroll=knobs.scan_unroll)
    new_cache = None if mode == "train" else {"k": kvs[0], "v": kvs[1]}
    if new_cache is not None and bt is not None:
        new_cache["block_tables"] = bt
    x = common.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, auxs.mean(), new_cache


def _forward_ssm(params, x, positions, cfg, ms, knobs, mode, cache, pos,
                 valid_len=None):
    B, S, D = x.shape
    mamba = mamba1_block if cfg.ssm_version == 1 else mamba2_block
    every = cfg.shared_attn_every
    is_hybrid = cfg.family == "hybrid"
    shared_p = params.get("shared")
    want_state = mode != "train"
    if mode != "prefill":
        valid_len = None                   # pads only exist in prefill

    def body(carry, inp):
        x, shared_kv = carry
        lp, idx = inp["lp"], inp["idx"]
        st = inp.get("st")
        h, new_st = mamba(
            common.rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps),
            lp["ssm"], cfg, ms, st, chunk=knobs.ssm_chunk,
            valid_len=valid_len)
        x = x + h
        if is_hybrid and shared_p is not None:
            a_idx = idx // every

            def with_attn(x, shared_kv):
                if mode == "decode":
                    c = (jax.lax.dynamic_index_in_dim(shared_kv[0], a_idx, 0,
                                                      keepdims=False),
                         jax.lax.dynamic_index_in_dim(shared_kv[1], a_idx, 0,
                                                      keepdims=False))
                else:
                    c = None
                y, kv = _shared_block(x, shared_p, cfg, ms, knobs,
                                      positions, c, pos)
                if want_state:
                    shared_kv = (
                        jax.lax.dynamic_update_index_in_dim(
                            shared_kv[0], kv[0].astype(shared_kv[0].dtype),
                            a_idx, 0),
                        jax.lax.dynamic_update_index_in_dim(
                            shared_kv[1], kv[1].astype(shared_kv[1].dtype),
                            a_idx, 0))
                return y, shared_kv

            x, shared_kv = jax.lax.cond(
                idx % every == 0, with_attn,
                lambda x, skv: (x, skv), x, shared_kv)
        x = constrain(x, ms, "D", "M" if knobs.seq_shard else None, None)
        out_st = new_st if want_state else None
        return (x, shared_kv), out_st

    body = _maybe_remat(body, knobs)
    if is_hybrid:
        n_apps = (cfg.n_layers + every - 1) // every
        K, hd = cfg.n_kv_heads, cfg.hd
        if mode == "decode":
            shared_kv0 = (cache["shared_k"], cache["shared_v"])
        else:
            shared_kv0 = (jnp.zeros((n_apps, B, S, K, hd), jnp.bfloat16),
                          jnp.zeros((n_apps, B, S, K, hd), jnp.bfloat16))
    else:
        shared_kv0 = (jnp.zeros((0,), jnp.bfloat16),) * 2

    xs = {"lp": params["layers"], "idx": jnp.arange(cfg.n_layers)}
    if mode == "decode":
        xs["st"] = {"conv": cache["conv"], "h": cache["h"]}
    else:
        xs["st"] = None
    if knobs.scan_unroll == -1:              # python loop (cost probes)
        carry = (x, shared_kv0)
        ys = []
        for i in range(cfg.n_layers):
            xi = jax.tree_util.tree_map(lambda t: t[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        (x, shared_kv) = carry
        sts = (jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *ys)
               if ys[0] is not None else None)
    else:
        (x, shared_kv), sts = jax.lax.scan(body, (x, shared_kv0), xs,
                                           unroll=knobs.scan_unroll)
    new_cache = None
    if want_state:
        new_cache = {"conv": sts["conv"], "h": sts["h"]}
        if is_hybrid:
            new_cache.update({"shared_k": shared_kv[0],
                              "shared_v": shared_kv[1]})
    x = common.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32), new_cache


def logits_fn(params, hidden, cfg: ModelConfig, ms=None):
    w = (params["embed"]["tokens"].T if cfg.tie_embeddings
         else params["lm_head"]["w"])
    return jnp.einsum("bsd,dv->bsv", hidden, w.astype(hidden.dtype))


def loss_fn(params, batch, cfg: ModelConfig, ms=None,
            knobs: ModelKnobs = ModelKnobs()):
    """Mean cross entropy (labels pre-shifted by the data pipeline)."""
    hidden, aux, _ = forward(params, batch, cfg, ms, knobs, mode="train")
    labels = batch["labels"]
    B, S = labels.shape
    if hidden.shape[1] != S:                # vlm: loss on text positions only
        hidden = hidden[:, hidden.shape[1] - S:]

    def ce(h, y):
        lg = logits_fn(params, h, cfg, ms).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return (lse - tgt).sum()

    if knobs.ce_chunk and S > knobs.ce_chunk and S % knobs.ce_chunk == 0:
        nc = S // knobs.ce_chunk
        hc = hidden.reshape(B, nc, knobs.ce_chunk, -1).transpose(1, 0, 2, 3)
        yc = labels.reshape(B, nc, knobs.ce_chunk).transpose(1, 0, 2)

        def step(tot, inp):
            h, y = inp
            return tot + ce(h, y), None

        total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hc, yc))
    else:
        total = ce(hidden, labels)
    loss = total / (B * S)
    return loss + cfg.router_aux_weight * aux, {"ce": loss, "aux": aux}


# ===========================================================================
# Serving entry points
# ===========================================================================

def init_cache_shapes(cfg: ModelConfig, batch: int, max_seq: int):
    """ShapeDtypeStruct pytree for the decode cache."""
    L = cfg.n_layers
    out = {}
    if cfg.family in ("dense", "moe", "vlm", "encoder"):
        K, hd = cfg.n_kv_heads, cfg.hd
        out["k"] = jax.ShapeDtypeStruct((L, batch, max_seq, K, hd), jnp.bfloat16)
        out["v"] = jax.ShapeDtypeStruct((L, batch, max_seq, K, hd), jnp.bfloat16)
    else:
        Di, Kc = cfg.d_inner, cfg.ssm_conv
        out["conv"] = jax.ShapeDtypeStruct((L, batch, Di, Kc - 1), jnp.bfloat16)
        if cfg.ssm_version == 1:
            out["h"] = jax.ShapeDtypeStruct((L, batch, Di, cfg.ssm_state),
                                            jnp.float32)
        else:
            out["h"] = jax.ShapeDtypeStruct(
                (L, batch, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32)
        if cfg.family == "hybrid":
            every = cfg.shared_attn_every
            n_apps = (L + every - 1) // every
            K, hd = cfg.n_kv_heads, cfg.hd
            out["shared_k"] = jax.ShapeDtypeStruct(
                (n_apps, batch, max_seq, K, hd), jnp.bfloat16)
            out["shared_v"] = jax.ShapeDtypeStruct(
                (n_apps, batch, max_seq, K, hd), jnp.bfloat16)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  init_cache_shapes(cfg, batch, max_seq))


def init_paged_cache_shapes(cfg: ModelConfig, n_blocks: int, block_size: int):
    """ShapeDtypeStruct pytree for a paged decode cache: fixed-size KV blocks
    addressed through per-request block tables (``block_tables`` supplied at
    decode time by the pool).  Attention families only — recurrent state has
    no sequence axis to page."""
    assert cfg.family in ("dense", "moe", "vlm", "encoder"), cfg.family
    L, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    # head-major blocks: (bs, hd) per head is the kernel's KV tile
    sds = jax.ShapeDtypeStruct((L, n_blocks, K, block_size, hd), jnp.bfloat16)
    return {"k": sds, "v": sds}


def prefill(params, batch, cfg: ModelConfig, ms=None,
            knobs: ModelKnobs = ModelKnobs(), valid_len=None):
    hidden, _, cache = forward(params, batch, cfg, ms, knobs, mode="prefill",
                               valid_len=valid_len)
    logits = logits_fn(params, hidden[:, -1:], cfg, ms)
    return logits, cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, ms=None,
                knobs: ModelKnobs = ModelKnobs()):
    """tokens: (B, S); pos: (B,) write position of the first token (S > 1 =
    chunked prefill against the cache).  ``cache`` is dense (per-request
    rows) or paged (block pool + ``block_tables``).  Returns (logits, cache).
    """
    hidden, _, new_cache = forward(params, {"tokens": tokens}, cfg, ms, knobs,
                                   mode="decode", cache=cache, pos=pos)
    logits = logits_fn(params, hidden, cfg, ms)
    return logits, new_cache
