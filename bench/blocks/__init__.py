"""Blocks: everything that knows a model block's equations, one file each.

A configuration file (``bench/configs/<config>.json``) names its block
with a top-level ``"block"`` key, ``dense`` where it has none.  ``of``
finds ``<block>.py`` in ``DIRS`` and is the only way the harness, the
check and the per-layer readers reach a block, so adding a block adds a
file here and edits nothing.  A block module provides:

  make_params(m, seed)      the program's whole parameter tree, in the
                            type it is served in (bf16), on the default
                            device, made in one jitted call
  logits_at(m, seed, seqs, rows, pack, precision="f32")
                            the plain reference: f32 at ``highest``
                            precision; ``precision="fp8"`` is the control
                            (``bench/reference.py``); one (len(rows[i]), V)
                            numpy array per sequence
  prefill_flops(m, n, start)  a prefill of ``n`` tokens after ``start``
                            cached ones, one row of logits
  decode_flops(m, keys)     one decoded token whose context is ``keys``
                            positions (itself included)
  paged_attention_call(m, queries, start)
                            (flops, bytes) of one request's paged
                            attention, ``queries`` tokens at ``start``..

``m`` is the configuration's ``model`` object.  What a block must keep to:

- The reference is given sequences packed side by side into one row,
  with ``seg`` the sequence of each position (``reference.pack_rows``).
  Nothing may pass between sequences: attention masks across ``seg``,
  and a recurrent layer starts from zero state at each new segment (or
  runs the sequences one by one).
- Weights come from ``bench/weights.py``: leaf ``name`` of layer ``l``
  is drawn from ``fold_in(leaf, l)`` of the seed's key (``layer_draw``),
  so one layer drawn alone, as the reference draws it, equals the
  served one.  Layers of different kinds may have different leaves; the
  reference imports nothing of the program and takes nothing it made.
- Counts are of required work: a causal query attends to the keys at or
  before it, padding is not counted, a prefill projects one row of
  logits, and each count covers only the layers that have the work (a
  block with 4 attention layers of 40 counts attention over 4).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

DIRS = (Path(__file__).resolve().parent,)
DEFAULT = "dense"
PROVIDES = ("make_params", "logits_at", "prefill_flops", "decode_flops",
            "paged_attention_call")


def names() -> list:
    """The blocks there are, in every directory of ``DIRS``."""
    return sorted({p.stem for d in DIRS for p in d.glob("*.py")
                   if p.stem != "__init__"})


def of(conf: dict):
    """The module of the block a configuration names."""
    name = conf.get("block", DEFAULT)
    if name not in names():
        raise LookupError(f"no block {name!r}; the blocks are: "
                          + ", ".join(names()))
    path = next(d / f"{name}.py" for d in DIRS
                if (d / f"{name}.py").is_file())
    spec = importlib.util.spec_from_file_location(
        "bench_block_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in PROVIDES if not hasattr(mod, f)]
    if missing:
        raise LookupError(f"block {name!r} ({path}) lacks "
                          + ", ".join(missing))
    return mod
