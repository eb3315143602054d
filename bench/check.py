"""What decides ``correct``: served tokens against the f32 reference.

After the window, a sample drawn from the seed of the requests that
finished in it (the longest always among them, then seeded picks until
``check.tokens`` served tokens, 256 at the cells' sizes) is run once
through the f32 reference of the configuration's block (its
``logits_at``), prompt and served tokens together, packed side by side
into rows of ``check.pack`` tokens (one compiled shape).  For each
served token the number compared is how far its reference logit lies
below the reference's best at that position: 0 where the program chose
the reference's argmax, small where bf16 rounding flipped a near tie,
large where the program computed something else.  The widest such gap
over the sample is held to the configuration's limit (``check.logit_gap``
in its file; how the limit was set is in PERF.md), and the sample has to
hold at least ``check.tokens`` served tokens.

The control (``control=True``) puts the block's reference computed in
float8 in the program's place: at each position of the same prompts and
served tokens, the token that float8 puts first goes through the same
gap and limit, so a sound control run comes out not correct.  The
program's own gap is printed beside it.
"""
from __future__ import annotations

import sys
import time

import numpy as np


def finished(win) -> list:
    """Requests that completed inside the window."""
    return [r for r in win.recs
            if r.done is not None and win.w0 < r.done <= win.w1
            and not r.refused]


def _size(r) -> int:
    """Tokens the reference runs for ``r``: prompt + served but the last."""
    return len(r.spec.prompt) + len(r.req.tokens_out) - 1


def sample(recs: list, seed: int, pack: int, want: int) -> list:
    """The longest request that fits a row, then seeded picks, until
    ``want`` served tokens."""
    fit = [r for r in recs if _size(r) <= pack]
    if not fit:
        return []
    rng = np.random.default_rng([seed, 17])
    longest = max(fit, key=_size)
    pick, tokens = [longest], len(longest.req.tokens_out)
    for i in rng.permutation(len(fit)):
        if tokens >= want:
            break
        if fit[i] is not longest:
            pick.append(fit[i])
            tokens += len(fit[i].req.tokens_out)
    return pick


def rows_of(pick: list, pack: int) -> list:
    """Pack the picked requests into rows of at most ``pack`` tokens,
    first fit, longest first."""
    rows: list = []
    for r in sorted(pick, key=_size, reverse=True):
        for row in rows:
            if row[0] + _size(r) <= pack:
                row[0] += _size(r)
                row[1].append(r)
                break
        else:
            rows.append([_size(r), [r]])
    return [row[1] for row in rows]


def widest_gap(ref: np.ndarray, tokens) -> float:
    """max over rows of (best reference logit - logit of the token)."""
    tokens = np.asarray(tokens)
    return float(np.max(ref.max(-1) - ref[np.arange(len(tokens)), tokens]))


def inputs(pick: list):
    """Sequences and rows: prompt + served tokens but the last, and the
    positions whose next-token logits chose each served token."""
    seqs, rows, toks = [], [], []
    for r in pick:
        p, out = r.spec.prompt, list(r.req.tokens_out)
        seqs.append(np.concatenate([p, np.asarray(out[:-1], np.int32)]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
        toks.append(out)
    return seqs, rows, toks


def gaps(block, m: dict, seed: int, pick: list, pack: int,
         control: bool):
    """Widest gap of the served tokens, and of the control's tokens."""
    gap = cgap = 0.0
    for row in rows_of(pick, pack):
        seqs, rows, toks = inputs(row)
        ref = block.logits_at(m, seed, seqs, rows, pack)
        gap = max([gap] + [widest_gap(r, t) for r, t in zip(ref, toks)])
        if control:
            low = block.logits_at(m, seed, seqs, rows, pack,
                                  precision="fp8")
            cgap = max([cgap] + [widest_gap(r, lo.argmax(-1))
                                 for r, lo in zip(ref, low)])
    return gap, cgap


def check(conf: dict, block, seed: int, win, *, control: bool = False,
          log=print) -> dict:
    """The verdict on ``win``; ``block`` is ``blocks.of(conf)``."""
    m = conf["model"]
    V = m["vocab_size"]
    done = finished(win)
    short = sum(len(r.req.tokens_out) != r.spec.max_new for r in done)
    oov = sum(int(np.sum((np.asarray(r.req.tokens_out) < 0)
                         | (np.asarray(r.req.tokens_out) >= V)))
              for r in done)
    pack, want = conf["check"]["pack"], conf["check"]["tokens"]
    limit = conf["check"]["logit_gap"]
    pick = sample(done, seed, pack, want)
    n = sum(len(r.req.tokens_out) for r in pick)
    checks = {"short_requests": {"value": short, "limit": 0},
              "out_of_vocab": {"value": oov, "limit": 0},
              "compared_tokens": {"value": n, "limit": want}}
    gap = None
    if pick:
        t0 = time.perf_counter()
        gap, cgap = gaps(block, m, seed, pick, pack, control)
        log(f"# reference: {len(pick)} requests, {n} tokens, "
            f"{len(rows_of(pick, pack))} rows, "
            f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
        if control:
            checks["program_logit_gap"] = {"value": gap, "limit": limit}
            gap = cgap
    checks["logit_gap"] = {"value": gap, "limit": limit}
    correct = (gap is not None and gap <= limit and n >= want
               and short == 0 and oov == 0)
    return {"correct": bool(correct), "checks": checks}
