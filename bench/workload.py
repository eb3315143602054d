"""The one traffic generator: turns a traffic file into requests.

A traffic file (``bench/traffic/<mix>.json``) holds parameters only:

  loop        "open" (arrivals on a schedule) or "closed" (callers that
              each send their next request when the last one completes)
  requests    size of the request set (one round).  Open loop: those
              not yet due when the window closes are never sent.  Closed
              loop: once every request of a round has been sent, the
              callers go on with the next round, for as long as the
              window lasts (see "Rounds" below)
  rate_per_s  open loop: offered requests per second
  callers     closed loop: number of callers
  lead_in_s   seconds of the cell's own traffic before the window opens
  prompt      lognormal {median, sigma, min, max} of a request's own tokens
  output      lognormal {median, sigma, min, max} of tokens to generate
  sessions    optional {count, zipf_s, prefix: lognormal}: each request
              belongs to a session picked by Zipf; the session's prefix
              (drawn once per run) precedes the request's own tokens
  levels      how many distinct values a length takes (quantile points)

Every seed replays the same schedule: the same requests (lengths and
sessions) in the same order with the same arrival gaps, drawn once from
the file's distributions; the seed draws the token ids (and, in the
harness, the weights).  With the few dozen requests a window holds at
this system's speed, reordering them alone moved a tail by tens of
percent from seed to seed; replaying one schedule keeps each seed's work
the same, and fixes per cell the shapes the warm-up has to cover.

Rounds.  A closed loop's supply must not depend on the program's speed:
a program fast enough to send every request of the set would otherwise
leave the chip idle for the rest of the window.  So ``rounds`` yields the
set again and again.  Round 0 is ``generate``'s set.  Round r >= 1 has
round 0's lengths, sessions and ``max_new`` in round 0's order; its own
token ids are drawn from the same seed after round r-1's, so round 0 is
the same whether or not later rounds are drawn, and unique prompts stay
unique (no round hits the prefix cache on an earlier round's blocks).  A
session's prefix is drawn once per run and is the same in every round.
Each request's ``idx`` is ``r * requests + j``, distinct over all
rounds.  Every round has round 0's lengths, so warming round 0's shapes
warms them all.  An open loop's supply is its schedule: it has round 0
alone.
"""
from __future__ import annotations

import itertools
import json
import math
from collections.abc import Iterator
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Spec:
    """One request as the traffic defines it (no program types here)."""
    idx: int
    due_s: float | None       # open loop: offset from the schedule start
    session: int | None
    prefix_len: int           # tokens shared with the session (0: none)
    prompt: np.ndarray        # (P,) int32, prefix included
    max_new: int


def load_traffic(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic file {path}")
    return json.loads(path.read_text())


def lognormal_levels(d: dict, n: int) -> list[int]:
    """``n`` quantile points of a clipped lognormal, as whole tokens."""
    nd = NormalDist()
    mu = math.log(d["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(math.exp(mu + d["sigma"] * z)),
                               d["min"]), d["max"])))
    return out


def _sizes(d: dict, n: int, levels: int) -> np.ndarray:
    """``n`` lengths over ``levels`` quantile points, in ascending order."""
    pts = lognormal_levels(d, min(levels, n))
    return np.array([pts[i * len(pts) // n] for i in range(n)], np.int64)


def _zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Requests per popularity rank: ``n`` split over ``k`` ranks by Zipf."""
    w = np.array([(r + 1) ** -s for r in range(k)])
    exact = n * w / w.sum()
    c = np.floor(exact).astype(np.int64)
    for r in np.argsort(-(exact - c))[:n - c.sum()]:
        c[r] += 1
    return c


def _gaps(n: int, rate: float) -> np.ndarray:
    """``n`` quantile points of an exponential gap of mean 1/``rate``."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def generate(t: dict, seed: int, vocab: int) -> list[Spec]:
    """The cell's requests for one run (round 0): the schedule is the same
    for every seed, the token ids are drawn from ``seed``."""
    return next(rounds(t, seed, vocab))


def rounds(t: dict, seed: int, vocab: int) -> Iterator[list[Spec]]:
    """Round 0, then round 1, 2, ... of the request set, drawn lazily."""
    fixed = np.random.default_rng(0)         # the schedule, for every seed
    rng = np.random.default_rng(seed)        # this run's tokens
    n = int(t["requests"])
    levels = int(t.get("levels", 64))
    own = fixed.permutation(_sizes(t["prompt"], n, levels))
    new = fixed.permutation(_sizes(t["output"], n, levels))

    sess = [None] * n
    rank_len: list[int] = []
    if "sessions" in t:
        s = t["sessions"]
        k = int(s["count"])
        # the prefix length of each popularity rank is fixed too, so no
        # seed decides whether the hottest session has the longest prefix
        pts = lognormal_levels(s["prefix"], k)
        rank_len = [pts[o] for o in fixed.permutation(k)]
        counts = _zipf_counts(n, k, float(s["zipf_s"]))
        sess = [int(r) for r in fixed.permutation(np.repeat(np.arange(k),
                                                            counts))]
    prefixes = [rng.integers(0, vocab, ln).astype(np.int32)
                for ln in rank_len]

    if t["loop"] == "open":
        due = np.cumsum(fixed.permutation(_gaps(n, t["rate_per_s"])))
        due = list(due - due[0])             # the first request opens the run
    else:
        due = [None] * n                     # a caller sends it when free

    order = fixed.permutation(n)
    # an open loop's supply is its schedule; a closed loop never runs dry
    for rnd in range(1) if t["loop"] == "open" else itertools.count():
        specs = []
        for j, i in enumerate(order):
            body = rng.integers(0, vocab, int(own[i])).astype(np.int32)
            r = sess[i]
            if r is not None:
                body = np.concatenate([prefixes[r], body])
            specs.append(Spec(idx=rnd * n + j, due_s=None if due[j] is None
                              else float(due[j]), session=r,
                              prefix_len=rank_len[r] if r is not None else 0,
                              prompt=body, max_new=int(new[i])))
        yield specs
