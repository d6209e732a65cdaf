"""One general generator of traffic, driven by a data file.

A traffic mix is `benchmarks/traffic/<name>.json`.  The program under
test never sees this file or the seed: it sees the generated requests.

The traffic is quasi-random, NOT independent draws.  For a given mix,
rate and duration every seed gets THE SAME multiset of prompt lengths,
answer lengths and inter-arrival gaps: the distribution's quantiles at
(i + 0.5) / n (log-normal lengths, exponential gaps), in another order,
with other token ids.  So two seeds offer the same work and differ only
in how it is interleaved; a seed cannot draw a heavier sample than
another.  What that costs: a count of arrivals that never varies, no
burst longer than the largest quantile gap, and length tails that end at
the outermost quantile, inside the mix's clips (chat at 0.8/s in 10 s
stretches: 8 arrivals a stretch, prompts 131..1124, answers 59..276,
no gap over 3.6 s).  PERF.md section 2 sets the spread of independent
draws beside it.

Serving mixes (`"kind": "open_loop"`):
  prompt / output : {"dist": "lognormal", "median", "sigma", "min", "max"}
  strata_s        : the schedule is laid out in stretches of this length
                    that each offer the same work (default: one stretch)
Training mixes (`"kind": "train_job"`): {"positions": tokens a sequence}
— the program makes its own batch from the seed, so there is nothing to
generate; the file states the shape of the job.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist
from typing import List, NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Req(NamedTuple):
    due_s: float          # seconds after the schedule's start
    prompt: tuple         # token ids
    max_tokens: int


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n quantile points of the distribution, clipped and rounded;
    sorted ascending (the caller permutes)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf(float(p)) for p in _quantiles(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    x = np.clip(x, spec["min"], spec["max"])
    return np.maximum(np.rint(x), 1).astype(np.int64)


def gaps(n: int, rate_per_s: float) -> np.ndarray:
    """n inter-arrival gaps whose mean is exactly 1 / rate: the
    exponential distribution's quantiles, rescaled to the mean."""
    g = -np.log1p(-_quantiles(n))
    return g * (1.0 / rate_per_s) / g.mean()


def schedule(mix: dict, rate_per_s: float, duration_s: float, seed: int,
             vocab_size: int, warm: int = 0,
             splits: Optional[List[float]] = None) -> List[Req]:
    """Requests due in [0, duration_s): due times do not depend on how
    fast anything completes (open loop).  `warm` more requests are due at
    0: the mix's quantile prompts with the mix's quantile answers cut to
    the fractions (i + 0.5) / warm, the residual lives a steady state
    would hold (the same multiset for every seed, like everything else)."""
    if mix.get("kind") != "open_loop":
        raise ValueError("schedule() is for open_loop mixes")
    rng = np.random.RandomState(seed % (2 ** 32))
    out: List[Req] = []
    # Strata: the schedule is laid out stretch by stretch (`strata_s`
    # seconds each, the last one shorter).  A stretch of d seconds gets
    # round(rate x d) requests whose prompt lengths, answer lengths and
    # gaps are the distribution's quantiles for that count, each in a
    # seeded order; so every stretch of every seed offers the same work,
    # and what a seed changes is the order inside a stretch.
    strata = float(mix.get("strata_s", duration_s)) or duration_s
    edges = list(np.arange(0.0, duration_s, strata)) + [duration_s]
    if splits:
        edges = sorted(set(edges) | {float(x) for x in splits
                                     if 0 < x < duration_s})
    for a, b in zip(edges, edges[1:]):
        d = b - a
        n = int(round(rate_per_s * d))
        if n <= 0:
            continue
        p_len = lengths(mix["prompt"], n)[rng.permutation(n)]
        o_len = lengths(mix["output"], n)[rng.permutation(n)]
        g = gaps(n, n / d)[rng.permutation(n)]
        due = a + np.cumsum(g) - g[0] * rng.uniform(0.0, 1.0)
        for i in range(n):
            toks = rng.randint(0, vocab_size, int(p_len[i]))
            out.append(Req(float(due[i]), tuple(int(t) for t in toks),
                           int(o_len[i])))
    if warm > 0:
        wp = lengths(mix["prompt"], warm)[rng.permutation(warm)]
        wo = lengths(mix["output"], warm)[rng.permutation(warm)]
        frac = _quantiles(warm)[rng.permutation(warm)]
        for i in range(warm):
            toks = rng.randint(0, vocab_size, int(wp[i]))
            out.append(Req(0.0, tuple(int(t) for t in toks),
                           max(1, int(round(wo[i] * frac[i])))))
    out.sort(key=lambda r: r.due_s)
    return out


def check_sample(mix: dict, n: int, cover: List[int], max_tokens: int,
                 seed: int, vocab_size: int) -> List[Req]:
    """Requests for the output check: one prompt in every length class
    of `cover` (pairs lo < length <= hi given as the class's hi, in
    ascending order; the prompt takes a seeded length inside the class),
    the rest from the mix's own quantiles."""
    rng = np.random.RandomState((seed + 977) % (2 ** 32))
    lens: List[int] = []
    lo = int(mix["prompt"].get("min", 1))
    for hi in cover:
        hi = int(hi)
        lens.append(int(rng.randint(max(lo, hi // 2 + 1), hi + 1)))
        lo = hi + 1
    rest = max(0, n - len(lens))
    if rest:
        ql = lengths(mix["prompt"], rest)
        lens += [int(x) for x in ql[rng.permutation(rest)]]
    return [Req(0.0, tuple(int(t) for t in rng.randint(0, vocab_size, ln)),
                max_tokens) for ln in lens[:max(n, len(cover))]]
