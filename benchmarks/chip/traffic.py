"""Traffic for the chip benchmark: one general generator, fed by the data
files under ``traffic/``.

The arithmetic of arrivals is copied from the repository's scenario
library (``repro.serve.scenarios``: seeded open-loop Poisson arrivals as
(due time, prompt length, output length) rows) so that the benchmark's
yardstick cannot change with the program. Lengths are different: the
library draws them from 3- or 4-point supports, so every length is an
atom and a tail percentile lands on an atom. Here lengths are quantiles
of a dense truncated log-normal, one per request.

Steadiness: lengths and inter-arrival gaps are stratified quantiles,
fixed by the file, put in an order fixed by ``ORDER_SEED``; the run's
seed draws only the token ids. Every seed gets the same work in the same
order: on the chip, runs of different seeds whose order differed spread
by 32% in TTFT p90, and two runs of one order by 1–2% (PERF.md).

Two loops:

* ``open``: independent users. ``rate_rps`` arrivals per second for the
  whole window, each request timed from its due time.
* ``closed``: one client per engine slot; a client sends its next
  request when its last one finished. The lengths are a fixed replay
  list that clients take in order; the seed changes only token ids.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

_N = NormalDist()
ORDER_SEED = 0         # the order of lengths and gaps, for every run


@dataclass(frozen=True)
class Item:
    """One request of the traffic: due time (open loop; seconds from
    the window's start), prompt token ids, and output length."""

    rid: int
    due: float
    prompt: np.ndarray
    out_len: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> List[int]:
    """``n`` stratified quantiles, at (i + 1/2)/n, of a log-normal with
    ``median`` and log-scale ``sigma`` truncated to [lo, hi] (truncated,
    not clamped: no mass piles up on a bound), rounded to integers."""
    if not (1 <= lo <= hi) or n < 1 or sigma <= 0 or median <= 0:
        raise ValueError(f"bad length distribution: n={n} median={median} "
                         f"sigma={sigma} [{lo}, {hi}]")
    mu = math.log(median)
    f_lo = _N.cdf((math.log(lo) - mu) / sigma)
    f_hi = _N.cdf((math.log(hi) - mu) / sigma)
    out = []
    for i in range(n):
        u = f_lo + (i + 0.5) / n * (f_hi - f_lo)
        x = math.exp(mu + sigma * _N.inv_cdf(u))
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def exponential_gaps(n: int, rate_rps: float) -> List[float]:
    """``n`` stratified quantiles of the exponential inter-arrival time
    of a Poisson process, scaled so that they sum to exactly
    ``n / rate_rps``: the mean rate holds in every run."""
    if n < 1 or rate_rps <= 0:
        raise ValueError(f"bad arrivals: n={n} rate={rate_rps}")
    g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / rate_rps / sum(g)
    return [x * scale for x in g]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


def open_loop(spec: Dict, seconds: float, seed: int,
              vocab: int) -> List[Item]:
    """Every request due in a window of ``seconds``: ``rate_rps *
    seconds`` of them, the last one due before the window closes."""
    n = int(spec["rate_rps"] * seconds)
    p, o = spec["prompt_len"], spec["output_len"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                               o["max"])
    gaps = exponential_gaps(n, spec["rate_rps"])
    order = np.random.default_rng(ORDER_SEED)
    prompts = order.permutation(prompts)
    outs = order.permutation(outs)
    gaps = order.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]      # the first request opens the window
    rng = np.random.default_rng(seed)
    return [Item(rid=i, due=float(due[i]),
                 prompt=_tokens(rng, int(prompts[i]), vocab),
                 out_len=int(outs[i])) for i in range(n)]


def closed_loop(spec: Dict, seed: int, vocab: int,
                n: int) -> List[Item]:
    """The first ``n`` requests of the replay list, cycled; the seed
    draws token ids only."""
    rows: Sequence = spec["replay"]
    rng = np.random.default_rng(seed)
    return [Item(rid=i, due=0.0,
                 prompt=_tokens(rng, int(rows[i % len(rows)][0]), vocab),
                 out_len=int(rows[i % len(rows)][1])) for i in range(n)]


def check_fits(items: Sequence[Item], max_len: int):
    """Every request has to fit the cache: no refusals by design."""
    for it in items:
        if len(it.prompt) + it.out_len > max_len:
            raise ValueError(f"request {it.rid}: prompt {len(it.prompt)} + "
                             f"output {it.out_len} > max_len {max_len}")
