"""The one traffic generator: turns a mix file from ``bench/traffic/`` into
requests, from the run's seed.

A mix file is JSON with these keys:

- ``loop``: ``"open"``, arrivals on a schedule, whatever the server does
  (the only loop this generator knows).
- ``arrivals``: ``{"process": "poisson", "rate_per_s": r}``
  or ``{"process": "onoff", "rate_per_s": r, "on_s": a, "off_s": b}``,
  a fixed cycle of ``a`` seconds of Poisson arrivals at ``r * (a + b) / a``
  followed by ``b`` seconds with none, so that ``r`` is the mean rate.
- ``prompt_tokens``: the prompt length (the served prefill has one shape).
- ``output_tokens``: ``{"dist": "lognormal", "median": m, "sigma": s,
  "min": lo, "max": hi}``, clipped to ``[lo, hi]``.
- ``order_seed``: the seed of the schedule's order.

The schedule is a property of the mix, the same for every run: the gaps
are the exponential's quantiles at ``(i + 0.5) / n`` and the lengths the
lognormal's, each shuffled once by ``order_seed``. Which long request
arrives beside which decides how many sequences decode together, so a
schedule drawn anew per run would move the latencies more than the
server does. A run's own seed draws its prompts (``prompt``), its
weights and the requests it checks. The shapes follow
``PoissonArrivals``, ``MMPPArrivals`` and ``LognormalLen`` of
``repro.sched.workload``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

# independent streams of one seed
_GAPS, _LENGTHS, _PROMPT = 1, 2, 3


@dataclass(frozen=True)
class Arrival:
    """One request of the mix: due ``due_ms`` after the window opens."""
    rid: int
    due_ms: float
    max_new: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


def exp_gaps(n: int, total_ms: float, g: np.random.Generator) -> np.ndarray:
    """``n`` exponential gaps at their quantiles, scaled to sum to
    ``total_ms`` and shuffled."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    return g.permutation(gaps * (total_ms / gaps.sum()))


def output_lengths(spec: dict, n: int, g: np.random.Generator) -> List[int]:
    """``n`` output lengths at the distribution's quantiles, shuffled."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output-length distribution {spec['dist']!r}")
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    vals = [math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)
    return [int(v) for v in g.permutation(vals)]


def due_times(arrivals: dict, seconds: float, g: np.random.Generator
              ) -> List[float]:
    """Due times in ms over a window of ``seconds``, the first at 0."""
    window_ms = seconds * 1e3
    if arrivals["process"] == "poisson":
        n = max(1, round(arrivals["rate_per_s"] * seconds))
        gaps = exp_gaps(n, window_ms, g)
        return list(np.concatenate([[0.0], np.cumsum(gaps[:-1])]))
    if arrivals["process"] == "onoff":
        on_ms, off_ms = arrivals["on_s"] * 1e3, arrivals["off_s"] * 1e3
        rate_on = arrivals["rate_per_s"] * (on_ms + off_ms) / on_ms
        n_on = max(1, round(rate_on * arrivals["on_s"]))
        out, start = [], 0.0
        while start < window_ms:
            gaps = exp_gaps(n_on, on_ms, g)
            phase = start + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
            out.extend(float(t) for t in phase if t < window_ms)
            start += on_ms + off_ms
        return out
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def open_loop(mix: dict, seconds: float) -> List[Arrival]:
    """Every request of the mix that falls due in a window of
    ``seconds``."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    order = mix["order_seed"]
    due = due_times(mix["arrivals"], seconds, rng(order, _GAPS))
    lens = output_lengths(mix["output_tokens"], len(due),
                          rng(order, _LENGTHS))
    return [Arrival(i, float(t), n) for i, (t, n) in enumerate(zip(due, lens))]


def prompt(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Request ``rid``'s prompt, ``[1, length]`` token ids."""
    g = np.random.default_rng([seed % 2**64, _PROMPT, rid])
    return g.integers(0, vocab, size=(1, length), dtype=np.int32)


def max_output(mix: dict) -> int:
    return int(mix["output_tokens"]["max"])
