"""The one general traffic generator: a pure function of a mix's data file
and ``--seed``.

Two kinds of mix:

* ``token_stream`` (training): ``corpus()`` gives the packed stream of ids.
* ``requests`` (serving): ``requests()`` gives arrivals with prompt and
  answer lengths. Lengths and inter-arrival gaps are the QUANTILES of their
  distributions at ``(i + 0.5) / n`` (a Poisson process given its count,
  stratified), in an order drawn from the MIX's own ``order_seed``: every
  run of a cell offers the same schedule of sizes and gaps, and ``--seed``
  draws the token ids (and the weights). With an order drawn from
  ``--seed`` a 90th percentile over the 58 requests of a window swung by a
  fifth from seed to seed (PERF.md).
"""

from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    with open(HERE / f"{name}.json") as f:
        return json.load(f)


@dataclass
class Arrival:
    due_s: float          # relative to the start of the window; < 0 = lead-in
    prompt: np.ndarray    # (P,) int32
    max_new_tokens: int


def corpus(mix: dict, seed: int, vocab_size: int, n_batches: int) -> np.ndarray:
    """``n_batches * batch`` rows of ``seq_len`` ids, flat, int32."""
    rng = np.random.default_rng([int(seed), 1])
    n = n_batches * mix["batch"] * mix["seq_len"]
    return rng.integers(0, vocab_size, size=n, dtype=np.int32)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped log-normal, as whole numbers."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    norm = statistics.NormalDist()
    z = np.array([norm.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


BLOCK = 8


def _spread_order(values: np.ndarray, rng) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``BLOCK`` neighbours holds one value from each ``BLOCK``-quantile of
    the set: the large and the small are spread evenly over the window
    instead of bunching."""
    n = len(values)
    ranked = np.sort(values)
    strata = [list(rng.permutation(ranked[n * s // BLOCK: n * (s + 1) // BLOCK]))
              for s in range(BLOCK)]
    out = []
    while any(strata):
        block = [stratum.pop() for stratum in strata if stratum]
        out.extend(rng.permutation(block))
    return np.asarray(out, dtype=values.dtype)


def requests(mix: dict, seed: int, *, rate_per_s: float, seconds: float,
             lead_in_s: float, vocab_size: int) -> list[Arrival]:
    """Arrivals of the window ``[0, seconds)`` and, before them, of a
    lead-in ``[-lead_in_s, 0)``, sorted by due time. The lead-in is the
    window's own last ``lead_in_s`` seconds moved to the front (the window
    wrapped around): what is in flight when the window opens is what will
    be in flight when it closes, so the tokens a window delivers do not
    depend on which requests happen to straddle its edges."""
    order = np.random.default_rng([int(mix["order_seed"]), 2])
    ids_rng = np.random.default_rng([int(seed), 3])
    n = int(round(rate_per_s * seconds))
    if n < 1:
        return []
    if mix["arrivals"] != "poisson_given_count":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    # Exponential gaps at their mid-quantiles, rescaled to fill the window.
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = _spread_order(gaps, order) * (seconds / gaps.sum())
    due = np.cumsum(gaps) - gaps / 2
    prompts = _spread_order(_quantiles(mix["prompt"], n), order)
    answers = _spread_order(_quantiles(mix["answer"], n), order)
    window = []
    for d, p, a in zip(due, prompts, answers):
        ids = ids_rng.integers(0, vocab_size, size=int(p), dtype=np.int32)
        window.append(Arrival(float(d), ids, int(min(a, mix["max_total"] - p))))
    lead = [
        Arrival(a.due_s - seconds, a.prompt, a.max_new_tokens)
        for a in window if a.due_s >= seconds - lead_in_s
    ]
    return lead + window
