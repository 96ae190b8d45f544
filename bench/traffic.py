"""Traffic from a mix file (``bench/traffic/<mix>.json``) and a seed.

Every seed gives the same multiset of prompt lengths, output lengths and
inter-arrival gaps, in another order: the values are the distribution's
quantiles at ``(i + 0.5) / n``, and the seed only permutes them and draws
the token ids.  An open-loop run has two phases, the warm-up and the
measured window, and each holds its own fixed number of arrivals (the
rate times its length) whose gaps add up to its length.  So every seed
puts the same work in the window, and the spread between runs is the
spread of the system, not of the draw.  A backlog's order is fixed as
well: its requests are admitted one by one as slots free, so the order
decides which prefills fall inside the window.  Its prompts and outputs
take their quantiles in van der Corput order (base 2 and base 3), so
every stretch of admissions spreads over the mix's lengths, and the seed
draws only the token ids.

A mix is either open loop (``arrival.process`` "gamma": Gamma-distributed
gaps with the given mean rate and coefficient of variation, bursty when
the CV is above 1) or a backlog (``"backlog"``: every request due at 0).

A request lives for hundreds of decode steps, longer than any run, so a
run does not wait for its pool to fill: ``in_flight`` draws the
population a pool holds in steady state, and set-up admits it before the
traffic starts.  Its size is the mix's ``in_flight`` (``"slots"``: every
slot, for a backlog; ``"little"``: the rate times a request's mean
lifetime, by Little's law).  A request in flight at a random moment has
an output length drawn in proportion to the length (a long request is in
flight for longer) and is uniformly far through it: its prompt carries
the tokens it has already emitted, and it has the rest still to emit.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due: float          # seconds after the traffic starts
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def spread_order(n: int, base: int) -> np.ndarray:
    """0..n-1 ranked by the radical inverse of 0, 1, ... in `base` (van
    der Corput): every run of consecutive entries spreads over 0..n-1."""
    def inverse(i):
        x, f = 0.0, 1.0 / base
        while i:
            i, d = divmod(i, base)
            x += d * f
            f /= base
        return x
    return np.argsort(np.argsort([inverse(i) for i in range(n)]))


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths: quantiles of a lognormal with the given median and sigma,
    clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(arrival: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps (s): quantiles of a Gamma law with mean
    1/rate and the given coefficient of variation."""
    from scipy.stats import gamma

    shape = 1.0 / arrival["cv"] ** 2
    return gamma.ppf(_quantiles(n), shape, scale=1.0 / (arrival["rate_per_s"] * shape))


def _phases(mix: dict, seconds: float) -> list:
    """(start, length, arrivals) of the warm-up and the window."""
    rate = mix["arrival"]["rate_per_s"]
    warm = float(mix["warmup_s"])
    return [(0.0, warm, max(1, round(rate * warm))),
            (warm, seconds, max(1, round(rate * seconds)))]


def n_requests(mix: dict, seconds: float) -> int:
    """Requests a run needs: a backlog's count, or the arrivals of the
    warm-up and the window at the mix's rate."""
    arr = mix["arrival"]
    if arr["process"] == "backlog":
        return int(arr["count"])
    return sum(n for _, _, n in _phases(mix, seconds))


def mean_output(mix: dict) -> float:
    """Mean output length of the mix's law."""
    return float(lengths(mix["output_len"], 4096).mean())


def in_flight(mix: dict, seed: int, n: int, vocab: int) -> list[Request]:
    """`n` requests as a pool in steady state holds them: each with its
    prompt followed by the tokens it has emitted so far, and the number it
    has still to emit.  Every seed gives the same requests' sizes, in
    another order and with other token ids."""
    fixed = np.random.default_rng(0)
    full = np.sort(lengths(mix["output_len"], 4096))
    cdf = np.cumsum(full) / full.sum()
    total = full[np.minimum(np.searchsorted(cdf, _quantiles(n)), len(full) - 1)]
    total = fixed.permutation(total)
    age = np.floor(fixed.permutation(_quantiles(n)) * total).astype(np.int64)
    prompt = fixed.permutation(lengths(mix["prompt_len"], n)) + age
    rng = np.random.default_rng([seed, 2])
    return [Request(0.0, rng.integers(0, vocab, int(prompt[i]), dtype=np.int32),
                    int(total[i] - age[i])) for i in rng.permutation(n)]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """The run's requests in due order."""
    rng = np.random.default_rng(seed)
    arr = mix["arrival"]
    backlog = arr["process"] == "backlog"
    if backlog:
        parts = [(np.zeros(int(arr["count"])), int(arr["count"]))]
    elif arr["process"] == "gamma":
        parts = []
        for t0, length, n in _phases(mix, seconds):
            g = rng.permutation(gaps(arr, n))
            g *= length / g.sum()
            parts.append((t0 + np.concatenate([[0.0], np.cumsum(g[:-1])]), n))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    out = []
    for due, n in parts:
        if backlog:
            prompt = lengths(mix["prompt_len"], n)[spread_order(n, 2)]
            new = lengths(mix["output_len"], n)[spread_order(n, 3)]
        else:
            prompt = rng.permutation(lengths(mix["prompt_len"], n))
            new = rng.permutation(lengths(mix["output_len"], n))
        out += [Request(float(d), rng.integers(0, vocab, int(p), dtype=np.int32),
                        int(o)) for d, p, o in zip(due, prompt, new)]
    return out
