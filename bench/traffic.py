"""Open-loop traffic: the one generator that reads ``bench/traffic/<mix>.json``.

A mix file holds the length distributions (lognormal, given by median and
sigma, clipped to [min, max]), the arrival process (``poisson`` or ``gamma``
with a coefficient of variation ``cv``) and its rate in requests per second,
and the warm-load rule ``warm``: ``fill`` requests in flight when the load
starts (an integer, or ``"batch"`` for the configuration's ``max_batch``) and
the ``seconds`` of load before the measured window opens.

The warm-up and the window each hold exactly rate x duration arrivals, and
every seed gets the same multiset of request sizes and inter-arrival gaps in
each, drawn once from a fixed stream; ``--seed`` only reorders them and draws
the prompt tokens. So two seeds offer the same work in a different order.

The warm fill stands for requests already in flight in a steady state: a
request found in flight is picked with probability proportional to its output
length (the length-biased distribution), and has a uniformly distributed part
of that length still to go. A fill request therefore asks for that residual,
and its prompt is lengthened by the part already generated, so its context is
as long as it would be in the steady state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

BASE_STREAM = 20231116  # fixed: the multiset of sizes and gaps every seed shares


@dataclass(frozen=True)
class Planned:
    rid: int
    due: float          # seconds after the load starts
    prompt: np.ndarray  # int32 token ids
    max_new: int
    fill: bool          # part of the warm fill (due at 0)


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def inter_arrivals(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    rate = float(spec["rate"])
    if spec["process"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if spec["process"] == "gamma":
        k = 1.0 / float(spec["cv"]) ** 2
        return rng.gamma(k, 1.0 / (rate * k), n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def residual_fill(out_lengths: np.ndarray, n: int, rng: np.random.Generator):
    """(total, remaining) output lengths of ``n`` requests found in flight:
    total drawn length-biased from ``out_lengths``, remaining uniform on
    [1, total]."""
    w = out_lengths / out_lengths.sum()
    total = rng.choice(out_lengths, size=n, p=w)
    remaining = rng.integers(1, total + 1)
    return total, remaining


def fill_count(traffic: dict, max_batch: int) -> int:
    fill = traffic["warm"]["fill"]
    return max_batch if fill == "batch" else int(fill)


def _phase(traffic: dict, start: float, duration: float, base, rng, vocab: int,
           first_rid: int) -> list[Planned]:
    """The open-loop arrivals of one phase: exactly round(rate * duration)
    requests, due in [start, start + duration). Their sizes and gaps are one
    fixed multiset (from ``base``), put in the seed's order; the gaps are
    scaled to fill the phase, which keeps the process's shape (exponential
    or gamma gaps) and fixes its count."""
    k = max(1, round(float(traffic["arrival"]["rate"]) * duration))
    prompts = lognormal_lengths(traffic["prompt"], k, base)
    outputs = lognormal_lengths(traffic["output"], k, base)
    gaps = inter_arrivals(traffic["arrival"], k, base)
    order = rng.permutation(k)
    gaps = gaps[rng.permutation(k)] * (duration / gaps.sum())
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Planned(first_rid + i, float(due[i]),
                    _tokens(rng, int(prompts[order[i]]), vocab),
                    int(outputs[order[i]]), False) for i in range(k)]


def plan(traffic: dict, *, seed: int, seconds: float, max_batch: int,
         vocab: int) -> list[Planned]:
    """Every request of one run, in due order: the warm fill at 0, the
    warm-up's arrivals, then the window's. Each seed sees the same sizes and
    gaps in the warm-up and in the window, in another order."""
    base = np.random.default_rng(BASE_STREAM)
    rng = np.random.default_rng(seed)
    warm = float(traffic["warm"]["seconds"])
    nf = fill_count(traffic, max_batch)
    f_prompt = lognormal_lengths(traffic["prompt"], nf, base)
    f_total, f_left = residual_fill(
        lognormal_lengths(traffic["output"], 4096, base), nf, base)
    reqs: list[Planned] = []
    for j in rng.permutation(nf):
        length = int(f_prompt[j] + f_total[j] - f_left[j])
        reqs.append(Planned(len(reqs), 0.0, _tokens(rng, length, vocab),
                            int(f_left[j]), True))
    if warm > 0:
        reqs += _phase(traffic, 0.0, warm, base, rng, vocab, len(reqs))
    reqs += _phase(traffic, warm, float(seconds), base, rng, vocab, len(reqs))
    return reqs


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int32)
