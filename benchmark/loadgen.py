"""The one traffic generator: every serving mix is a data file of its
parameters (`benchmark/traffic/<mix>.json`), never code.

Everything is drawn from the seed and from nothing else, and the amount
of work is fixed by the mix, not by the draw:

- **Arrivals** (open loop): `round(rate x seconds)` arrivals in the
  window, whatever the seed. Gaps are Gamma(1/cv^2) (cv 1: a Poisson
  process; cv 3: bursts), and the arrival times are the running sum of
  the gaps scaled to the window: for cv 1 that is exactly a Poisson
  process conditioned on its count. A request is *due* at its arrival
  time, and every latency is taken from then (`common/loadharness.py`'s
  rule): a stalled server makes the numbers worse instead of slowing the
  offered load. How late the generator itself ran is reported.
- **Lengths**: stratified. A block of n draws takes one value from each
  of the n equal-probability slices of the distribution, in an order
  shuffled by the seed, so two seeds offer (nearly) the same total of
  tokens in another order.
- **Prompts**: uniform tokens over the published vocabulary; with
  `shared_prefix`, a Zipf-popular system prompt in front of a distinct
  suffix (`serving/loadgen.py::zipf_prefix_prompts`'s shape); with
  `repeat`, a short motif tiled to the prompt's length (text that
  repeats itself, for n-gram speculation).

Closed loop (`"loop": "closed"`): `clients` callers, each sending its
next request when the last one finished; request i is the same whatever
client takes it.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

#: Stream ids folded into the seed, so that arrivals, lengths and tokens
#: of the window and of the pre-roll never share a draw.
_ARRIVALS, _PROMPT_LEN, _OUTPUT_LEN, _TOKENS, _PREFIX, _PICK = range(6)
LENGTH_BLOCK = 256


class TrafficRequest(NamedTuple):
    index: int
    due_s: float            # seconds from the window's start (open loop)
    prompt: List[int]
    max_new_tokens: int


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, *stream])


def _ppf(dist: Dict[str, Any], p: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution, clipped to [min, max]."""
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(p.shape, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + p * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(q)) for q in p])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", float("inf"))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified_lengths(dist: Dict[str, Any], n: int, seed: int,
                       *stream: int) -> np.ndarray:
    """n lengths, one from each of the distribution's n equal slices."""
    rng = _rng(seed, *stream)
    p = (rng.permutation(n) + rng.random(n)) / n
    return _ppf(dist, np.clip(p, 1e-9, 1 - 1e-9))


def arrival_times(rate_per_s: float, seconds: float, seed: int,
                  cv: float = 1.0, *stream: int) -> np.ndarray:
    """`round(rate x seconds)` due times in [0, seconds), ascending."""
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        return np.zeros((0,))
    rng = _rng(seed, _ARRIVALS, *stream)
    gaps = rng.gamma(1.0 / (cv * cv), 1.0, size=n + 1)
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()


class Mix:
    """Requests of one traffic file under one seed. `request(i)` is a
    pure function of (mix, seed, stream, i)."""

    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int,
                 stream: int = 0) -> None:
        self.traffic = traffic
        self.seed = seed
        self.vocab = vocab
        self.stream = stream
        self.max_total = int(traffic.get("max_total_tokens", 1 << 30))
        self._blocks: Dict[int, Any] = {}
        self._prefixes: Optional[List[List[int]]] = None
        self._prefix_weights: Optional[np.ndarray] = None
        sp = traffic.get("shared_prefix")
        if sp:
            k = int(sp["prompts"])
            lens = stratified_lengths(sp["tokens"], k, seed, _PREFIX)
            self._prefixes = [
                _rng(seed, _PREFIX, j).integers(0, vocab, int(n)).tolist()
                for j, n in enumerate(lens)
            ]
            w = 1.0 / np.arange(1, k + 1) ** float(sp.get("zipf_skew", 1.1))
            self._prefix_weights = w / w.sum()

    def _lengths(self, block: int):
        if block not in self._blocks:
            t = self.traffic
            self._blocks[block] = (
                stratified_lengths(t["prompt_tokens"], LENGTH_BLOCK,
                                   self.seed, _PROMPT_LEN, self.stream, block),
                stratified_lengths(t["output_tokens"], LENGTH_BLOCK,
                                   self.seed, _OUTPUT_LEN, self.stream, block),
            )
        return self._blocks[block]

    def request(self, i: int, due_s: float = 0.0) -> TrafficRequest:
        prompts, outputs = self._lengths(i // LENGTH_BLOCK)
        n_prompt = int(prompts[i % LENGTH_BLOCK])
        n_out = int(outputs[i % LENGTH_BLOCK])
        n_out = max(1, min(n_out, self.max_total - n_prompt))
        rng = _rng(self.seed, _TOKENS, self.stream, i)
        rep = self.traffic.get("repeat")
        if self._prefixes is not None:
            j = int(_rng(self.seed, _PICK, self.stream, i).choice(
                len(self._prefixes), p=self._prefix_weights))
            head = self._prefixes[j][:max(n_prompt - 1, 0)]
            prompt = head + rng.integers(
                0, self.vocab, n_prompt - len(head)).tolist()
        elif rep:
            motif = rng.integers(0, self.vocab, int(rep["motif_tokens"]))
            prompt = np.resize(motif, n_prompt).tolist()
        else:
            prompt = rng.integers(0, self.vocab, n_prompt).tolist()
        return TrafficRequest(i, float(due_s), prompt, n_out)

    def open_loop(self, seconds: float) -> List[TrafficRequest]:
        """The window's schedule: every request due in [0, seconds)."""
        arr = self.traffic["arrivals"]
        due = arrival_times(float(arr["rate_per_s"]), seconds, self.seed,
                            float(arr.get("cv", 1.0)), self.stream)
        return [self.request(i, t) for i, t in enumerate(due)]


def quantile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default);
    the value itself for one sample. Raises on none: a metric with no
    sample is left out by its reader, not reported as 0."""
    if len(values) == 0:
        raise ValueError("quantile of no samples")
    return float(np.quantile(np.asarray(values, np.float64), q))
