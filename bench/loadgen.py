"""The one traffic generator: reads a mix's data file and draws requests.

A mix file (``bench/traffic/<mix>.json``) states the loop (closed with
``clients``, or open with ``rate_per_s``), an optional shared prefix,
the length distributions of each request's own prompt tokens and of
its output, the engine's slots, ``max_len`` and prefill chunk, and how
many finished requests a run compares with the reference.

Every seed gets the same work in another order. Each distribution is
cut into ``strata`` equal-probability strata and yields the value at
each stratum's middle quantile; a seed only permutes them (afresh for
every cycle through the strata). Open-loop gaps are drawn the same way
from the exponential distribution. Token ids are uniform over the
vocabulary, from the seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def quantile(dist: Dict[str, Any], u: float) -> int:
    """The value of a length distribution at probability ``u``."""
    kind, lo, hi = dist["dist"], dist["min"], dist["max"]
    if kind == "uniform":
        x = lo + math.floor(u * (hi - lo + 1))
    elif kind == "loguniform":
        x = round(math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo))))
    elif kind == "lognormal":
        x = round(dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(u)))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return int(min(max(x, lo), hi))


def strata_values(dist: Dict[str, Any], strata: int) -> List[int]:
    return [quantile(dist, (j + 0.5) / strata) for j in range(strata)]


def _cycled(values, rng) -> Iterator:
    while True:
        for j in rng.permutation(len(values)):
            yield values[j]


class Traffic:
    """Requests and arrivals of one mix for one seed."""

    def __init__(self, spec: Dict[str, Any], seed: int, vocab: int):
        self.spec = spec
        self.vocab = vocab
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {self.loop}")
        k = spec["strata"]
        root = np.random.SeedSequence(seed)
        r_tok, r_prompt, r_out, r_gap, self.sample_rng = (
            np.random.default_rng(s) for s in root.spawn(5))
        self._tok = r_tok
        self.shared = self.tokens(spec.get("shared_prefix", 0))
        self._prompt = _cycled(strata_values(spec["prompt"], k), r_prompt)
        self._out = _cycled(strata_values(spec["output"], k), r_out)
        if self.loop == "open":
            rate = spec["rate_per_s"]
            gaps = [-math.log(1.0 - (j + 0.5) / k) / rate for j in range(k)]
            self._gap = _cycled(gaps, r_gap)

    def tokens(self, n: int) -> List[int]:
        return self._tok.integers(0, self.vocab, size=n).tolist()

    def next_request(self) -> Tuple[List[int], int]:
        """``(prompt, max_new_tokens)`` of the next request sent."""
        own = next(self._prompt)
        return self.shared + self.tokens(own), int(next(self._out))

    def arrivals(self, seconds: float) -> List[float]:
        """Open loop: due times (seconds from the window's start)."""
        out, t = [], 0.0
        while True:
            t += next(self._gap)
            if t >= seconds:
                return out
            out.append(t)

    def warmup_request(self) -> Tuple[List[int], int]:
        """A short request of the mix's own kind for set-up."""
        p, o = self.spec["prompt"], self.spec["output"]
        return self.shared + self.tokens(p["min"]), int(o["min"])


def check_geometry(spec: Dict[str, Any], page: int) -> None:
    """A mix's shapes that the engine and the reference both need."""
    eng = spec["engine"]
    s, c, m = spec.get("shared_prefix", 0), eng["prefill_chunk"], \
        eng["max_len"]
    need = s + spec["prompt"]["max"] + spec["output"]["max"]
    if s % c or s % page or m % c:
        raise ValueError("shared prefix and max_len must be whole chunks "
                         "and pages")
    if need > m:
        raise ValueError(f"longest request needs {need} rows > max_len {m}")
