"""The one traffic generator: a mix file of parameters -> requests.

A mix (``traffic/<name>.json``) states the arrivals and the length
distributions; this module turns it and a seed into a list of
``Request(due, prompt, max_new)``.  Every seed gets the same multiset of
prompt lengths, output lengths and inter-arrival gaps — the quantiles of
the stated distributions — in an order drawn from the seed, and its own
token ids.  So two seeds differ in which request comes when and what it
says, not in how much work the run holds.

Arrivals are Poisson at ``rate_per_s``: the gaps are exponential
quantiles with that mean.  The run's window holds ``rate_per_s x
seconds`` requests, due from 0 on; a lead-in of about ``lead_in_s``
seconds before it holds ``rate_per_s x lead_in_s`` more, the last due
one mean gap before 0, so the window opens on an engine already under
the mix's load.  Each part has its own fixed multiset of gaps and lengths,
so the window holds the same work for every seed.

Order (optional ``order`` block of the mix): with ``strata`` k, each
multiset is split into k equal strata by size, and every k consecutive
requests of a part hold one value of each stratum (its own draw for the
prompt lengths, the answer lengths and the gaps), so no stretch of a
run piles up long prompts or short gaps; with ``lead_in_order_seed``,
the lead-in's lengths and gaps come in the order drawn from that number
for every run, so every seed opens its window on the same load (the
token ids are still the run's own).  Without the block, plain
permutations.

Length distributions: ``lognormal`` with ``median``, ``sigma`` (of the
log), clipped to ``[min, max]``; ``uniform`` over ``[min, max]``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (P,) int32 token ids
    max_new: int


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def quantiles(n: int) -> np.ndarray:
    """The n mid-point probabilities (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles of ``spec``, ascending, clipped."""
    q = quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def arrange(x: np.ndarray, strata: int, rng) -> np.ndarray:
    """x (ascending) in an order drawn from ``rng`` in which every run of
    ``strata`` consecutive values holds one value of each of ``strata``
    equal parts of x; with one stratum, a plain permutation."""
    if strata <= 1:
        return rng.permutation(x)
    parts = [rng.permutation(s) for s in np.array_split(x, strata)]
    out = []
    for j in range(len(parts[0])):
        out.extend(rng.permutation([s[j] for s in parts if j < len(s)]))
    return np.asarray(out, dtype=x.dtype)


def part(mix: dict, n: int, start, rng, vocab: int,
         order_rng=None) -> list:
    """n requests at the mix's rate, due from ``start`` on; with ``start``
    None, the last is due one mean gap before 0.  The order of lengths
    and gaps is drawn from ``order_rng`` where given, the token ids from
    ``rng``."""
    o = rng if order_rng is None else order_rng
    k = mix.get("order", {}).get("strata", 1)
    p_len = arrange(lengths(mix["prompt_tokens"], n), k, o)
    o_len = arrange(lengths(mix["output_tokens"], n), k, o)
    gaps = -np.log1p(-quantiles(n)) / mix["arrivals"]["rate_per_s"]
    due = np.cumsum(arrange(gaps, k, o))
    due += start if start is not None else (
        -due[-1] - 1.0 / mix["arrivals"]["rate_per_s"])
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(p_len[i]), dtype=np.int32),
                    int(o_len[i]))
            for i in range(n)]


def lead_in(mix: dict) -> float:
    """Seconds from the start of the lead-in to the window's opening: the
    lead-in's gaps and one mean gap more, the same for every seed (the
    first request comes one of those gaps after the start)."""
    lead = float(mix["arrivals"].get("lead_in_s", 0))
    if lead <= 0:
        return 0.0
    rate = mix["arrivals"]["rate_per_s"]
    n = count(mix, lead)
    return float(np.sum(-np.log1p(-quantiles(n)) / rate) + 1.0 / rate)


def generate(mix: dict, *, seed: int, seconds: float,
             vocab: int) -> list[Request]:
    """The run's requests in due order: the lead-in's, then the
    window's."""
    if mix["arrivals"]["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {mix['arrivals']['kind']!r}")
    rng = np.random.default_rng(seed)
    lead = float(mix["arrivals"].get("lead_in_s", 0))
    reqs = []
    if lead > 0:
        fixed = mix.get("order", {}).get("lead_in_order_seed")
        reqs = part(mix, count(mix, lead), None, rng, vocab,
                    None if fixed is None else np.random.default_rng(fixed))
    return reqs + part(mix, count(mix, seconds), 0.0, rng, vocab)
