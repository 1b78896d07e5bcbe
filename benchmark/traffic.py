"""One general traffic generator: a mix is a data file of parameters
(``benchmark/traffic/<mix>.json``) and this module turns it and a seed into
requests. Imports numpy only: the load generator's process never touches JAX.

A mix file:

    {"loop": "open",                      # or "closed"
     "rate_rps": 5.6,                     # open: mean arrivals per second
     "arrival_cv": 1.0,                   # open: 1 = Poisson, >1 = bursts
     "clients": 16,                       # closed: callers that each wait
     "ramp_s": 8,                         # load before the window opens
     "grace_s": 10,                       # wait for first tokens after it
     "prompt_tokens": {"dist": ...},      # see ``draw``
     "output_tokens": {"dist": ...},
     "shared_prefix": {"groups": 32, "tokens": 2048, "zipf": 1.1}  # optional
    }

Every seed offers the same work at the same pace. The sizes and the gaps
between arrivals are drawn once from the mix's own ``base_seed`` (for a given
window length); the run's ``--seed`` picks the token ids and deals sizes and
gaps out in another order, but only inside consecutive blocks of
``shuffle_block`` requests (8 unless the mix says otherwise). Dealt over the
whole run instead, the order moved the number of arrivals inside a 50 s
window by 10% and the tokens per second with it (spread 7.8% over six seeds
against 0.3% between two runs of one seed; my chip runs, PR 27).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n whole sizes from a distribution given as data."""
    dist = spec["dist"]
    if dist == "const":
        out = np.full(n, spec["value"], dtype=np.float64)
    elif dist == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, size=n).astype(
            np.float64)
    elif dist == "lognormal":
        out = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif dist == "mixture":
        weights = np.array([p["weight"] for p in spec["parts"]], np.float64)
        which = rng.choice(len(weights), size=n, p=weights / weights.sum())
        out = np.zeros(n)
        for i, part in enumerate(spec["parts"]):
            sel = which == i
            out[sel] = draw(part, int(sel.sum()), rng)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec:
        out = np.maximum(out, spec["min"])
    if "max" in spec:
        out = np.minimum(out, spec["max"])
    return np.rint(out).astype(np.int64)


def size_bound(spec: dict, which: str) -> int:
    """The smallest ("min") or largest ("max") size a distribution gives."""
    if spec["dist"] == "const":
        return int(spec["value"])
    if spec["dist"] == "mixture":
        pick = min if which == "min" else max
        return pick(size_bound(p, which) for p in spec["parts"])
    return int(spec[which])


def deal(order: np.random.Generator, n: int, block: int) -> np.ndarray:
    """0..n-1, shuffled inside each consecutive block of ``block``."""
    idx = np.arange(n)
    for lo in range(0, n, block):
        idx[lo:lo + block] = order.permutation(idx[lo:lo + block])
    return idx


def span_s(mix: dict, seconds: float) -> float:
    """From the first offered request to the window's end."""
    return float(mix.get("ramp_s", 0)) + float(seconds)


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             rate_rps: float = None) -> list:
    """The run's requests, in the order they are offered →
    [{"due": s|None, "prompt": [ids], "max_tokens": n}, ...]. ``due`` is
    seconds after the load starts (open loop); a closed loop's callers take
    the next request from the list when their last one ended."""
    base = np.random.default_rng(int(mix.get("base_seed", 0)))
    order = np.random.default_rng(int(seed))
    total = span_s(mix, seconds)
    block = int(mix.get("shuffle_block", 8))
    if mix["loop"] == "open":
        rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
        n = max(1, int(round(rate * total)))
        cv = float(mix.get("arrival_cv", 1.0))
        # gamma gaps with the mean 1/rate and this coefficient of
        # variation (cv 1: exponential, a Poisson process), scaled so that
        # the n arrivals fill the span exactly
        gaps = base.gamma(1.0 / cv ** 2, cv ** 2, size=n)
        gaps *= total / gaps.sum()
        gaps = gaps[deal(order, n, block)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        # more than the callers can finish; what is used depends on the
        # system's speed
        n = int(mix.get("pool", 4096))
        due = [None] * n
    sizes = deal(order, n, block)       # a prompt and its answer together
    plen = draw(mix["prompt_tokens"], n, base)[sizes]
    olen = draw(mix["output_tokens"], n, base)[sizes]
    shared = mix.get("shared_prefix")
    prefixes, group_of = None, None
    if shared:
        g = int(shared["groups"])
        prefixes = base.integers(0, vocab, size=(g, int(shared["tokens"])))
        ranks = np.arange(1, g + 1, dtype=np.float64)
        p = ranks ** -float(shared.get("zipf", 1.0))
        group_of = order.choice(g, size=n, p=p / p.sum())
    out = []
    for i in range(n):
        ids = order.integers(0, vocab, size=int(plen[i]))
        if prefixes is not None:
            pre = prefixes[group_of[i]][:max(0, int(plen[i]) - 1)]
            ids[:len(pre)] = pre
        out.append({"due": None if due[i] is None else float(due[i]),
                    "prompt": ids.tolist(), "max_tokens": int(olen[i])})
    return out


def buckets_used(mix: dict, engine_buckets: list) -> list:
    """The prefill buckets this mix's prompts can land in: from the one
    that takes the shortest prompt to the one that takes the longest."""
    lo = size_bound(mix["prompt_tokens"], "min")
    hi = size_bound(mix["prompt_tokens"], "max")
    buckets = sorted(engine_buckets)
    first = min(b for b in buckets if b >= lo)
    last = min(b for b in buckets if b >= hi)
    return [b for b in buckets if first <= b <= last]
