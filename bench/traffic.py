"""The one traffic generator: a mix file of parameters in, requests out.

A mix is a JSON file under ``bench/traffic/``. A seed changes the token
ids, drawn over the configuration's vocabulary, and never how much work a
run holds: request sizes are the same, in the same order, for every seed.
Lengths are the distribution's quantiles at (i + 0.5) / n, laid out so
that every aligned run of the engine's ``max_batch`` consecutive requests
draws one from each of ``max_batch`` equal slices of the distribution, with
prompt and output lengths paired by a fixed shuffle. So every batch of a
run spans the distribution. (A seed-shuffled order made the offline batch
read 5% apart between seeds: a 30-s window is shorter than a request, so
which short request started first set how many refills fell inside it.)
Poisson gaps are the exponential's quantiles, shuffled by the seed.

Serving mixes (``"kind": "serve"``) name:

* ``arrival``: ``{"process": "all_at_once"}`` (an offline batch, every
  request due at 0) or ``{"process": "poisson", "rate_per_s": r}``;
* ``requests``: how many, for all-at-once; a Poisson mix holds enough to
  cover its lead-in, the window and the tail after it;
* ``prompt`` and ``output``: token-length distributions,
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
  or ``{"dist": "uniform", "min": a, "max": b}``;
* ``tenants`` (optional): ``{"n": 8, "prefix_len": 512, "zipf_s": 1.1}``,
  a shared system prompt per tenant, picked by Zipf popularity, in front
  of each prompt;
* ``window``: ``"batch_decoding"`` (starts once ``max_batch`` requests are
  streaming at once: every row of the batch past its prefill and decoding)
  or ``{"lead_in_s": t}`` (starts t seconds after the first arrival).

A quantize mix (``"kind": "quantize"``) holds the solver's settings.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Request:
    index: int
    due_s: float              # seconds after the schedule's start
    prompt: np.ndarray        # int32 token ids
    max_tokens: int
    tenant: Optional[int] = None


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n whole-token lengths at the quantiles (i + 0.5) / n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"] + 1)
        x = np.floor(x)
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, spec.get("min", 1), spec["max"]).astype(np.int64)


def spread_order(n: int) -> np.ndarray:
    """0 .. n-1 in bit-reversed (van der Corput) order: any aligned run of
    2**k consecutive entries is spread evenly over the whole range."""
    bits = max(1, (n - 1).bit_length())
    rev = np.array([int(format(i, f"0{bits}b")[::-1], 2)
                    for i in range(1 << bits)])
    return rev[rev < n]


def stratified_sizes(mix: dict, n: int, block: int) -> tuple:
    """(prompt lengths, output lengths) in request order, the same for
    every seed; every aligned run of ``block`` spans the distribution."""
    order = spread_order(n)
    prompt = quantiles(mix["prompt"], n)[order]
    output = quantiles(mix["output"], n)[order]
    pairing = np.random.default_rng(0)
    for s in range(0, n, block):
        output[s:s + block] = pairing.permutation(output[s:s + block])
    return prompt, output


def zipf_counts(n_tenants: int, s: float, n: int) -> np.ndarray:
    """Requests per tenant: Zipf(s) shares of n, rounded to sum to n."""
    w = 1.0 / np.arange(1, n_tenants + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(exact - counts))[:rest]] += 1
    return counts


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrival"]
    if arr["process"] == "all_at_once":
        return int(mix["requests"])
    lead = float(mix["window"]["lead_in_s"])
    tail = float(mix.get("tail_s", 60.0))
    return int(math.ceil(arr["rate_per_s"] * (lead + seconds + tail)))


def generate(mix: dict, seed: int, vocab: int, seconds: float,
             max_batch: int) -> List[Request]:
    """The requests of one run, from ``seed``, for an engine that runs
    ``max_batch`` sequences at once."""
    if mix.get("kind") != "serve":
        raise ValueError("generate() makes serving traffic")
    n = n_requests(mix, seconds)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    prompt_len, out_len = stratified_sizes(mix, n, max_batch)
    arr = mix["arrival"]
    if arr["process"] == "all_at_once":
        due = np.zeros(n)
    elif arr["process"] == "poisson":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / arr["rate_per_s"]
        due = np.cumsum(rng.permutation(gaps))
        due -= due[0]
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    tenant_of = [None] * n
    prefixes = []
    ten = mix.get("tenants")
    if ten:
        prefixes = [rng.integers(0, vocab, ten["prefix_len"], dtype=np.int32)
                    for _ in range(ten["n"])]
        counts = zipf_counts(ten["n"], ten["zipf_s"], n)
        tenant_of = rng.permutation(np.repeat(np.arange(ten["n"]), counts))
    reqs = []
    for i in range(n):
        body = rng.integers(0, vocab, int(prompt_len[i]), dtype=np.int32)
        t = tenant_of[i]
        prompt = body if t is None else np.concatenate([prefixes[t], body])
        reqs.append(Request(i, float(due[i]), prompt, int(out_len[i]),
                            None if t is None else int(t)))
    return reqs
