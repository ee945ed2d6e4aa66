"""The one traffic generator: turns a traffic file's parameters and a seed
into a request schedule on the engine's step clock. Numpy and SciPy only.

Arrivals are a Poisson process on the step clock (the continuous engine
admits at step boundaries and takes no wall-clock arrival time), and lengths
follow the file's distributions. So that every seed offers the same work,
the requests come in blocks of ``block`` (default 32): each block holds the
same inter-arrival gaps, prompt lengths and output lengths, namely the
distributions' quantiles at (i + 1/2) / block, and the seed only shuffles
them within each block (a different shuffle per block and per quantity) and
draws the prompts' token ids. A window that spans a few blocks then sees
nearly the same work under every seed, in another order.

Length distributions (``{"dist": ...}`` in the traffic file):
  lognormal    median, sigma, lo, hi   exp(N(ln median, sigma)), clipped
  uniform      lo, hi                  integers, both ends included
  loguniform   lo, hi                  exp(U(ln lo, ln hi)), rounded
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri

BLOCK = 32


def quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    """The length distribution's quantiles at probabilities ``u``."""
    kind = spec["dist"]
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if kind == "lognormal":
        raw = np.exp(math.log(spec["median"]) + spec["sigma"] * ndtri(u))
    elif kind == "uniform":
        raw = np.floor(lo + u * (hi - lo + 1))
    elif kind == "loguniform":
        raw = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(raw), lo, hi).astype(np.int64)


def mean_length(spec: dict, points: int = 200_000) -> float:
    """Mean of a length distribution, from the distribution itself (its
    quantiles on a fine grid), independent of any run's seed."""
    u = (np.arange(points) + 0.5) / points
    return float(quantiles(spec, u).mean())


def capacity_rate(traffic: dict) -> float:
    """Requests per step the engine can sustain: each of ``slots`` slots
    consumes one token per step and a request holds its slot for
    L + N - 1 steps, so capacity is slots / E[L + N - 1]."""
    occ = (mean_length(traffic["prompt_len"])
           + mean_length(traffic["output_len"]) - 1)
    return traffic["slots"] / occ


def schedule(traffic: dict, seed: int, vocab: int) -> dict:
    """The run's ``traffic["requests"]`` requests: arrival step, prompt
    length, output length and prompt token ids."""
    n = int(traffic["requests"])
    block = int(traffic.get("block", BLOCK))
    u = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-u) / float(traffic["rate"])
    prompt = quantiles(traffic["prompt_len"], u)
    output = quantiles(traffic["output_len"], u)
    rng = np.random.default_rng(seed)
    blocks = -(-n // block)

    def shuffled(a):
        return np.concatenate([a[rng.permutation(block)]
                               for _ in range(blocks)])[:n]
    gaps, prompt, output = shuffled(gaps), shuffled(prompt), shuffled(output)
    arrival = np.floor(np.cumsum(gaps)).astype(np.int64)
    arrival -= arrival[0]
    tokens = [rng.integers(0, vocab, int(p)).astype(np.int32) for p in prompt]
    return dict(arrival=arrival, prompt_len=prompt, output_len=output,
                tokens=tokens)
