"""The one traffic generator: everything a cell sends is made here from a
traffic file's parameters and ``--seed``. The program receives arrays and
``Request``s only.

A new traffic mix is a new data file under ``<path>/traffic/``; this file
does not change for it. Token ids are Zipf-distributed. Request lengths are
log-normal (``median``, ``sigma``), clipped to ``[min, max]``, and
stratified: the n lengths sit at the quantiles (i + 1/2)/n of the
distribution, so every seed sends the same multiset of lengths (the same
total work), and the seed draws their order and the tokens.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def zipf_tokens(rng: np.random.Generator, shape, vocab: int,
                exponent: float) -> np.ndarray:
    """Token ids in ``[0, vocab)`` with p(k) proportional to (k+1)^-a: a
    unigram skew like text's, so a trained loss can fall below ln(vocab)."""
    weights = 1.0 / np.power(np.arange(1, vocab + 1, dtype=np.float64),
                             exponent)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(shape), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)


def train_batches(params: dict, vocab: int, seed: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """``(x, y)`` of ``distinct_batches * global_batch`` rows: inputs and
    next-token labels of seeded sequences."""
    rng = np.random.default_rng([seed, 1])
    rows = int(params["distinct_batches"]) * int(params["global_batch"])
    tok = zipf_tokens(rng, (rows, int(params["seq_len"]) + 1), vocab,
                      float(params["zipf_exponent"]))
    return tok[:, :-1], tok[:, 1:]


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` stratified lengths of the log-normal ``spec``, clipped to
    its ``[min, max]``, in an order drawn from ``rng``."""
    us = (np.arange(n) + 0.5) / n
    rng.shuffle(us)
    norm = NormalDist()
    raw = np.array([float(spec["median"]) * math.exp(
        float(spec["sigma"]) * norm.inv_cdf(float(u))) for u in us])
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def num_requests(params: dict, seconds: float) -> int:
    """The fixed work of a closed-batch run: ceil(rate * seconds)."""
    return max(1, math.ceil(float(params["requests_per_second"]) * seconds))


def serve_requests(params: dict, vocab: int, seed: int, n: int
                   ) -> List[Tuple[np.ndarray, int]]:
    """``n`` ``(prompt, max_new_tokens)`` pairs; prompt + output never
    exceeds ``max_total_len`` (the output is cut to fit)."""
    rng = np.random.default_rng([seed, 2])
    plen = lengths(params["prompt_len"], n, rng)
    olen = lengths(params["output_len"], n, rng)
    cap = int(params["max_total_len"])
    olen = np.maximum(1, np.minimum(olen, cap - plen))
    flat = zipf_tokens(rng, (int(plen.sum()),), vocab,
                       float(params["zipf_exponent"]))
    out, at = [], 0
    for p, o in zip(plen, olen):
        out.append((flat[at:at + p].copy(), int(o)))
        at += int(p)
    return out
