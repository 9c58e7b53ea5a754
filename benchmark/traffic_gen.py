"""The one general traffic generator: a traffic mix is a data file of
parameters under ``benchmark/traffic/``, and everything a cell sends is
made here from that file and ``--seed``.  The program receives only the
generated inputs.

Every seed sends the same set of sizes in another order: lengths are laid
on an even grid of quantiles of the stated distribution (no draw, so no
seed has a heavier mix than another), in blocks of ``block`` requests, and
the seed only permutes each block and draws the token ids.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", name + ".json")
    with open(path) as f:
        return json.load(f)


def quantile_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 1/2) / n of ``spec``:
    {"dist": "uniform" | "log_uniform" | "fixed", "min", "max"}."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "fixed":
        vals = np.full(n, lo, float)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo)
    elif spec["dist"] == "log_uniform":
        vals = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def request_sizes(traffic: dict, seed: int, count: int) -> list:
    """``count`` (prompt_len, output_len) pairs.  One block of ``block``
    pairs is fixed for all seeds (prompt and output grids, the outputs in a
    fixed scrambled order so long prompts do not always get long answers);
    the seed permutes each successive block."""
    block = int(traffic["block"])
    prompts = quantile_grid(traffic["prompt_len"], block)
    outputs = quantile_grid(traffic["output_len"], block)
    outputs = outputs[np.random.default_rng(0).permutation(block)]
    rng = np.random.default_rng([int(seed), 1])
    pairs = []
    while len(pairs) < count:
        order = rng.permutation(block)
        pairs.extend((int(prompts[i]), int(outputs[i])) for i in order)
    return pairs[:count]


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index``: uniform over the vocabulary, no two
    requests sharing a prefix."""
    rng = np.random.default_rng([int(seed), 2, int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int64).astype(np.int32)


def train_ids(seed: int, step: int, sequences: int, seq_len: int,
              vocab: int) -> np.ndarray:
    """Token ids [sequences, seq_len + 1] of training step ``step``
    (inputs are [:, :-1], next-token labels [:, 1:]): uniform over the
    vocabulary, a fresh batch every step, every row different."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    return rng.integers(0, vocab, size=(sequences, seq_len + 1),
                        dtype=np.int64).astype(np.int32)
