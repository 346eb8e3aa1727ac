"""The one traffic generator: reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and yields requests from the seed.

A mix is data only.  Its keys:

    fill     the first ``fill`` requests are the clients' first requests:
             due when set-up starts, they fill the slots before the window
             opens.
    arrival  {"process": name, ...}: request ``fill + k`` is due
             ``due_s(k, arrival)`` seconds after the window opens, where
             ``due_s`` is found in ``chipbench/arrivals/<name>.py``.
    vocab    token ids are drawn from [0, vocab).
    prefixes {"count", "len_min", "len_max", "zipf"}: shared documents or
             system prompts.  Document k has the length
             len_min + (len_max - len_min) * frac(0.5 + k * golden), so the
             set of lengths is uniform; a request picks a document with
             probability ~ 1 / (k + 1)**zipf.
    suffix   {"median", "sigma", "min", "max"}: the unique part of each
             prompt, lognormal, clipped.
    output   {"median", "sigma", "min", "max"}: tokens to generate.
    block    stratification block.  Inside each block of that many
             requests, suffix and output lengths are the block's quantiles
             of their distributions and documents come in counts
             proportional to their popularity, in an order fixed by the
             block's index.

Sizes, documents, their order and the arrivals are the same for every
seed: a mix is a fixed replay.  The seed draws every token id, so each
seed's prompts are new content to the page cache.  The program receives
only the generated prompts and output lengths.
"""

from __future__ import annotations

import importlib
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

__all__ = ["Request", "Traffic", "load_mix"]

_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0
_SEED_MASK = (1 << 64) - 1


@dataclass(frozen=True)
class Request:
    due_s: float
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int
    prefix: int                  # which shared document it starts with


def load_mix(path) -> Dict:
    return json.loads(Path(path).read_text())


def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((k + 0.5) / n) for k in range(n)])
    vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def _largest_remainder(p: np.ndarray, n: int) -> np.ndarray:
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:n - int(counts.sum())]] += 1
    return counts


class Traffic:
    """Deterministic request stream of one mix under one seed."""

    def __init__(self, mix: Dict, seed: int):
        self.mix = mix
        self.seed = int(seed) & _SEED_MASK
        self.vocab = int(mix["vocab"])
        self.block = int(mix["block"])
        self.fill = int(mix["fill"])
        self._arrival = importlib.import_module(
            f"chipbench.arrivals.{mix['arrival']['process']}")
        pre = mix["prefixes"]
        n = int(pre["count"])
        u = (0.5 + np.arange(n) * _GOLDEN) % 1.0
        self.prefix_lens = np.rint(
            pre["len_min"] + (pre["len_max"] - pre["len_min"]) * u
        ).astype(np.int64)
        pop = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** pre["zipf"]
        self.popularity = pop / pop.sum()
        self._suffix_q = _lognormal_quantiles(mix["suffix"], self.block)
        self._output_q = _lognormal_quantiles(mix["output"], self.block)
        self._doc_seq = np.repeat(np.arange(n),
                                  _largest_remainder(self.popularity,
                                                     self.block))
        self._prefix_tokens: Dict[int, np.ndarray] = {}
        self._blocks: Dict[int, tuple] = {}

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def prefix_tokens(self, k: int) -> np.ndarray:
        toks = self._prefix_tokens.get(k)
        if toks is None:
            toks = self._rng(1, k).integers(
                0, self.vocab, size=int(self.prefix_lens[k]), dtype=np.int32)
            self._prefix_tokens[k] = toks
        return toks

    def _block(self, j: int) -> tuple:
        """Documents, suffix and output lengths of block ``j``, in an order
        fixed by ``j`` alone."""
        b = self._blocks.get(j)
        if b is None:
            fixed = np.random.default_rng([j])
            b = tuple(fixed.permutation(x) for x in
                      (self._doc_seq, self._suffix_q, self._output_q))
            self._blocks[j] = b
        return b

    def due_s(self, i: int) -> float:
        """Seconds after the window opens that request ``i`` is due.  The
        fill (``i < fill``) reads 0: it is due when set-up starts."""
        if i < self.fill:
            return 0.0
        return float(self._arrival.due_s(i - self.fill, self.mix["arrival"]))

    def request(self, i: int) -> Request:
        docs, suffixes, outputs = self._block(i // self.block)
        r = i % self.block
        k = int(docs[r])
        suffix = self._rng(3, i).integers(0, self.vocab,
                                          size=int(suffixes[r]),
                                          dtype=np.int32)
        prompt = np.concatenate([self.prefix_tokens(k), suffix])
        return Request(self.due_s(i), prompt, int(outputs[r]), k)

    def requests(self, n: int) -> List[Request]:
        return [self.request(i) for i in range(n)]
