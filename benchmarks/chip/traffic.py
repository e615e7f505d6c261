"""The benchmark's traffic: packed language-model rows, made from a seed.

A mix is a data file under ``traffic/`` (``kind: packed_lm``) with the
batch, the row length and the generator's parameters. Tokens are
Zipf-distributed ids, documents of exponentially distributed length
(mean ``doc_len_mean``) are packed into each row, and each document
boundary is stamped with ``eod_token``, which the loss masks out. Every
step's rows depend only on (seed, step), so two runs of one seed see the
same data, and every seed gives the same sizes.

This is a copy of the program's ``SyntheticPackedLM`` kept with the
benchmark so that the yardstick cannot move with the program.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class PackedLM:
    """``batch_np(step)`` -> ``{"ids", "labels", "mask"}`` numpy arrays of
    shape [batch, seq_len], the interface ``ShardedLoader`` reads."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        if mix.get("kind") != "packed_lm":
            raise ValueError(f"unknown traffic kind {mix.get('kind')!r}")
        self.batch = int(mix["batch"])
        self.seq_len = int(mix["seq_len"])
        self.zipf_a = float(mix["zipf_a"])
        self.doc_len_mean = int(mix["doc_len_mean"])
        self.eod = int(mix["eod_token"])
        self.vocab = int(vocab_size)
        self.seed = int(seed)

    def batch_np(self, step: int) -> Dict[str, np.ndarray]:
        B, S = self.batch, self.seq_len
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        toks = rng.zipf(self.zipf_a, size=(B, S + 1)) % (self.vocab - 1) + 1
        n_docs = max(S // self.doc_len_mean, 1)
        for b in range(B):
            toks[b, rng.integers(1, S, size=n_docs)] = self.eod
        labels = toks[:, 1:].astype(np.int32)
        return {"ids": toks[:, :-1].astype(np.int32), "labels": labels,
                "mask": labels != self.eod}
