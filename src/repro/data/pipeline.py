"""Deterministic synthetic LM data pipeline.

Real-pipeline shape: seeded per (shard, step) so any host can regenerate
any step's data independently (fault-tolerant restart resumes mid-epoch
without coordination), sharded placement onto the mesh, packed sequences
with document boundaries and a loss mask, and a prefetch iterator.
"""
from __future__ import annotations

import threading
import queue
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell


@dataclass
class DataConfig:
    seed: int = 0
    doc_len_mean: int = 512       # packed documents, exponential lengths
    zipf_a: float = 1.2           # token distribution (heavy-tailed)
    eod_token: int = 0


class SyntheticPackedLM:
    """Zipf-token documents packed into fixed-length rows.

    Deterministic: batch(step) depends only on (seed, step), never on
    iteration history -- restarts resume exactly.
    """

    def __init__(self, cfg: ModelConfig, cell: ShapeCell, data: DataConfig):
        self.cfg, self.cell, self.data = cfg, cell, data

    def batch_np(self, step: int) -> Dict[str, np.ndarray]:
        B, S = self.cell.global_batch, self.cell.seq_len
        rng = np.random.default_rng(
            np.random.SeedSequence([self.data.seed, step]))
        v = self.cfg.vocab_size
        toks = rng.zipf(self.data.zipf_a, size=(B, S + 1)) % (v - 1) + 1
        # stamp document boundaries
        n_docs = max(int(S / self.data.doc_len_mean), 1)
        for b in range(B):
            cuts = rng.integers(1, S, size=n_docs)
            toks[b, cuts] = self.data.eod_token
        ids = toks[:, :-1].astype(np.int32)
        labels = toks[:, 1:].astype(np.int32)
        mask = labels != self.data.eod_token
        return {"ids": ids, "labels": labels, "mask": mask}


class ShardedLoader:
    """Places host batches onto the mesh with the step fn's batch specs,
    prefetching ahead on a background thread."""

    def __init__(self, dataset: SyntheticPackedLM, mesh,
                 specs: Dict[str, P], prefetch: int = 2,
                 enc_embed_dim: int = 0):
        self.ds = dataset
        self.mesh = mesh
        self.specs = specs
        self.enc_embed_dim = enc_embed_dim
        self._q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._step = 0
        self._thread: Optional[threading.Thread] = None

    def _place(self, batch_np: Dict[str, np.ndarray]):
        out = {}
        for k, v in batch_np.items():
            spec = self.specs.get(k, P())
            if k == "mask" and "mask" not in self.specs:
                spec = self.specs.get("labels", P())
            out[k] = jax.device_put(v, NamedSharding(self.mesh, spec))
        return out

    def get(self, step: int):
        """The batch of ``step``, placed on the mesh. Host spans on the
        profiler's clock: ``data.get`` around ``data.make`` (the host
        batch) and ``data.place`` (its transfer)."""
        with jax.profiler.TraceAnnotation("data.get"):
            with jax.profiler.TraceAnnotation("data.make"):
                b = self.ds.batch_np(step)
                if self.enc_embed_dim:
                    rng = np.random.default_rng(
                        np.random.SeedSequence([17, self.ds.data.seed, step]))
                    B = self.ds.cell.global_batch
                    S = max(self.ds.cell.seq_len // 4, 8)
                    b["enc_embeds"] = rng.standard_normal(
                        (B, S, self.enc_embed_dim)).astype(np.float32)
                    b["enc_embeds"] = b["enc_embeds"].astype(jnp.bfloat16)
            with jax.profiler.TraceAnnotation("data.place"):
                return self._place(b)

    def __iter__(self) -> Iterator:
        step = 0
        while True:
            yield self.get(step)
            step += 1
