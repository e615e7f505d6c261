"""Serving launcher: continuous batching over the paged KV cache with
the FCDP-Comm frozen parameter layout (pod-replicated, intra-sharded --
zero DCN bytes per token).

A mixed-length synthetic workload streams through the request scheduler
(``core/serve_schedule.py``): sequences are admitted the moment a batch
slot and their full KV page reservation free up, long prompts prefill in
chunks between decode steps, and finished sequences retire immediately.
``--policy static`` runs the same jitted steps with wait-for-full-batch
admission for comparison.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 16 --seq-len 128 --gen-len 16
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs.base import RunConfig, ShapeCell
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core.engine import StepBundle
from repro.core.engine.serve import default_paged_kv
from repro.core.kv_cache import PagedKVConfig
from repro.core.serve_schedule import PagedServeEngine, Request, summarize
from repro.launch.cli import (add_mesh_args, add_system_args,
                              init_compile_cache, system_config_from_args)
from repro.launch.mesh import make_device_mesh, make_smoke_mesh


def mixed_requests(n: int, seq_len: int, gen_len: int, vocab: int,
                   seed: int = 0):
    """Mixed-length synthetic workload: prompt lengths spread over
    [gen_len, seq_len - gen_len] so short and long requests interleave."""
    rng = np.random.default_rng(seed)
    lo = min(gen_len, seq_len - gen_len)
    plens = rng.integers(max(lo, 1), seq_len - gen_len, endpoint=True,
                         size=n)
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab, (int(p),)).astype(np.int32),
                    max_new_tokens=gen_len)
            for i, p in enumerate(plens)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    add_system_args(ap)
    ap.add_argument("--smoke", action="store_true")
    add_mesh_args(ap)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128,
                    help="max prompt+generation length per request")
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--policy", choices=["continuous", "static"],
                    default="continuous")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk size (tokens per scheduler tick)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size (0 = default_paged_kv sizing)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    init_compile_cache()
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        mesh = make_smoke_mesh()
    else:
        cfg = get_config(args.arch)
        mesh = make_device_mesh(args.pod, args.data, args.model)
    cell = ShapeCell("serve", "decode", args.seq_len, args.batch)
    run = RunConfig(model=cfg, shape=cell,
                    system=system_config_from_args(args, min_shard_size=8))
    bundle = StepBundle(run, mesh)
    params = bundle.init_all_params(seed=0)

    if args.page_size:
        mpps = -(-args.seq_len // args.page_size)
        from repro.core.engine.serve import paged_replicas
        slots = args.batch // paged_replicas(bundle, cell)
        kv = PagedKVConfig(page_size=args.page_size,
                           pages_per_replica=1 + slots * mpps,
                           max_pages_per_seq=mpps)
    else:
        kv = default_paged_kv(bundle, cell)
    engine = PagedServeEngine(bundle, kv, chunk=args.chunk,
                              policy=args.policy)
    requests = mixed_requests(args.requests, args.seq_len, args.gen_len,
                              cfg.vocab_size, seed=args.seed)

    t0 = time.perf_counter()
    results, wall = engine.serve(params, requests)
    summary = summarize(results, wall)
    summary["policy"] = args.policy
    summary["kv"] = {"page_size": kv.page_size,
                     "pages_per_replica": kv.pages_per_replica,
                     "max_pages_per_seq": kv.max_pages_per_seq}
    print(json.dumps(summary, indent=2))
    done = sorted(results, key=lambda r: r.rid)[0]
    print(f"request 0 (prompt {done.prompt_len}): "
          f"continuation ids[:8] = {done.tokens[:8]}")
    print(f"total (incl. compile): {time.perf_counter() - t0:.2f}s; "
          f"scheduler steps: {engine.steps}")
    return results


if __name__ == "__main__":
    main()
