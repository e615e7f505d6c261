"""Decoder-only language model covering the dense / moe / ssm / hybrid /
vlm families. Chameleon-style VLM is a decoder over a unified token space
(VQ image tokens arrive pre-embedded through the frontend stub)."""
from __future__ import annotations

import copy
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SystemConfig
from repro.core.fcdp import gather_param
from repro.core.partition import ParamDef, label_tree
from repro.core.strategy import resolve_strategies
from repro.models import stack as stk
from repro.models.common import MeshInfo, pad_vocab, psum_tp
from repro.models.layers import (chunked_tp_softmax_xent, embed_lookup,
                                 rms_norm, tp_softmax_xent)


def layer_plan(cfg: ModelConfig) -> Tuple[List[Tuple[str, ...]], int]:
    """Returns (plan, n_groups). plan[i] = sublayer kinds at position i."""
    if cfg.family in ("dense", "vlm"):
        return [("attn", "mlp")], cfg.num_layers
    if cfg.family == "moe":
        return [("attn", "moe")], cfg.num_layers
    if cfg.family == "ssm":
        return [("rwkv_tm", "rwkv_cm")], cfg.num_layers
    if cfg.family == "hybrid":
        period = cfg.hybrid_period
        assert cfg.num_layers % period == 0
        plan = []
        m = cfg.moe
        for i in range(period):
            mixer = "attn" if i in cfg.hybrid_attn_positions else "mamba"
            ffn = "moe" if (m and i % m.moe_period == m.moe_offset) else "mlp"
            plan.append((mixer, ffn))
        return plan, cfg.num_layers // period
    raise ValueError(f"layer_plan: unsupported family {cfg.family}")


class LM:
    """Bundles defs + step-fn bodies for one decoder-only architecture."""

    def __init__(self, cfg: ModelConfig, sys: SystemConfig, mesh):
        self.cfg, self.sys, self.mesh = cfg, sys, mesh
        self.mi = MeshInfo.from_mesh(mesh, act_psum=sys.act_psum,
                                     quant_impl=sys.quant_impl)
        self.plan, self.n_groups = layer_plan(cfg)
        self.vpad = pad_vocab(cfg.vocab_size, self.mi.tp)
        # labels first (override rules match dotted paths), then the
        # per-leaf strategy resolution (ParamDef tag > mode_overrides >
        # mode); uniform configs get the plain singleton strategy back
        self._defs, self.strategy = resolve_strategies(
            sys, label_tree(self._build_defs()),
            strict=not sys.peft)  # adapter-targeting rules match post-injection
        self._plans = self.strategy.plan_tree(
            self._defs, mesh, sys.min_shard_size,
            compress_bwd=(sys.grad_compress == "int8_pod"),
            param_compress=(sys.param_compress == "int8_pod"),
            quant_impl=sys.quant_impl,
            fused_matmul=sys.fused_matmul, fused_impl=sys.fused_impl)

    # -- parameters ---------------------------------------------------------
    def _build_defs(self):
        cfg, tp = self.cfg, self.mi.tp
        defs: Dict[str, Any] = {
            "embed": ParamDef((self.vpad, cfg.d_model), ("tp", "fsdp"),
                              init="embed"),
            "final_norm": ParamDef((cfg.d_model,), ("fsdp",), init="ones"),
            "blocks": stk.stack_defs(
                stk.group_defs(cfg, self.plan, tp, self.sys), self.n_groups),
        }
        if not cfg.tie_embeddings:
            defs["head"] = ParamDef((cfg.d_model, self.vpad), ("fsdp", "tp"))
        return defs

    @property
    def defs(self):
        return self._defs

    @property
    def plans(self):
        return self._plans

    def with_plans(self, plans):
        """Shallow view of this model bound to a different GatherPlan
        tree (the async grad-reduce stream feeds stage-1-resident
        params, see core/schedule.py:stage1_resident_plans)."""
        m = copy.copy(self)
        m._plans = plans
        return m

    # -- shared forward pieces ----------------------------------------------
    def _embed(self, params, ids):
        cfg = self.cfg
        with jax.named_scope("embed"):
            table = gather_param(params["embed"], self._plans["embed"])
            scale = (math.sqrt(cfg.d_model) if cfg.name.startswith("gemma")
                     else 1.0)
            x = embed_lookup(table, ids, self.mi, scale=scale)
            return x.astype(jnp.dtype(self.sys.compute_dtype))

    def _head_weights(self, params):
        if self.cfg.tie_embeddings:
            table = gather_param(params["embed"], self._plans["embed"])
            return table.T                     # [D, V_local]
        return gather_param(params["head"], self._plans["head"])

    def _segments(self):
        """(start, length, placement) segments implementing FCDP-Cache's
        device-fraction split over the layer stack."""
        n_dev = self.strategy.device_cache_groups(
            self.n_groups, self.sys.device_cache_fraction)
        segs = []
        if n_dev > 0:
            segs.append((0, n_dev, "device"))
        if n_dev < self.n_groups:
            segs.append((n_dev, self.n_groups - n_dev, None))
        return segs

    def _run_blocks(self, params, x, ctx, state=None):
        aux = jnp.float32(0)
        new_state_parts = []
        for (start, length, placement) in self._segments():
            p_slice = jax.tree.map(lambda a: a[start:start + length],
                                   params["blocks"])
            s_slice = (jax.tree.map(lambda a: a[start:start + length], state)
                       if state is not None else None)
            x, s_new, a = stk.apply_stack(
                self.cfg, self.sys, self.mi, self.plan, p_slice,
                self._plans["blocks"], x, ctx, s_slice, placement,
                strategy=self.strategy)
            aux = aux + a
            if s_new is not None:
                new_state_parts.append(s_new)
        if new_state_parts:
            new_state = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *new_state_parts)
        else:
            new_state = None
        return x, new_state, aux

    # -- training loss -------------------------------------------------------
    def loss_fn(self, params, batch):
        """Runs inside shard_map. batch: ids/labels/mask [B_local, S].
        Returns (loss_sum, token_count, aux_sum) -- caller psums."""
        cfg, sys, mi = self.cfg, self.sys, self.mi
        ids, labels = batch["ids"], batch["labels"]
        mask = batch.get("mask")
        S = ids.shape[1]
        x = self._embed(params, ids)
        ctx = {"positions": jnp.arange(S)[None, :], "causal": True}
        x, _, aux = self._run_blocks(params, x, ctx)
        with jax.named_scope("loss"):
            x = rms_norm(x, gather_param(params["final_norm"],
                                         self._plans["final_norm"]),
                         cfg.norm_eps)
            head = self._head_weights(params)
            loss_sum, cnt = chunked_tp_softmax_xent(
                x, head, labels, mi, cfg.vocab_size, sys.loss_chunk, mask)
        return loss_sum, cnt, aux

    # -- serving -------------------------------------------------------------
    def init_decode_state(self, batch_local: int, max_len: int,
                          seq_sharded: bool = False):
        return stk.init_group_state(self.cfg, self.plan, self.mi, batch_local,
                                    max_len, self.n_groups, seq_sharded)

    def prefill_fn(self, params, ids, state):
        """Full-sequence forward that also fills decode state.
        Returns (last-token logits [B, V_local], new_state)."""
        S = ids.shape[1]
        x = self._embed(params, ids)
        ctx = {"positions": jnp.arange(S)[None, :], "causal": True,
               "prefill": True}
        x, new_state, _ = self._run_blocks(params, x, ctx, state)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        logits = x[:, -1:] @ self._head_weights(params)
        return logits[:, 0], new_state

    def decode_fn(self, params, tok, state, seq_sharded: bool = False):
        """One decode step. tok: [B_local, 1] token ids.
        Returns (logits [B_local, V_local], new_state)."""
        x = self._embed(params, tok)
        ctx = {"decode": True, "seq_sharded": seq_sharded}
        x, new_state, _ = self._run_blocks(params, x, ctx, state)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        logits = x @ self._head_weights(params)
        return logits[:, 0], new_state

    # -- paged serving (continuous batching) ---------------------------------
    def init_paged_state(self, n_pages: int, page_size: int):
        """Paged KV pools, stacked like the contiguous decode state."""
        return stk.init_paged_group_state(self.cfg, self.plan, self.mi,
                                          n_pages, page_size, self.n_groups)

    def paged_decode_fn(self, params, tok, state, table, lengths):
        """One decode step over the paged cache. tok: [B_local, 1];
        table: [B_local, max_pages] local page ids; lengths: [B_local]
        current written length per row (the incoming token's absolute
        position). Returns (logits [B_local, V_local], new_state)."""
        x = self._embed(params, tok)
        ctx = {"paged": True, "decode": True,
               "positions": lengths[:, None], "page_table": table}
        x, new_state, _ = self._run_blocks(params, x, ctx, state)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        logits = x @ self._head_weights(params)
        return logits[:, 0], new_state

    def paged_prefill_fn(self, params, ids, state, table, pos0, last_idx):
        """One prefill CHUNK over the paged cache. ids: [B_local, C]
        (rows not prefilling this call carry padding and a scratch
        table row); pos0: [B_local] absolute position of each row's
        chunk start; last_idx: [B_local] position within the chunk of
        the row's last prompt token (logits are taken there -- only
        meaningful for rows finishing their prompt this chunk).
        Returns (logits [B_local, V_local], new_state)."""
        S = ids.shape[1]
        x = self._embed(params, ids)
        positions = pos0[:, None] + jnp.arange(S, dtype=pos0.dtype)[None, :]
        ctx = {"paged": True, "prefill_chunk": True,
               "positions": positions, "page_table": table}
        x, new_state, _ = self._run_blocks(params, x, ctx, state)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
        logits = x_last @ self._head_weights(params)
        return logits[:, 0], new_state
