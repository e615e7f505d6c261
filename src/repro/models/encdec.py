"""Encoder-decoder backbone (seamless-m4t-medium).

Audio frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings [B, S_enc, D] delivered by input_specs().
The decoder is a standard causal stack with per-layer cross-attention.
"""
from __future__ import annotations

import copy
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SystemConfig
from repro.core.fcdp import gather_param
from repro.core.partition import ParamDef, label_tree
from repro.core.strategy import resolve_strategies
from repro.models import stack as stk
from repro.models.common import MeshInfo, pad_vocab
from repro.models.layers import chunked_tp_softmax_xent, embed_lookup, rms_norm

ENC_PLAN = [("attn", "mlp")]
DEC_PLAN = [("attn", "xattn", "mlp")]


class EncDec:
    def __init__(self, cfg: ModelConfig, sys: SystemConfig, mesh):
        assert cfg.num_encoder_layers > 0
        self.cfg, self.sys, self.mesh = cfg, sys, mesh
        self.mi = MeshInfo.from_mesh(mesh, act_psum=sys.act_psum,
                                     quant_impl=sys.quant_impl)
        self.n_enc = cfg.num_encoder_layers
        self.n_dec = cfg.num_layers
        self.plan_enc, self.plan_dec = ENC_PLAN, DEC_PLAN
        self.vpad = pad_vocab(cfg.vocab_size, self.mi.tp)
        # labels first, then per-leaf strategy resolution (see models/lm.py)
        self._defs, self.strategy = resolve_strategies(
            sys, label_tree(self._build_defs()),
            strict=not sys.peft)  # adapter-targeting rules match post-injection
        self._plans = self.strategy.plan_tree(
            self._defs, mesh, sys.min_shard_size,
            compress_bwd=(sys.grad_compress == "int8_pod"),
            param_compress=(sys.param_compress == "int8_pod"),
            quant_impl=sys.quant_impl,
            fused_matmul=sys.fused_matmul, fused_impl=sys.fused_impl)

    def _build_defs(self):
        cfg, tp = self.cfg, self.mi.tp
        return {
            "embed": ParamDef((self.vpad, cfg.d_model), ("tp", "fsdp"),
                              init="embed"),
            "enc_blocks": stk.stack_defs(
                stk.group_defs(cfg, self.plan_enc, tp), self.n_enc),
            "enc_norm": ParamDef((cfg.d_model,), ("fsdp",), init="ones"),
            "dec_blocks": stk.stack_defs(
                stk.group_defs(cfg, self.plan_dec, tp), self.n_dec),
            "final_norm": ParamDef((cfg.d_model,), ("fsdp",), init="ones"),
            "head": ParamDef((cfg.d_model, self.vpad), ("fsdp", "tp")),
        }

    defs = property(lambda self: self._defs)
    plans = property(lambda self: self._plans)

    def with_plans(self, plans):
        """Shallow view bound to a different GatherPlan tree (async
        grad-reduce stream, see core/schedule.py)."""
        m = copy.copy(self)
        m._plans = plans
        return m

    def _encode(self, params, enc_embeds):
        """enc_embeds: [B, S_enc, D] precomputed frame embeddings (stub)."""
        S = enc_embeds.shape[1]
        ctx = {"positions": jnp.arange(S)[None, :], "causal": False}
        x = enc_embeds.astype(jnp.dtype(self.sys.compute_dtype))
        x, _, _ = stk.apply_stack(self.cfg, self.sys, self.mi, self.plan_enc,
                                  params["enc_blocks"],
                                  self._plans["enc_blocks"], x, ctx,
                                  strategy=self.strategy)
        return rms_norm(x, gather_param(params["enc_norm"],
                                        self._plans["enc_norm"]),
                        self.cfg.norm_eps)

    def loss_fn(self, params, batch):
        """batch: enc_embeds [B,S_enc,D], ids/labels/mask [B,S_dec]."""
        cfg, sys, mi = self.cfg, self.sys, self.mi
        enc_out = self._encode(params, batch["enc_embeds"])
        ids, labels = batch["ids"], batch["labels"]
        S = ids.shape[1]
        with jax.named_scope("embed"):
            table = gather_param(params["embed"], self._plans["embed"])
            x = embed_lookup(table, ids, mi).astype(
                jnp.dtype(sys.compute_dtype))
        ctx = {"positions": jnp.arange(S)[None, :], "causal": True,
               "enc_out": enc_out}
        x, _, aux = stk.apply_stack(cfg, sys, mi, self.plan_dec,
                                    params["dec_blocks"],
                                    self._plans["dec_blocks"], x, ctx,
                                    strategy=self.strategy)
        with jax.named_scope("loss"):
            x = rms_norm(x, gather_param(params["final_norm"],
                                         self._plans["final_norm"]),
                         cfg.norm_eps)
            head = gather_param(params["head"], self._plans["head"])
            loss_sum, cnt = chunked_tp_softmax_xent(
                x, head, labels, mi, cfg.vocab_size, sys.loss_chunk,
                batch.get("mask"))
        return loss_sum, cnt, aux

    def init_decode_state(self, batch_local: int, max_len: int,
                          enc_len: int, seq_sharded: bool = False):
        return stk.init_group_state(self.cfg, self.plan_dec, self.mi,
                                    batch_local, max_len, self.n_dec,
                                    seq_sharded, enc_len=enc_len)

    def prefill_fn(self, params, enc_embeds, ids, state):
        """Encode source + run decoder prefix, filling decode state."""
        enc_out = self._encode(params, enc_embeds)
        S = ids.shape[1]
        table = gather_param(params["embed"], self._plans["embed"])
        x = embed_lookup(table, ids, self.mi).astype(
            jnp.dtype(self.sys.compute_dtype))
        ctx = {"positions": jnp.arange(S)[None, :], "causal": True,
               "enc_out": enc_out, "prefill": True}
        x, new_state, _ = stk.apply_stack(
            self.cfg, self.sys, self.mi, self.plan_dec, params["dec_blocks"],
            self._plans["dec_blocks"], x, ctx, state,
            strategy=self.strategy)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        head = gather_param(params["head"], self._plans["head"])
        logits = x[:, -1:] @ head
        return logits[:, 0], new_state

    def decode_fn(self, params, tok, state, seq_sharded: bool = False):
        table = gather_param(params["embed"], self._plans["embed"])
        x = embed_lookup(table, tok, self.mi).astype(
            jnp.dtype(self.sys.compute_dtype))
        ctx = {"decode": True, "seq_sharded": seq_sharded}
        x, new_state, _ = stk.apply_stack(
            self.cfg, self.sys, self.mi, self.plan_dec, params["dec_blocks"],
            self._plans["dec_blocks"], x, ctx, state,
            strategy=self.strategy)
        x = rms_norm(x, gather_param(params["final_norm"],
                                     self._plans["final_norm"]),
                     self.cfg.norm_eps)
        head = gather_param(params["head"], self._plans["head"])
        logits = x @ head
        return logits[:, 0], new_state
