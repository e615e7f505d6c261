"""The dense decoder family: GQA attention and a SwiGLU MLP in every
layer, as Qwen2 and Granite publish them (``model_type`` qwen2, granite).

The model, from the configuration's published keys: embedding lookup;
per layer an RMSNorm, GQA attention with RoPE (rotating the two halves of
each head), optional q/k/v bias, softmax scaled by 1/sqrt(head_dim),
causal over the whole row, LoRA terms (x @ A) @ B * alpha / rank on the
targeted projections, a residual add; then an RMSNorm, a SwiGLU MLP
(silu(h @ W_gate) * (h @ W_in)) @ W_out and a residual add; a final
RMSNorm, the LM head (the embedding's transpose when tied) and the mean
cross-entropy over the tokens the mask keeps. The layers are one stack,
``blocks.pos0``, as the program stores them.
"""
from __future__ import annotations

import math
from typing import List, Optional

import jax

from benchmarks.chip.reference import (Group, LeafSpec, Model, attention,
                                       rms_norm, rope)

PREFIX = "blocks.pos0"


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def model_config(name: str, cfg: dict):
    """The program's ModelConfig for a configuration file. Keys the
    program cannot run (Granite's multipliers, MLP biases) must hold
    their neutral values; the file states what was run."""
    from repro.configs.base import ModelConfig
    neutral = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
               "logits_scaling": 1.0, "mlp_bias": False,
               "attention_multiplier": 1.0 / math.sqrt(head_dim(cfg))}
    for key, want in neutral.items():
        if key in cfg and not math.isclose(cfg[key], want, rel_tol=1e-9):
            raise ValueError(f"{name}: {key}={cfg[key]} cannot be run by the "
                             f"program's dense model (only {want})")
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{name}: hidden_act {cfg['hidden_act']!r}")
    return ModelConfig(
        name=name, family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=head_dim(cfg), act="swiglu",
        qkv_bias=bool(cfg.get("attention_bias", False)),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


# ---------------------------------------------------------------------------
# The reference model
# ---------------------------------------------------------------------------

def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim(cfg), cfg["intermediate_size"], cfg["vocab_size"])


def param_specs(cfg: dict, peft: Optional[dict]) -> List[LeafSpec]:
    """Every parameter leaf in the order its key is drawn."""
    L, D, H, KV, hd, F, V = _dims(cfg)
    full = not peft
    attn = {"wq": ((L, D, H * hd), "normal"), "wk": ((L, D, KV * hd), "normal"),
            "wv": ((L, D, KV * hd), "normal"), "wo": ((L, H * hd, D), "normal"),
            "norm": ((L, D), "ones")}
    if cfg.get("attention_bias"):
        attn.update({"bq": ((L, H * hd), "zeros"), "bk": ((L, KV * hd), "zeros"),
                     "bv": ((L, KV * hd), "zeros")})
    specs = [LeafSpec(f"{PREFIX}.attn.{k}", s, i, full)
             for k, (s, i) in attn.items()]
    for t in (peft or {}).get("targets", ()):
        (_, d_in, d_out), _ = attn[t]
        r = peft["rank"]
        specs += [LeafSpec(f"{PREFIX}.attn.{t}_lora_a", (L, d_in, r),
                           "normal", True),
                  LeafSpec(f"{PREFIX}.attn.{t}_lora_b", (L, r, d_out),
                           "zeros", True)]
    mlp = {"norm": ((L, D), "ones"), "w_gate": ((L, D, F), "normal"),
           "w_in": ((L, D, F), "normal"), "w_out": ((L, F, D), "normal")}
    specs += [LeafSpec(f"{PREFIX}.mlp.{k}", s, i, full)
              for k, (s, i) in mlp.items()]
    specs += [LeafSpec("embed", (V, D), "embed", full),
              LeafSpec("final_norm", (D,), "ones", full)]
    if not cfg["tie_word_embeddings"]:
        specs.append(LeafSpec("head", (D, V), "normal", full))
    return sorted(specs, key=lambda s: s.path.split("."))


def layer(x, p, cfg, lora_scale, ein):
    """One decoder layer. p: this layer's weights by short name."""
    _, D, H, KV, hd, _, _ = _dims(cfg)
    eps, B, S = cfg["rms_norm_eps"], x.shape[0], x.shape[1]

    def proj(h, name):
        y = ein("bsd,de->bse", h, p[name])
        if f"{name}_lora_a" in p:
            y = y + ein("bsr,re->bse", ein("bsd,dr->bsr", h, p[f"{name}_lora_a"]),
                        p[f"{name}_lora_b"]) * lora_scale
        return y

    h = rms_norm(x, p["attn.norm"], eps)
    q, k, v = (proj(h, f"attn.{n}") for n in ("wq", "wk", "wv"))
    if "attn.bq" in p:
        q, k, v = q + p["attn.bq"], k + p["attn.bk"], v + p["attn.bv"]
    q = rope(q.reshape(B, S, H, hd), cfg["rope_theta"])
    k = rope(k.reshape(B, S, KV, hd), cfg["rope_theta"])
    o = attention(q, k, v.reshape(B, S, KV, hd), ein).reshape(B, S, H * hd)
    x = x + proj(o, "attn.wo")
    h = rms_norm(x, p["mlp.norm"], eps)
    z = jax.nn.silu(ein("bsd,df->bsf", h, p["mlp.w_gate"])) * ein(
        "bsd,df->bsf", h, p["mlp.w_in"])
    return x + ein("bsf,fd->bsd", z, p["mlp.w_out"])


def reference_model(cfg: dict, peft: Optional[dict]) -> Model:
    """One group: every layer of the stack, each ``layer``."""
    lora_scale = peft["alpha"] / peft["rank"] if peft else 0.0
    return Model(
        groups=[Group(PREFIX, cfg["num_hidden_layers"],
                      lambda x, p, ein: layer(x, p, cfg, lora_scale, ein))],
        embed="embed", final_norm="final_norm",
        head="embed" if cfg["tie_word_embeddings"] else "head",
        norm_eps=cfg["rms_norm_eps"])


# ---------------------------------------------------------------------------
# FLOPs per token, the rule of ``mfu``
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of one transformer layer (GQA attention + GLU
    MLP), from the configuration's published keys."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"]


def adapter_params(cfg: dict, peft: Optional[dict]) -> int:
    """LoRA parameters of one layer: rank x (in + out) per target."""
    if not peft:
        return 0
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    dims = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    return sum(peft["rank"] * sum(dims[t]) for t in peft["targets"])


def model_flops_per_token(cfg: dict, seq_len: int,
                          peft: Optional[dict]) -> float:
    """Training FLOPs per token (forward + backward, no recompute).

    - Forward: 2 x every matmul parameter (attention and MLP projections,
      LoRA adapters, and the LM head, tied or not; the embedding lookup
      is no matmul), plus causal attention, 4 x (S/2) x heads x head_dim
      per layer.
    - Backward of a full fine-tune: 2 x forward. Of LoRA: 1 x forward for
      the activation gradients, plus 2 x the adapter parameters for the
      adapters' own weight gradients.
    - Recomputation is never counted.
    """
    n_layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    adapters = n_layers * adapter_params(cfg, peft)
    matmul = (n_layers * layer_matmul_params(cfg) + adapters
              + cfg["hidden_size"] * cfg["vocab_size"])
    attn = n_layers * 4 * (seq_len / 2) * heads * head_dim(cfg)
    fwd = 2 * matmul + attn
    if peft:
        return 2 * fwd + 2 * adapters
    return 3 * fwd
