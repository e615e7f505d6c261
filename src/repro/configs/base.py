"""Configuration dataclasses for the repro framework.

Everything an (arch x shape x system) cell needs is described here;
model code, partitioner, and launchers consume these frozen configs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # layers that are MoE: every `moe_period` starting at `moe_offset`
    moe_period: int = 1
    moe_offset: int = 0
    aux_loss_weight: float = 0.01


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64   # rank of the data-dependent decay LoRA
    tokenshift: bool = True


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // num_heads
    act: str = "swiglu"         # swiglu | geglu | gelu
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_seq_len: int = 1 << 19
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # hybrid (jamba): within each period, which positions are attention
    hybrid_period: int = 0           # 0 -> not hybrid
    hybrid_attn_positions: Tuple[int, ...] = ()
    # encdec
    num_encoder_layers: int = 0      # >0 -> encoder-decoder
    # vlm / audio frontends are stubs: inputs arrive pre-embedded
    frontend: str = "none"           # none | vq_image | audio_frames
    # which sublayer mixes tokens, decided per family in models/registry
    sub_quadratic: bool = False      # True -> supports long_500k

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def param_count(self) -> int:
        """Approximate total parameter count (used for roofline MODEL_FLOPS)."""
        from repro.models.registry import count_params
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.registry import count_params
        return count_params(self, active_only=True)


@dataclass(frozen=True)
class ShapeCell:
    """One assigned input-shape cell."""
    name: str               # train_4k | prefill_32k | decode_32k | long_500k
    kind: str               # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPE_CELLS: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", "train", 4096, 256),
    ShapeCell("prefill_32k", "prefill", 32768, 32),
    ShapeCell("decode_32k", "decode", 32768, 128),
    ShapeCell("long_500k", "decode", 524288, 1),
)


def shape_cell(name: str) -> ShapeCell:
    for c in SHAPE_CELLS:
        if c.name == name:
            return c
    raise KeyError(f"unknown shape cell {name!r}; have {[c.name for c in SHAPE_CELLS]}")


# remat/activation policies understood by core/fcdp.py:make_remat_policy
ACTIVATION_POLICIES = ("save_all", "block_io", "offload_acts",
                       "save_collectives")


@dataclass(frozen=True)
class SystemConfig:
    """Which distributed-training system and caching policy to use.

    mode:
      zero3   - full sharding, re-gather fwd+bwd               (paper baseline)
      zeropp  - device-cached intra shard, intra-only bwd AG   (ZeRO++ analog)
      fcdp    - host-cached intra shard, intra-only bwd AG     (the paper)
      mics    - subgroup (pod-local) sharding, no cross-pod AG (MiCS analog)
      hier    - pod-local param sharding, optimizer state sharded over
                ('data','pod') (hierarchical partitioning, Xu et al.)

    Validated at construction: device_cache_fraction must lie in [0, 1],
    activation_policy must be a known policy, prefetch_depth must be
    a non-negative int (None derives it from the legacy `prefetch`
    bool), and every mode_overrides rule must be well-formed and name a
    registered strategy. `mode` itself is validated at strategy
    resolution.
    """
    mode: str = "fcdp"
    # Per-tensor strategy overrides: ordered (path-glob, mode) rules
    # matched (fnmatch, first match wins) against the label_tree dotted
    # path of each ParamDef at StepBundle/model construction -- e.g.
    # (("blocks.*.moe.we_*", "mics"), ("embed", "hier")) keeps the dense
    # trunk on `mode` while experts ride MiCS pod-replication and the
    # embedding shards hierarchically. An explicit ParamDef.strategy tag
    # beats every rule; a rule that is the first match for zero params
    # raises at resolution. 'pattern=mode' strings are accepted and
    # canonicalized to pairs (the CLI --mode-override form).
    mode_overrides: Tuple[Tuple[str, str], ...] = ()
    # FCDP-Cache: fraction of layers allowed to keep the cached shard on
    # device (planner output; tau in the paper). 0.0 -> all host, 1.0 -> all device.
    device_cache_fraction: float = 0.0
    # Streaming gather scheduler (core/schedule.py): depth of the ring
    # buffer of in-flight stage-1 (inter/DCN) gather caches. Step i
    # issues layer i+k's stage-1 all-gather -- no data dependency on
    # layer i's compute, so XLA's latency-hiding scheduler overlaps the
    # DCN transfer -- while computing layer i from the oldest ring slot.
    # 0 = sequential schedule (the paper-faithful baseline the mode
    # comparisons are defined on); k trades k in-flight stage-1 buffers
    # (carried across the layer scan, so the backward reads them back
    # instead of re-gathering) for up to k layers' worth of DCN overlap.
    # Strategy-gated: a no-op for MiCS/hier / frozen / single-pod paths
    # where stage 1 is structurally empty. None -> derived from the
    # legacy `prefetch` bool (True -> 1).
    prefetch_depth: Optional[int] = None
    # DEPRECATED legacy alias (DeprecationWarning on use, removed next
    # release -- pass prefetch_depth): an init-only bool (True -> depth
    # 1, False -> depth 0). Because it is an InitVar,
    # dataclasses.replace() never carries it over, so a non-None value
    # here was ALWAYS passed explicitly in this construction and wins
    # over a (possibly replace-carried) prefetch_depth. Old readers
    # keep working through the read-only `prefetch` property
    # (== prefetch_depth > 0) installed below.
    prefetch: dataclasses.InitVar[Optional[bool]] = None
    # second scheduler stream (engine/train.py): on the gradient-
    # accumulation path, hold microbatch i's stage-1-level gradients for
    # one iteration and run their pod-axis reduce-scatter concurrently
    # with microbatch i+1's forward instead of serializing it inside the
    # backward. Trades one in-flight stage-1-sized gradient buffer for
    # DCN overlap; total reduce volume is unchanged. Strategy-gated
    # (needs a non-empty stage 1; MiCS/hier decline).
    async_grad_reduce: bool = False
    # third scheduler stream (engine/train.py): pipeline the once-per-step
    # optimizer epilogue -- the LAST microbatch's pod-axis reduce-scatter,
    # the optimizer apply, and the widened updated-shard all-gather --
    # across the step boundary: step i returns a carry of (accumulated
    # storage-level grads, the last microbatch's stage-1-level pending
    # grads) and step i+1 finalizes it at its top, where the epilogue
    # collectives have no data dependency on step i+1's first microbatch
    # forward prologue and overlap with it. Staleness-free: step i+1's
    # forward consumes the UPDATED parameters (the swap happens before the
    # first layer that reads them); only the collectives' latency is
    # hidden, per-step DCN volume is byte-identical. Requires
    # async_grad_reduce (the deferred pod reduce is the stream-2
    # primitive, validated here) and gradient accumulation
    # (RunConfig.microbatch >= 2, validated at RunConfig construction);
    # strategy-gated via supports_cross_step (MiCS/hier decline on their
    # own -- no stage-1 reduce to carry -- but their widened epilogue
    # collectives ride the carry when mixed with a streaming group).
    cross_step_pipeline: bool = False
    host_offload: bool = True          # False -> Saveable instead of Offloadable
    # FCDP-Comm / PEFT
    peft: bool = False
    lora_rank: int = 8
    lora_targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo")
    # LoRA alpha: the adapter term is scaled by alpha/rank. None ->
    # alpha = 2*rank (scale 2.0). Single source of truth -- both the
    # analytic peft accounting and models/attention.py read the scale
    # through core.peft.lora_scale(sys).
    lora_alpha: Optional[float] = None
    # activation checkpointing: save_all (paper-faithful torch default),
    # block_io (remat layer internals), offload_acts
    activation_policy: str = "save_all"
    # beyond-paper: int8 block-quantized gradient stage over the pod axis
    grad_compress: str = "none"        # none | int8_pod
    # beyond-paper (ZeRO++ qwZ): int8 block-quantized stage-1 (pod-axis)
    # parameter all-gather -- blocks + fp32 scales on the wire,
    # dequantized on arrival so the FCDP host cache stays bf16 and the
    # backward reuse is free and full-precision
    param_compress: str = "none"       # none | int8_pod
    # implementation of the quantize/dequantize hot loops shared by
    # grad_compress / param_compress / act_psum
    quant_impl: str = "jnp"            # jnp | pallas | pallas_interpret
    # gather-fused collective matmul (kernels/collective_matmul.py):
    # consume stage-2 (intra-pod) weight chunks as the ring delivers
    # them instead of all-gathering before the first matmul.
    #   none      -- unfused (gather_stage2 then matmul)
    #   ag_matmul -- fused forward; backward replays the exact unfused
    #                op sequence, so losses/grads stay bit-identical
    #   both      -- backward ring-fused too (matmul->reduce-scatter
    #                dual; re-associates the dx sum, exact vs the
    #                kernels/ref.py oracle rather than the unfused path)
    # Eligibility is per-leaf and plan-level: see GatherPlan.fused in
    # core/strategy.py.
    fused_matmul: str = "none"         # none | ag_matmul | both
    # per-chunk matmul codepath for the fused ring
    fused_impl: str = "jnp"            # jnp | pallas | pallas_interpret
    # chunked cross-entropy (beyond-paper memory optimization)
    loss_chunk: int = 0                # 0 -> unchunked
    # param/compute dtypes
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    master_dtype: str = "float32"
    opt_state_dtype: str = "float32"
    # replicate tensors smaller than this many elements instead of ZeRO-sharding
    min_shard_size: int = 2048
    # sequence parallelism over the model axis (beyond-paper optimization)
    sequence_parallel: bool = False
    remat_scan: bool = True            # scan over layer groups
    # serving: store all weights in the FCDP-Comm frozen layout
    # (pod-replicated, intra-sharded host cache) -> zero DCN traffic/token
    serve_frozen: bool = True
    # attention implementation: 'pallas' runs causal self-attention
    # without a KV cache on the fused flash-attention kernel where it
    # applies (a TPU mesh, rows and head_dim that tile it), else the
    # chunked jnp path; 'jnp' forces the chunked path (the oracle);
    # 'pallas_interpret' the kernel in the TPU interpreter, any backend
    # (tests: the interpreter cannot run under the layer remat)
    attn_impl: str = "pallas"
    # MoE dispatch token chunk (bounds the [E,C,D] buffer)
    moe_token_chunk: int = 8192
    # beyond-paper: keep expert weights resident (ZeRO over pod only) --
    # per-step gather volume >> resident size for MoE tensors
    moe_weight_resident: bool = False
    # beyond-paper: int8 transport for the large TP activation
    # all-reduces (the dominant ICI term on dense train cells)
    act_psum: str = "bf16"            # bf16 | int8
    # beyond-paper: decode-time gather-free MoE -- compute against the
    # sharded expert weights (tokens all-gathered over the shard axes,
    # partial-contraction psum) instead of gathering GBs of expert
    # weights per layer for a handful of tokens
    moe_serve_sharded: bool = False

    def __post_init__(self, prefetch):
        if self.mode_overrides:
            # canonicalize + validate (unknown strategy name / malformed
            # rule raises naming the offending rule); zero-match
            # patterns raise later, at per-leaf resolution, where the
            # ParamDef tree exists. Deferred import: the strategy
            # registry pulls in jax, which plain config construction
            # should not require.
            from repro.core.strategy import normalize_mode_overrides
            object.__setattr__(self, "mode_overrides",
                               normalize_mode_overrides(self.mode_overrides))
        if not 0.0 <= self.device_cache_fraction <= 1.0:
            raise ValueError(
                "device_cache_fraction must be in [0, 1], got "
                f"{self.device_cache_fraction!r}")
        if self.activation_policy not in ACTIVATION_POLICIES:
            raise ValueError(
                f"unknown activation_policy {self.activation_policy!r}; "
                f"known: {sorted(ACTIVATION_POLICIES)}")
        depth = self.prefetch_depth
        if prefetch is not None:
            # one-release migration path: the boolean knob is deprecated
            # in favor of the single prefetch_depth int (the launchers
            # already dropped --prefetch/--no-prefetch for
            # --prefetch-depth); next release the InitVar goes away.
            import warnings
            warnings.warn(
                "SystemConfig(prefetch=...) is deprecated; pass "
                "prefetch_depth instead (True -> 1, False -> 0). The "
                "boolean shim will be removed in the next release.",
                DeprecationWarning, stacklevel=3)
        if depth is None:                    # legacy bool shim
            depth = 1 if prefetch else 0
        elif prefetch is not None:
            # an explicit legacy bool wins over a carried depth:
            # replace(prefetch=False) must actually disable the schedule
            depth = (depth or 1) if prefetch else 0
        if not isinstance(depth, int) or isinstance(depth, bool) \
                or depth < 0:
            raise ValueError(
                f"prefetch_depth must be a non-negative int, got {depth!r}")
        object.__setattr__(self, "prefetch_depth", depth)
        for knob in ("grad_compress", "param_compress"):
            if getattr(self, knob) not in ("none", "int8_pod"):
                raise ValueError(
                    f"unknown {knob} {getattr(self, knob)!r}; "
                    "known: none, int8_pod")
        if self.quant_impl not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown quant_impl {self.quant_impl!r}; "
                "known: jnp, pallas, pallas_interpret")
        if self.fused_matmul not in ("none", "ag_matmul", "both"):
            raise ValueError(
                f"unknown fused_matmul {self.fused_matmul!r}; "
                "known: none, ag_matmul, both")
        if self.fused_impl not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown fused_impl {self.fused_impl!r}; "
                "known: jnp, pallas, pallas_interpret")
        if self.cross_step_pipeline and not self.async_grad_reduce:
            raise ValueError(
                "cross_step_pipeline=True requires async_grad_reduce=True: "
                "the carried epilogue is the stream-2 deferred pod reduce "
                "plus the optimizer apply; without the async stream there "
                "is no stage-1-level pending gradient to carry")

    def replace(self, **kw) -> "SystemConfig":
        # dataclasses.replace re-derives unspecified InitVars via
        # getattr, which would read the `prefetch` property and smuggle
        # the OLD on/off state back in (overriding e.g. an explicit
        # prefetch_depth=0). Pin it to None unless the caller passes it.
        kw.setdefault("prefetch", None)
        return dataclasses.replace(self, **kw)


# legacy read-only view of the scheduler knob (the InitVar above holds
# this class-attribute slot until we overwrite it post-decoration)
SystemConfig.prefetch = property(lambda self: self.prefetch_depth > 0)


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: str = "cosine"     # cosine | linear | constant


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeCell
    system: SystemConfig = field(default_factory=SystemConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    seed: int = 0
    microbatch: int = 0          # 0 -> no gradient accumulation

    def __post_init__(self):
        if self.system.cross_step_pipeline and self.microbatch < 2:
            raise ValueError(
                "cross_step_pipeline=True requires gradient accumulation "
                f"(microbatch >= 2), got microbatch={self.microbatch!r}: "
                "the carried epilogue is defined per accumulation step")

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)
