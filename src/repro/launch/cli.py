"""One CLI/config surface for every launcher.

``add_system_args(parser)`` installs the SystemConfig-shaped flags and
``system_config_from_args(args, **overrides)`` builds the config, so
``launch/train.py``, ``launch/dryrun.py``, ``launch/serve.py`` and the
benchmark harness (``benchmarks/harness``) all expose the SAME knobs
with the same spellings and defaults. Before this module each launcher
carried its own argparse block and the flags had drifted (train grew
``--prefetch`` while dryrun spelled it ``--no-prefetch``; dryrun never
learned ``--quant-impl``/``--fused-impl`` at all).

Migration note (one release): the boolean prefetch surface is GONE from
the CLIs -- ``--prefetch``/``--no-prefetch`` are replaced by the single
``--prefetch-depth N`` knob (0 = sequential schedule, k = depth-k
streaming ring). The ``SystemConfig(prefetch=...)`` constructor bool
still works but emits a DeprecationWarning and will be removed next
release; pass ``prefetch_depth`` instead.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

from repro.configs.base import ACTIVATION_POLICIES, SystemConfig
from repro.core.strategy import (DEFAULT_STRATEGY, parse_mode_override,
                                 strategy_names)

# flags whose argparse dest maps 1:1 onto a SystemConfig field
_PASSTHROUGH = ("mode", "peft", "lora_rank", "lora_alpha",
                "activation_policy", "loss_chunk",
                "grad_compress", "param_compress", "quant_impl",
                "fused_matmul", "fused_impl", "async_grad_reduce",
                "cross_step_pipeline", "device_cache_fraction")


def add_system_args(parser: argparse.ArgumentParser, *,
                    default_prefetch_depth: int | None = None,
                    ) -> argparse._ArgumentGroup:
    """Install the shared SystemConfig flags on ``parser``.

    default_prefetch_depth: what ``--prefetch-depth`` means when the
    flag is absent (train/serve: None -> SystemConfig's own default of
    0; dryrun keeps its historical overlap-on default of 1).
    """
    g = parser.add_argument_group(
        "system", "distributed-system knobs (shared across launchers)")
    g.add_argument("--mode", default=DEFAULT_STRATEGY,
                   choices=list(strategy_names()),
                   help="sharding strategy for every param not claimed "
                        "by a --mode-override rule")
    g.add_argument("--mode-override", action="append", default=[],
                   metavar="GLOB=MODE",
                   help="per-tensor strategy override rule matched "
                        "against dotted param paths, first match wins; "
                        "repeatable (e.g. --mode-override "
                        "'blocks.*.moe.we_*=mics')")
    g.add_argument("--prefetch-depth", type=int,
                   default=default_prefetch_depth,
                   help="ring depth of the streaming gather scheduler "
                        "(0 = sequential paper-faithful schedule; "
                        f"default {default_prefetch_depth or 0}). "
                        "Replaces the removed --prefetch/--no-prefetch "
                        "booleans.")
    g.add_argument("--async-grad-reduce", action="store_true",
                   help="overlap microbatch i's pod-axis grad reduce "
                        "with microbatch i+1's forward (needs "
                        "microbatch > 1)")
    g.add_argument("--cross-step-pipeline", action="store_true",
                   help="carry step i's optimizer epilogue (last pod "
                        "reduce + update + widened gather) across the "
                        "step boundary and overlap it with step i+1's "
                        "first forward (needs --async-grad-reduce and "
                        "microbatch >= 2; bit-identical results)")
    g.add_argument("--device-cache-fraction", type=float, default=0.0,
                   help="FCDP-Cache tau: fraction of layers allowed to "
                        "keep the cached stage-1 shard on device")
    g.add_argument("--peft", action="store_true",
                   help="FCDP-Comm: freeze the trunk, train LoRA "
                        "adapters, communicate only trainables over DCN")
    g.add_argument("--lora-rank", type=int, default=8,
                   help="LoRA adapter rank r (with --peft)")
    g.add_argument("--lora-alpha", type=float, default=None,
                   help="LoRA alpha; the adapter term is scaled by "
                        "alpha/rank (default: 2*rank, i.e. scale 2.0)")
    g.add_argument("--lora-targets", default=None,
                   metavar="NAME[,NAME...]",
                   help="comma-separated projection names to inject "
                        "adapters into (default: wq,wk,wv,wo)")
    g.add_argument("--activation-policy", default="save_all",
                   choices=ACTIVATION_POLICIES)
    g.add_argument("--loss-chunk", type=int, default=0,
                   help="chunked cross-entropy (0 = unchunked)")
    g.add_argument("--grad-compress", default="none",
                   choices=("none", "int8_pod"),
                   help="qgZ: int8 block-quantized pod-axis gradient "
                        "reduce-scatter")
    g.add_argument("--param-compress", default="none",
                   choices=("none", "int8_pod"),
                   help="qwZ: int8-transported stage-1 weight all-gather")
    g.add_argument("--quant-impl", default="jnp",
                   choices=("jnp", "pallas", "pallas_interpret"),
                   help="codepath for the int8 quantize/dequantize steps")
    g.add_argument("--fused-matmul", default="none",
                   choices=("none", "ag_matmul", "both"),
                   help="gather-fused collective matmul: consume stage-2 "
                        "shards as the ppermute ring delivers them "
                        "(ag_matmul = fused fwd, bit-parity bwd; both = "
                        "bwd ring-fused too)")
    g.add_argument("--fused-impl", default="jnp",
                   choices=("jnp", "pallas", "pallas_interpret"),
                   help="codepath for the per-chunk matmul inside the "
                        "fused ring")
    return g


def add_mesh_args(parser: argparse.ArgumentParser) -> None:
    """Mesh sizes of a run on the devices that are present (without
    --smoke): pod x data x model must equal the device count."""
    for axis in ("pod", "data", "model"):
        parser.add_argument(f"--{axis}", type=int, default=1,
                            help=f"mesh {axis} size (without --smoke)")


def init_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at one fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    is set here. Otherwise the cache lives in ``.jax_cache`` at the root
    of the checkout: the path is part of what a later run looks up, so
    it never names a temp dir, a pid or a time. Called from each
    launcher's ``main()``, never at import."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    root = Path(__file__).resolve().parents[3]
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def system_config_from_args(args: argparse.Namespace,
                            **overrides) -> SystemConfig:
    """Build the SystemConfig from a parser that went through
    add_system_args. ``overrides`` are launcher-supplied fields outside
    the shared surface (min_shard_size, serve_frozen, ...) and win over
    the parsed flags."""
    kw = {f: getattr(args, f) for f in _PASSTHROUGH}
    kw["mode_overrides"] = tuple(parse_mode_override(s)
                                 for s in args.mode_override)
    kw["prefetch_depth"] = args.prefetch_depth
    if getattr(args, "lora_targets", None):
        kw["lora_targets"] = tuple(
            t.strip() for t in args.lora_targets.split(",") if t.strip())
    kw.update(overrides)
    return SystemConfig(**kw)
