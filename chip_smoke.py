#!/usr/bin/env python3
"""Chip smoke test: the trainer's main path on a TPU, in one process.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chip   # one 2x2 host: fcdp vs zero3

One chip: each main-path Pallas kernel (flash attention, the int8
quantize / dequantize / dequant-accumulate trio, the collective-matmul
chunk) runs once at the model's widths against its ``kernels/ref.py``
oracle, and must show ``tpu_custom_call`` in its compiled program. Then
``repro.launch.train.main`` takes a few steps of a LoRA fine-tune of
qwen2.5-3b at full width and depth (``--peft --mode fcdp``) from seeded
random weights.

--four-chip runs only the path that exists across chips: a full
fine-tune of qwen2.5-3b at full width, cut to 16 layers, on a
pod=2 x data=2 x model=1 mesh, once under ``--mode fcdp`` and once under
``--mode zero3`` with the same seed and batch. Their per-step losses
must agree, fcdp's compiled step must hold host-memory-space (``S(5)``)
buffers and zero3's none.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises, and no TPU means a non-zero exit before anything runs.
Timings and memory printed here are a smoke run's, not benchmark
metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"          # checkpoints of this script's runs

ARCH = "qwen2.5-3b"
# The LoRA run at full depth. block_io remat and a chunked loss keep the
# step inside 16 GB beside the 6.8 GB bf16 trunk: save_all would keep
# ~8.5 GB of matmul outputs, unchunked f32 logits another 2.5 GB.
ONE_CHIP = ["--arch", ARCH, "--peft", "--mode", "fcdp", "--batch", "1",
            "--seq-len", "4096", "--steps", "4",
            "--activation-policy", "block_io", "--loss-chunk", "1024",
            "--ckpt-every", "2"]
# Full fine-tune at 16 B per parameter: 16 layers plus the untied
# embedding and head is ~7.5 GB of state per chip (AOT for a described
# v5e: 12.85 GiB for fcdp, 12.32 GiB for zero3, with activations). The
# learning rate keeps three steps of a freshly initialised full
# fine-tune smooth: at 3e-4 the loss ran 12.63 -> 9.84 -> 15.6, and a
# trajectory that unstable turns last-bit differences into 1% apart.
FOUR_CHIP = ["--arch", ARCH, "--pod", "2", "--data", "2", "--model", "1",
             "--layers", "16", "--batch", "4", "--seq-len", "4096",
             "--steps", "3", "--lr", "1e-5", "--activation-policy",
             "block_io", "--loss-chunk", "1024", "--ckpt-dir", ""]
# Step 0 of a randomly initialised model predicts close to uniform:
# cross-entropy is ln(vocab) plus half the logit variance (12.22 at this
# width and depth on the CPU backend, vs ln(151936) = 11.93). A loss off
# by more than 1 nat means the labels, mask or vocab are wrong.
STEP0_NATS = 1.0
# fcdp and zero3 gather bit-identical weights and differ only in where
# the backward reads the stage-1 shard from (host copy vs regather). The
# two compiled programs may still fuse and order f32 reductions
# differently and round bf16 activations apart; Adam then turns sign
# flips of near-zero gradients into lr-sized updates. 1e-3 relative sits
# above that and below the per-step change a wrong gradient causes. The
# step-0 gradient norm, taken on identical weights, is held to the same
# bound: it checks the backward through the host cache directly.
LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); this script has no CPU fallback")
    if len(devs) != count:
        sys.exit(f"chip_smoke: need {count} TPU chip(s), found {len(devs)}")
    return devs


def compiled_kernel(name, fn, *args, **static):
    """Compile ``fn`` for the chip, prove the Pallas kernel lowered to
    Mosaic (not interpret mode), run it once."""
    compiled = fn.lower(*args, **static).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                             "program (kernel did not lower to Mosaic)")
    return compiled(*args)


def check_close(name, got, want, rtol, atol, why):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=f"{name} vs oracle")
    log(f"kernel {name}: {tuple(got.shape)} max|err| {err:.3g} "
        f"(rtol {rtol:g}, atol {atol:g}: {why}) tpu_custom_call ok")


def kernels():
    """Each main-path kernel once at qwen2.5-3b widths vs its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import collective_matmul as cm
    from repro.kernels import ops, ref
    from repro.kernels.quant import BLOCK

    key = jax.random.key(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    # flash attention: 16 heads x head_dim 128 x 4096 tokens
    shape = (1, 4096, 16, 128)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
               for kk in jax.random.split(k1, 3))
    out = compiled_kernel("flash_attention", ops.flash_attention, q, k, v,
                          causal=True, impl="pallas")
    want = jax.jit(ref.attention_ref)(q, k, v)
    check_close("flash_attention", out, want, 2e-2, 2e-2,
                "bf16 output, online vs full softmax")

    # int8 trio over the blocks of one 2048x2048 leaf
    x = jax.random.normal(k2, (2048 * 2048 // BLOCK, BLOCK), jnp.float32)
    qv, sv = compiled_kernel("int8_quantize_blocks",
                             ops.int8_quantize_blocks, x, impl="pallas")
    q_ref, s_ref = jax.jit(ref.int8_quantize_blocks_ref)(x)
    check_close("int8_quantize_blocks.scale", sv, s_ref, 1e-6, 0.0,
                "one f32 multiply")
    dq = int(np.max(np.abs(np.asarray(qv, np.int32)
                           - np.asarray(q_ref, np.int32))))
    if dq > 1:
        raise AssertionError(f"int8_quantize_blocks: codes differ by {dq}")
    log(f"kernel int8_quantize_blocks.q: max|code err| {dq} (<= 1: the "
        "f32 divide may round a .5 tie apart) tpu_custom_call ok")
    deq = compiled_kernel("int8_dequantize_blocks",
                          ops.int8_dequantize_blocks, qv, sv, impl="pallas")
    check_close("int8_dequantize_blocks", deq,
                jax.jit(ref.int8_dequantize_blocks_ref)(qv, sv),
                1e-6, 0.0, "one f32 multiply")
    n = 2                                   # sources of a pod=2 reduce
    qn = jnp.stack([qv, jnp.flip(qv, 0)])
    sn = jnp.stack([sv, jnp.flip(sv, 0)])
    acc = compiled_kernel("int8_dequant_accumulate",
                          ops.int8_dequant_accumulate, qn, sn, impl="pallas")
    want = jax.jit(ref.int8_dequant_acc_ref)(qn, sn)
    check_close("int8_dequant_accumulate", acc, want, 1e-6,
                1e-6 * n * float(jnp.max(jnp.abs(want))),
                "same f32 add order; a fused multiply-add may differ by an ulp")

    # the collective-matmul chunk at both contractions of the model
    mm = jax.jit(cm.matmul_chunk,
                 static_argnames=("block_m", "block_n", "interpret"))
    for kdim, kk in ((2048, k3), (11008, k4)):
        ka, kb = jax.random.split(kk)
        xa = jax.random.normal(ka, (4096, kdim), jnp.bfloat16)
        wb = (jax.random.normal(kb, (kdim, 1024), jnp.float32)
              / math.sqrt(kdim)).astype(jnp.bfloat16)
        out = compiled_kernel(f"matmul_chunk[K={kdim}]", mm, xa, wb)
        want = jax.jit(ref.matmul_chunk_ref)(xa, wb)
        check_close(f"matmul_chunk[K={kdim}]", out, want, 1e-2, 1e-2,
                    "f32 accumulation in another order, one bf16 rounding")


def losses_of(st):
    return [row["loss"] for row in st.metrics_log if "loss" in row]


def check_run(st, label: str):
    import numpy as np
    if st.result["restarts"]:
        raise AssertionError(f"{label}: {st.result['restarts']} restart(s)")
    losses = losses_of(st)
    if not losses or not all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite losses {losses}")
    times = [round(row["step_s"], 3) for row in st.metrics_log
             if "step_s" in row]
    log(f"{label}: losses {losses} | step times (s, step 0 compiles) "
        f"{times}")
    return losses


def peaks(label: str):
    import jax
    for d in jax.devices():
        s = d.memory_stats() or {}
        log(f"{label}: {d} peak_bytes_in_use {s.get('peak_bytes_in_use')} "
            f"bytes_in_use {s.get('bytes_in_use')} bytes_limit "
            f"{s.get('bytes_limit')}")


def one_chip():
    import jax

    from repro.configs.registry import get_config
    from repro.launch import train

    kernels()
    ckpt = WORK / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    st = train.main(ONE_CHIP + ["--ckpt-dir", str(ckpt)])
    cfg = st.run.model
    full = get_config(ARCH)
    if (cfg.d_model, cfg.vocab_size, cfg.num_layers) != (
            full.d_model, full.vocab_size, full.num_layers):
        raise AssertionError(f"not the full config: {cfg}")
    losses = check_run(st, "qwen2.5-3b LoRA 36 layers")
    ln_v = math.log(cfg.vocab_size)
    if abs(losses[0] - ln_v) > STEP0_NATS:
        raise AssertionError(f"step-0 loss {losses[0]:.4f} is not within "
                             f"{STEP0_NATS} of ln(vocab) = {ln_v:.4f}")
    log(f"step-0 loss {losses[0]:.4f} vs ln(vocab) {ln_v:.4f}; "
        f"last {losses[-1]:.4f}")
    peaks("one chip")
    return jax.devices()


def compiled_step(st):
    """The run's own train step, compiled for its inputs."""
    batch = st.loader.get(0)
    return st.step_fn.lower(st.train_p, st.frozen_p, st.opt,
                            batch).compile()


def check_placement(st, label: str):
    """Every state leaf spans all four chips, and no chip holds much more
    of the state than another (code that has only seen CPU devices could
    place everything on device 0)."""
    import jax
    devs = set(jax.devices())
    per_dev = {d: 0 for d in devs}
    leaves = jax.tree.leaves((st.train_p, st.frozen_p, st.opt))
    for x in leaves:
        if x.sharding.device_set != devs:
            raise AssertionError(f"{label}: a {x.shape} leaf lives on "
                                 f"{x.sharding.device_set}")
        for s in x.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    lo, hi = min(per_dev.values()), max(per_dev.values())
    if hi > 1.05 * lo:
        raise AssertionError(f"{label}: state bytes per chip unbalanced "
                             f"{sorted(per_dev.values())}")
    log(f"{label}: {len(leaves)} state leaves span all 4 chips; "
        f"state bytes per chip {lo}..{hi}")


def four_chip():
    import jax

    from repro.launch import train

    log("mesh pod=2 x data=2 x model=1 on one 2x2 host: the pod axis runs "
        "over ICI here, not DCN")
    runs = {}
    for mode in ("fcdp", "zero3"):
        st = train.main(FOUR_CHIP + ["--mode", mode])
        label = f"qwen2.5-3b full fine-tune 16 layers {mode}"
        losses = check_run(st, label)
        check_placement(st, label)
        compiled = compiled_step(st)
        n_host = len(re.findall(r"S\(5\)", compiled.as_text()))
        log(f"{label}: compiled step holds {n_host} S(5) host buffers; "
            f"{compiled.memory_analysis()}")
        peaks(f"after {mode} (peaks are per process, cumulative)")
        runs[mode] = (losses, st.metrics_log[0]["grad_norm"], n_host)
        # free this run's state before the next one is built
        for x in jax.tree.leaves((st.train_p, st.frozen_p, st.opt)):
            x.delete()
        del st, compiled
        gc.collect()
    (l_f, g_f, h_f), (l_z, g_z, h_z) = runs["fcdp"], runs["zero3"]
    if h_f == 0:
        raise AssertionError("fcdp step has no S(5) buffers: the stage-1 "
                             "host offload was dropped")
    if h_z:
        raise AssertionError(f"zero3 step holds {h_z} S(5) buffers")
    worst = max(abs(a - b) / abs(b) for a, b in zip(l_f, l_z))
    if len(l_f) != len(l_z) or worst > LOSS_RTOL:
        raise AssertionError(f"fcdp {l_f} vs zero3 {l_z}: relative "
                             f"difference {worst:.3g} > {LOSS_RTOL}")
    g_diff = abs(g_f - g_z) / abs(g_z)
    if g_diff > LOSS_RTOL:
        raise AssertionError(f"step-0 grad norm fcdp {g_f} vs zero3 {g_z}")
    log(f"fcdp vs zero3 losses agree: max relative difference {worst:.3g}; "
        f"step-0 grad norm {g_f:.6g} vs {g_z:.6g} ({g_diff:.3g}); "
        f"bound {LOSS_RTOL}")
    return jax.devices()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the fcdp-vs-zero3 phase on a 2x2 host")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chip else 1)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cli import init_compile_cache
    init_compile_cache()
    log(f"device {devs[0].device_kind} x{len(devs)}")
    devs = four_chip() if args.four_chip else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
