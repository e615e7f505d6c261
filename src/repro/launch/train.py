"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 50 --mode fcdp
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      --peft --batch 1 --seq-len 4096 --steps 4      # one chip

--smoke runs the reduced config of the same family on the local CPU
devices. Without it the published config runs at full width on the
devices that are present: the mesh is --pod x --data x --model, whose
product must equal the device count (the 256/512-chip production meshes
are compiled by repro.launch.dryrun). --layers cuts the depth; widths
never change. Includes checkpoint/restart, heartbeat, straggler
monitoring, and optional failure injection (--fail-at). An empty
--ckpt-dir runs without checkpoints, so a failing step is fatal.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import functools
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import (OptimizerConfig, RunConfig, ShapeCell,
                                shape_cell)
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core.engine import StepBundle
from repro.data.pipeline import DataConfig, ShardedLoader, SyntheticPackedLM
from repro.launch.cli import (add_mesh_args, add_system_args,
                              init_compile_cache, system_config_from_args)
from repro.launch.mesh import make_device_mesh, make_smoke_mesh
from repro.optim.adamw import init_opt_state
from repro.runtime import lowerings
from repro.runtime.elastic import mesh_meta, reshard_state
from repro.runtime.fault_tolerance import (FailureInjector, HeartbeatMonitor,
                                           StragglerMonitor,
                                           run_with_restarts)


def build(args):
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        # --multi-pod with --smoke carves a 2-wide pod axis (>= 8 local
        # devices) so the DCN-facing streams run on the toy mesh too
        mesh = make_smoke_mesh(multi_pod=args.multi_pod)
        cell = ShapeCell("smoke_train", "train", args.seq_len or 128,
                         args.batch or 8)
    else:
        cfg = get_config(args.arch)
        mesh = make_device_mesh(args.pod, args.data, args.model)
        base = shape_cell(args.cell)
        cell = ShapeCell(base.name, "train", args.seq_len or base.seq_len,
                         args.batch or base.global_batch)
    if args.layers:
        if not 0 < args.layers <= cfg.num_layers:
            raise ValueError(f"--layers {args.layers}: {cfg.name} has "
                             f"{cfg.num_layers} layers")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    sysc = system_config_from_args(
        args, min_shard_size=8 if args.smoke else 2048)
    run = RunConfig(model=cfg, shape=cell, system=sysc,
                    optimizer=OptimizerConfig(
                        lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 1)),
                    microbatch=args.microbatch)
    return RunState(run, mesh, args)


# how many of the latest do_train_step calls ``step_lowerings`` keeps
LOWERINGS_KEPT = 1024


class RunState:
    """One training run's state and step. ``counters`` holds what the
    run counts of itself: ``setup_s`` (seconds per set-up phase),
    ``init_peak_bytes`` (the most device memory in use at any time up
    to the end of set-up, over the mesh's devices; None where the
    backend keeps no memory statistics), ``step_lowerings`` (for each
    of the latest ``LOWERINGS_KEPT`` ``do_train_step`` calls, the
    program lowerings it triggered: 0 on a warm step) and, once a step
    has been traced, ``attention_paths`` (the self-attention calls the
    latest trace of a step ran on the fused kernel and on the chunked
    path: ``{"kernel": n, "chunked": m}``)."""

    def __init__(self, run, mesh, args):
        self.run, self.mesh, self.args = run, mesh, args
        self.counters = {"setup_s": {}, "init_peak_bytes": None,
                         "step_lowerings": collections.deque(
                             maxlen=LOWERINGS_KEPT)}
        with self._setup_phase("bundle"):
            self.bundle = StepBundle(run, mesh)
            self.step_fn = self.bundle.make_train_step()
        # cross-step pipeline (stream 3): the steady-state step carries
        # the previous step's optimizer epilogue; prime fills the
        # pipeline, flush drains it (end of run / before checkpoints)
        self.cross_step = self.bundle.cross_step
        self.carry = None
        self.steps_taken = 0     # steps run since init/restore (lets the
        #                          pre-loop restore of a just-written
        #                          step-0 seed skip the read-back)
        if self.cross_step:
            self.prime_fn = self.bundle.make_train_prime()
            self.flush_fn = self.bundle.make_train_flush()
        with self._setup_phase("init_params"):
            params = self.bundle.init_all_params(seed=run.seed)
            self.train_p, self.frozen_p = self.bundle.split(params)
            jax.block_until_ready(params)
        with self._setup_phase("init_opt"):
            # placed like the step's own opt outputs: left to the
            # compiler, the zero moments come out replicated (a full
            # copy per chip) and the step compiles a second time for the
            # next step's layout
            self.opt = jax.jit(functools.partial(
                init_opt_state, sys=run.system),
                out_shardings=self.bundle.state_shardings()["opt"])(
                    self.train_p)
            jax.block_until_ready(self.opt)
        self.counters["init_peak_bytes"] = _peak_bytes(mesh.devices.flat)
        ds = SyntheticPackedLM(run.model, run.shape, DataConfig(run.seed))
        enc_dim = run.model.d_model if run.model.num_encoder_layers else 0
        self.loader = ShardedLoader(ds, mesh,
                                    self.bundle.batch_spec(run.shape),
                                    enc_embed_dim=enc_dim)
        self.metrics_log = []
        self.result = None       # run_with_restarts' summary, once run

    def do_train_step(self, batch):
        """One training step under whichever schedule is live. With the
        cross-step pipeline the first call primes the carry (no update);
        call flush_carry() to drain before reading/persisting state.
        Sets ``last_primed``: a primed step's grad_norm is not known yet
        (the piped step reports the PREVIOUS step's norm, the flush
        reports the last one) -- metric consumers must not read a prime
        row's 0.0 as a real norm."""
        self.last_primed = False
        self.steps_taken += 1
        n0, paths0 = lowerings.count(), lowerings.attention_paths()
        if not self.cross_step:
            self.train_p, self.opt, m = self.step_fn(
                self.train_p, self.frozen_p, self.opt, batch)
        elif self.carry is None:
            self.last_primed = True
            self.carry, m = self.prime_fn(
                self.train_p, self.frozen_p, self.opt, batch)
        else:
            self.train_p, self.opt, self.carry, m = self.step_fn(
                self.train_p, self.frozen_p, self.opt, self.carry, batch)
        self.counters["step_lowerings"].append(lowerings.count() - n0)
        paths = {p: n - paths0[p]
                 for p, n in lowerings.attention_paths().items()}
        if any(paths.values()):      # this call traced the step
            self.counters["attention_paths"] = paths
        return m

    @contextlib.contextmanager
    def _setup_phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.counters["setup_s"][name] = time.perf_counter() - t0

    def flush_carry(self):
        """Finalize the outstanding cross-step epilogue, if any, so
        params/opt reflect every step taken (the next step re-primes).
        The flushed grad_norm -- the last step's, otherwise lost -- is
        appended to metrics_log as a ``flush`` row."""
        if self.carry is not None:
            self.train_p, self.opt, m = self.flush_fn(
                self.train_p, self.opt, self.carry)
            self.carry = None
            self.metrics_log.append(
                {"flush": True, "grad_norm": float(m["grad_norm"])})

    def state_tree(self):
        """The persisted training state. The cross-step carry rides
        along exactly when it is live, so a checkpoint taken
        mid-pipeline round-trips bit-exactly (manifest v2 records the
        carry section; restore validates it against the mesh)."""
        tree = {"params": self.train_p, "opt": self.opt}
        if self.carry is not None:
            tree["carry"] = self.carry
        return tree

    def load_state(self, tree):
        self.train_p, self.opt = tree["params"], tree["opt"]
        # a restored carry resumes the pipeline mid-flight; without one
        # the next do_train_step re-primes
        self.carry = tree.get("carry")
        self.steps_taken = 0


def _peak_bytes(devices):
    """The largest ``peak_bytes_in_use`` over ``devices``; None where the
    backend keeps no memory statistics (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return max(int(m.get("peak_bytes_in_use", 0)) for m in stats)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--cell", default="train_4k")
    add_system_args(ap)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="with --smoke: carve a 2-wide pod axis")
    add_mesh_args(ap)
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: number of layers (0 = the config's)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory ('' = no checkpoints: a "
                         "fixed default would resume a stale run)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    init_compile_cache()
    st = build(args)
    cfg, cell = st.run.model, st.run.shape
    dev = jax.devices()[0]
    print(f"arch {cfg.name}: d_model {cfg.d_model} d_ff {cfg.d_ff} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} vocab {cfg.vocab_size} "
          f"layers {cfg.num_layers}"
          + ("" if args.smoke else
             f" (of {get_config(args.arch).num_layers})")
          + f" | batch {cell.global_batch} x seq {cell.seq_len} | mesh "
          f"{dict(st.mesh.shape)} | mode {st.run.system.mode}"
          f"{' peft' if st.run.system.peft else ''} | "
          f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    injector = FailureInjector(fail_at_steps=tuple(args.fail_at))
    monitor = StragglerMonitor()
    hb = HeartbeatMonitor(timeout_s=600).start()

    def do_step(step: int):
        t0 = time.perf_counter()
        injector.maybe_fail(step)
        batch = st.loader.get(step)
        m = st.do_train_step(batch)
        loss = float(m["loss"])          # waits for the step to finish
        row = {"step": step, "loss": loss,
               "grad_norm": float(m["grad_norm"]),
               "step_s": time.perf_counter() - t0}
        if st.last_primed:
            # pipeline-fill step: no norm yet (the next piped step
            # reports this step's, the flush reports the last one)
            row["primed"] = True
        st.metrics_log.append(row)
        if step % max(args.steps // 20, 1) == 0:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"time {row['step_s']:.3f}s")

    def save(step: int):
        if ckpt is None:
            return
        # the checkpoint is taken mid-pipeline: the cross-step carry is
        # persisted as a manifest-v2 carry section (not flushed), so a
        # restart resumes the piped schedule bit-identically to an
        # uninterrupted run; the mesh signature in meta lets an elastic
        # restore detect that a carry never survives a mesh change
        ckpt.save(step, st.state_tree(), blocking=False,
                  meta=mesh_meta(st.mesh))

    def restore() -> int:
        if ckpt is None:     # only the initial call: without
            return 0         # checkpoints no failure is retried
        # a crash can land while an async save is still writing: drain
        # it first, or latest_step() would miss the in-flight checkpoint
        # and silently resume a full interval earlier
        ckpt.wait()
        latest = ckpt.latest_step()
        if latest == 0 and st.steps_taken == 0 and st.carry is None:
            # the pre-loop restore of the step-0 seed we just wrote:
            # live state IS the checkpoint, skip the read-back
            return 0
        if latest is None:
            # nothing persisted yet: drain any in-flight epilogue so the
            # live state is post-update, and restart from the top
            st.flush_carry()
            return 0
        state, carry_invalidated = reshard_state(
            ckpt, latest, st.bundle,
            {"params": st.train_p, "opt": st.opt})
        st.load_state(state)
        if carry_invalidated:
            # the saved carry could not be restored (mesh change, or the
            # pipeline is off in this run): resume one step earlier --
            # re-running the last step re-primes the pipeline and
            # rebuilds the identical carry, so its update is re-derived
            # rather than silently lost
            resume = max(latest - 1, 0)
            print(f"restored checkpoint at step {latest}; cross-step "
                  f"carry invalidated -> re-running step {resume} to "
                  "re-prime")
            return resume
        print(f"restored checkpoint at step {latest}")
        return latest

    # persist the initial state before the first step: a failure inside
    # the first checkpoint interval then restores to a well-defined step
    # 0 instead of replaying onto partially-trained live state
    if ckpt is not None and ckpt.latest_step() is None:
        ckpt.save(0, st.state_tree(), blocking=True,
                  meta=mesh_meta(st.mesh))

    t0 = time.time()
    result = run_with_restarts(
        args.steps, do_step, save, restore,
        checkpoint_every=args.ckpt_every,
        max_restarts=3 if ckpt is not None else 0,
        monitor=monitor, heartbeat=hb, flush_fn=st.flush_carry)
    st.flush_carry()
    hb.stop()
    if ckpt is not None:
        ckpt.wait()
    st.result = result
    dt = time.time() - t0
    toks = args.steps * st.run.shape.global_batch * st.run.shape.seq_len
    final_loss = next(m["loss"] for m in reversed(st.metrics_log)
                      if "loss" in m)
    print(f"done: {result} | {dt:.1f}s | {toks/dt:.0f} tok/s | "
          f"final loss {final_loss:.4f}")
    return st


if __name__ == "__main__":
    main()
