"""Train-step builder: shard_map orchestration, gradient flow
(reduce-scatter via gather transposes), and optimizer application on
ZeRO shards. Consumes a StepBundle whose strategy already fixed the
storage layout and gather schedule.

Everything here is PER LEAF, so per-tensor mixed sharding
(CompositeStrategy) needs no special casing: the opt-widening
reduce-scatter/all-gather pair fires for exactly the leaves whose opt
spec is wider than their storage spec (hier embeddings, ZeRO-2-for-
experts), and the async reduce stream defers exactly the leaves with a non-empty stage 1
(the streaming groups) -- single-stage groups' reduces pass through
untouched.

Three gradient/optimizer schedules exist on the accumulation path:

  sequential (default): each microbatch's backward contains the full
  gather transposes, so the pod-axis reduce-scatter serializes after
  every backward, and the optimizer epilogue serializes at the end of
  the step.

  async (SystemConfig.async_grad_reduce, strategy-gated): the scheduler's
  second stream. Each microbatch is differentiated with respect to the
  STAGE-1-GATHERED parameter view (core/schedule.py:
  stage1_resident_plans), so its backward stops at stage-1-level
  gradients with intra-pod reduces only; the pod-axis reduce-scatter of
  microbatch i then runs at the top of iteration i+1, where it has no
  data dependency on microbatch i+1's forward and overlaps with it.
  Memory trade: the stage-1-gathered param view is materialized at leaf
  level for the whole model (instead of per layer inside the scan) and
  one stage-1-sized gradient buffer rides the scan carry --
  core/schedule.py:async_buffer_bytes is the analytic per-chip cost,
  surfaced through core/cache.py. Per-step DCN volume is unchanged (the
  reduce moves, it is not added).

  cross-step (SystemConfig.cross_step_pipeline, scheduler stream 3,
  rides the async stream): the once-per-step optimizer tail -- the LAST
  microbatch's pod-axis reduce-scatter, the optimizer apply, and the
  widened updated-shard all-gather -- is carried across the step
  boundary instead of serializing at the end of the step. The step
  function splits into three compiled bodies sharing one closure:

    prime(params, frozen, opt, batch)        -> (carry, metrics)
    piped(params, frozen, opt, carry, batch) -> (params', opt', carry',
                                                 metrics)
    flush(params, opt, carry)                -> (params', opt', metrics)

  ``carry`` holds step i's accumulated storage-level grads plus the last
  microbatch's stage-1-level pending grads (the stream-2 fold,
  generalized to the step level). ``piped`` finalizes the carry at its
  TOP -- pod reduce + widen reduce-scatter + clip + AdamW +
  widened all-gather -- and runs its own microbatch loop against the
  UPDATED parameters, so the schedule is staleness-free: the epilogue
  collectives merely sit next to step i+1's first-microbatch forward
  prologue in one program, where XLA's latency-hiding scheduler overlaps
  them (they have no data dependency on the batch). Per-step DCN volume
  is byte-identical to the fused step: prime defers one reduce-scatter +
  one epilogue, every piped step retires exactly one while deferring its
  own, flush retires the last. Carry leaves cross the jit boundary with
  a leading 'partial' dimension sharded over every mesh axis their
  payload still varies over but does not mention (the pod axis of the
  pending stage-1 grads), so the pre-reduction partial sums are
  honestly typed (each device row holds its own partial; per-chip bytes
  are one shard -- core/schedule.py:cross_step_buffer_bytes is the
  analytic cost).
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import all_gather_invariant
from repro.core import schedule as sched
from repro.core.strategy import spec_axes
from repro.optim.adamw import adamw_update, clip_by_global_norm


def _entry_axes(spec: P, dim) -> tuple:
    """Mesh axes a PartitionSpec shards dimension ``dim`` over."""
    if dim is None or dim >= len(spec):
        return ()
    e = spec[dim]
    if e is None:
        return ()
    return tuple(e) if isinstance(e, (tuple, list)) else (e,)


# ---------------------------------------------------------------------------
# Cross-step carry layout (consumed by StepBundle for the dry-run sds)
# ---------------------------------------------------------------------------

def _stage1_storage_spec(spec: P, pdef, plan) -> P:
    """Storage-level PartitionSpec of the stage-1-gathered view of one
    leaf: the inter (DCN) axes stripped from the fsdp-dim entry. The
    identity for single-stage leaves."""
    if pdef.fsdp_dim is None or not (plan.is_gathered and plan.inter_axes):
        return spec
    entries = list(spec) + [None] * (len(pdef.shape) - len(spec))
    e = entries[pdef.fsdp_dim]
    axes = tuple(a for a in ((e,) if isinstance(e, str) else tuple(e or ()))
                 if a not in plan.inter_axes)
    entries[pdef.fsdp_dim] = (axes if len(axes) > 1
                              else (axes[0] if axes else None))
    return P(*entries)


def _carried_spec(base: P, varying, pdef, mi):
    """(full_spec, global_shape) of one carry leaf: the payload spec
    plus a leading 'partial' dim sharded over the mesh axes the payload
    varies over (``varying``, the storage spec's axes: autodiff types a
    gradient like its parameter) but does not mention. Pre-reduction
    gradients genuinely differ along those axes (partial sums awaiting
    their reduce), so the leading dim makes the global array honest --
    each device row holds its own partial -- while per-chip storage
    stays one shard. Over every other axis the gradient is already
    reduced, and the carry is typed replicated there, so the finalized
    parameters and optimizer state satisfy their out_specs."""
    lead = tuple(a for a in mi.axis_names
                 if a in varying and a not in spec_axes(base))
    entries = list(base) + [None] * (len(pdef.shape) - len(base))
    full = P(lead if len(lead) > 1 else (lead[0] if lead else None),
             *entries)
    shape = (max(1, math.prod(mi.size(a) for a in lead)),) + tuple(pdef.shape)
    return full, shape


def cross_step_carry_layout(bundle):
    """Per-train-leaf carry layout for the cross-step pipeline:
    ``{"g_acc": [(spec, global_shape, dtype), ...], "pending": [...]}``.
    ``g_acc`` leaves are storage-level accumulated gradients, ``pending``
    leaves are stage-1-level last-microbatch gradients (the deferred pod
    reduce operand)."""
    out = {"g_acc": [], "pending": []}
    for i in bundle.train_idx:
        d = bundle.def_leaves[i]
        plan = bundle.plan_leaves[i]
        spec = bundle.leaf_specs[i]
        for key, base in (("g_acc", spec),
                          ("pending", _stage1_storage_spec(spec, d, plan))):
            full, shape = _carried_spec(base, spec_axes(spec), d, bundle.mi)
            out[key].append((full, shape, d.dtype))
    return out


def cross_step_carry_signature(bundle):
    """``[(global_shape, dtype_str), ...]`` of the carry leaves in
    checkpoint flatten order (the ``carry`` dict's keys sort g_acc before
    pending) -- what ``runtime/elastic.reshard_state`` compares against a
    saved manifest's carry section to decide mesh-compatibility. The
    leading partial dim is mesh-shaped (the product of the varying,
    unmentioned axes' sizes), so a mesh change can show up here even
    when the payload shapes agree; a carry that fails this check must be invalidated and
    re-primed, never ``device_put`` as stale partials."""
    layout = cross_step_carry_layout(bundle)
    return [(tuple(shape), str(jnp.dtype(dtype)))
            for key in sorted(layout)
            for _, shape, dtype in layout[key]]


def _lift(x, axes):
    """Cast ``x`` to varying over whichever of ``axes`` its vma is
    missing: carry outputs must vary over every axis their out spec
    mentions."""
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, need, to="varying") if need else x


# ---------------------------------------------------------------------------
# Shared step-body parts
# ---------------------------------------------------------------------------

def _build_parts(bundle):
    run, mesh, mi = bundle.run, bundle.mesh, bundle.mi
    sys, opt_cfg = run.system, run.optimizer
    strategy = bundle.strategy
    model = bundle.model
    train_defs = [bundle.def_leaves[i] for i in bundle.train_idx]
    train_plans = [bundle.plan_leaves[i] for i in bundle.train_idx]
    frozen_defs = [bundle.def_leaves[i] for i in bundle.frozen_idx]
    frozen_plans = [bundle.plan_leaves[i] for i in bundle.frozen_idx]
    train_reps = [bundle.rep_factors[i] for i in bundle.train_idx]
    wd_mask = [len(d.shape) >= 2 and "_lora_" not in d.label
               for d in train_defs]
    dp_axes = mi.fsdp_axes
    tp_present = mi.tp > 1
    cell = run.shape
    bspecs = bundle.batch_spec(cell)
    # Optimizer state wider than param storage (ZeRO-2-for-experts,
    # hier's ('data','pod') opt sharding): grads get a reduce-scatter
    # over the widening axes before the update, updated shards get one
    # all-gather back per step.
    widen = {}
    for j, i in enumerate(bundle.train_idx):
        d = bundle.def_leaves[i]
        extra = tuple(
            a for a in _entry_axes(bundle.full_specs[i], d.fsdp_dim)
            if a not in _entry_axes(bundle.leaf_specs[i], d.fsdp_dim))
        if extra:
            widen[j] = (d.fsdp_dim, extra)

    def rs_widen(g, dim, axes):
        return jax.lax.psum_scatter(g, axes, scatter_dimension=dim,
                                    tiled=True)

    def ag_widen(p_, dim, axes):
        for a in reversed(axes):   # invert the tiled multi-axis scatter
            p_ = all_gather_invariant(p_, a, axis=dim, tiled=True)
        return p_

    # -- async pod-axis gradient-reduce stream (scheduler stream 2) ---------
    use_async = sched.async_reduce_enabled(run, strategy, mi)
    use_xstep = sched.cross_step_enabled(run, strategy, mi)
    g1_model = (model.with_plans(sched.stage1_resident_plans(model.plans))
                if use_async else None)
    nm = run.microbatch or 0

    def loss_fn_of(train_params, frozen_params, batch):
        params = bundle.merge(train_params, frozen_params)
        loss_sum, cnt, aux = model.loss_fn(params, batch)
        with jax.named_scope("loss"):
            loss_sum = (jax.lax.psum(loss_sum, dp_axes) if dp_axes
                        else loss_sum)
            cnt = jax.lax.psum(cnt, dp_axes) if dp_axes else cnt
            aux = jax.lax.psum(aux, dp_axes) if dp_axes else aux
            ce = loss_sum / jnp.maximum(cnt, 1.0)
            aux_n = aux / jnp.maximum(cnt, 1.0)
        return ce + aux_n, (ce, aux_n, cnt)

    def mb_slice(x, i):
        b = x.shape[0] // nm
        return jax.lax.dynamic_slice_in_dim(x, i * b, b, axis=0)

    def mb_loss_of(params_builder, mdl):
        def mb_loss(tp_, mb):
            params = params_builder(tp_)
            ls, c, a = mdl.loss_fn(params, mb)
            ls = jax.lax.psum(ls, dp_axes) if dp_axes else ls
            c = jax.lax.psum(c, dp_axes) if dp_axes else c
            a = jax.lax.psum(a, dp_axes) if dp_axes else a
            ce = ls / jnp.maximum(c, 1.0)
            return ce + a / jnp.maximum(c, 1.0), ce
        return mb_loss

    def g1_of(leaves, defs_, plans_):
        return [sched.leaf_stage1(w, d, p)
                for w, d, p in zip(leaves, defs_, plans_)]

    def pod_reduce(pending):
        return [sched.leaf_stage1_reduce(g, d, p)
                for g, d, p in zip(pending, train_defs, train_plans)]

    def grad_zero(train_params):
        from repro.models.common import pvary_like
        return jax.tree.map(
            lambda p_: pvary_like(jnp.zeros_like(p_), p_), train_params)

    def accumulate_async(train_params, frozen_params, ce0, batch):
        """The stream-2 microbatch loop: differentiate each microbatch
        w.r.t. the stage-1-gathered view, reduce the PREVIOUS
        microbatch's stage-1 grads at the top of each iteration
        (microbatch 0 peeled so exactly nm-1 reduce-scatters run
        in-loop), and return the accumulated storage-level grads plus
        the last microbatch's still-pending stage-1 grads."""
        mb_loss = mb_loss_of(
            lambda tp_: bundle.merge(
                tp_, g1_of(frozen_params, frozen_defs, frozen_plans)),
            g1_model)

        def mb_grads(i):
            mb = jax.tree.map(lambda x: mb_slice(x, i), batch)
            g1_tp = g1_of(train_params, train_defs, train_plans)
            return jax.value_and_grad(mb_loss, has_aux=True)(g1_tp, mb)

        def acc_body(carry, i):
            g_acc, pending, ce_acc = carry
            # stream 2: fold the PREVIOUS microbatch's stage-1 grads
            # down to storage shards -- a pure DCN reduce-scatter with
            # no data dependency on this microbatch's forward below, so
            # the latency-hiding scheduler overlaps the two
            g_acc = jax.tree.map(jnp.add, g_acc, pod_reduce(pending))
            (_, ce), g1_g = mb_grads(i)
            return (g_acc, g1_g, ce_acc + ce), None

        (_, ce_first), pending0 = mb_grads(0)
        (g_acc, pending, ce_sum), _ = jax.lax.scan(
            acc_body, (grad_zero(train_params), pending0, ce0 + ce_first),
            jnp.arange(1, nm))
        return g_acc, pending, ce_sum

    def accumulate_seq(train_params, frozen_params, ce0, batch):
        """Sequential accumulation: every microbatch's backward carries
        the full gather transposes (reduce inside the backward)."""
        mb_loss = mb_loss_of(
            lambda tp_: bundle.merge(tp_, frozen_params), model)

        def acc_body(carry, i):
            g_acc, ce_acc = carry
            mb = jax.tree.map(lambda x: mb_slice(x, i), batch)
            (_, ce), g = jax.value_and_grad(
                mb_loss, has_aux=True)(train_params, mb)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, ce_acc + ce), None
        (grads, ce_sum), _ = jax.lax.scan(
            acc_body, (grad_zero(train_params), ce0), jnp.arange(nm))
        return grads, ce_sum

    def fold(g_acc, pending):
        """Retire the deferred last-microbatch reduce and normalize."""
        grads = jax.tree.map(jnp.add, g_acc, pod_reduce(pending))
        return jax.tree.map(lambda g: g / nm, grads)

    def apply_grads(grads, opt_state):
        """The optimizer epilogue: replicated-storage grad psums, widen
        reduce-scatter, global-norm clip, AdamW on shards, widened
        updated-shard all-gather. One call site per schedule so the op
        order (and therefore the bits) are identical whether the
        epilogue runs fused or carried across the step boundary. Its
        ops carry the program scope ``optimizer``."""
        with jax.named_scope("optimizer"):
            return _epilogue(grads, opt_state)

    def _epilogue(grads, opt_state):
        if widen:
            grads = [rs_widen(g, *widen[j]) if j in widen else g
                     for j, g in enumerate(grads)]
        grads, gnorm = clip_by_global_norm(
            grads, train_reps, opt_cfg.grad_clip, dp_axes, tp_present)
        new_params, new_opt = adamw_update(
            grads, opt_state, opt_cfg, sys, wd_mask)
        if widen:
            new_params = [ag_widen(p_, *widen[j]) if j in widen else p_
                          for j, p_ in enumerate(new_params)]
        return new_params, new_opt, gnorm

    # the loss-carry zero: scan requires the carry's replication type to
    # match the body output's (replicated over every axis after the
    # loss psums)
    def ce_zero(opt_state):
        return (opt_state["step"] * 0).astype(jnp.float32)

    train_specs = [bundle.leaf_specs[i] for i in bundle.train_idx]
    frozen_specs = [bundle.leaf_specs[i] for i in bundle.frozen_idx]
    opt_leaf_specs = [bundle.full_specs[i] for i in bundle.train_idx]
    opt_specs = {"m": opt_leaf_specs, "v": opt_leaf_specs,
                 "master": opt_leaf_specs, "step": P()}
    metric_specs = {"loss": P(), "aux_loss": P(), "grad_norm": P(),
                    "tokens": P()}

    return SimpleNamespace(
        mesh=mesh, nm=nm, use_async=use_async, use_xstep=use_xstep,
        loss_fn_of=loss_fn_of, accumulate_async=accumulate_async,
        accumulate_seq=accumulate_seq, fold=fold, apply_grads=apply_grads,
        ce_zero=ce_zero, train_specs=train_specs,
        frozen_specs=frozen_specs, opt_specs=opt_specs, bspecs=bspecs,
        metric_specs=metric_specs)


# ---------------------------------------------------------------------------
# Cross-step carry pack/unpack
# ---------------------------------------------------------------------------

def _carry_io(bundle):
    layout = cross_step_carry_layout(bundle)
    specs = {k: [s for s, _, _ in v] for k, v in layout.items()}
    mention = {k: [tuple(sorted(spec_axes(s))) for s, _, _ in v]
               for k, v in layout.items()}

    def pack(g_acc, pending):
        return {"g_acc": [_lift(g, mention["g_acc"][j])[None]
                          for j, g in enumerate(g_acc)],
                "pending": [_lift(g, mention["pending"][j])[None]
                            for j, g in enumerate(pending)]}

    def unpack(carry):
        return ([x[0] for x in carry["g_acc"]],
                [x[0] for x in carry["pending"]])

    return specs, pack, unpack


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def build_train_step(bundle):
    """The steady-state train step for this bundle's schedule: the fused
    step (``(params, frozen, opt, batch) -> (params', opt', metrics)``)
    normally, the cross-step pipelined step (extra carry in/out, see the
    module docstring) when stream 3 is live -- StepBundle.train_input_sds
    tracks the signature, so dry-run/planner/bench lowering is uniform."""
    c = _build_parts(bundle)
    if c.use_xstep:
        return _build_piped(bundle, c)
    return _build_fused(bundle, c)


def _build_fused(bundle, c):
    def step_body(train_params, frozen_params, opt_state, batch):
        if c.nm > 1:
            ce0 = c.ce_zero(opt_state)
            if c.use_async:
                g_acc, pending, ce_sum = c.accumulate_async(
                    train_params, frozen_params, ce0, batch)
                # epilogue: the last microbatch's reduce has nothing
                # left to hide behind inside this step
                grads = c.fold(g_acc, pending)
            else:
                grads, ce_sum = c.accumulate_seq(
                    train_params, frozen_params, ce0, batch)
                grads = jax.tree.map(lambda g: g / c.nm, grads)
            ce, auxl, cnt = ce_sum / c.nm, jnp.float32(0), jnp.float32(1)
        else:
            (_, (ce, auxl, cnt)), grads = jax.value_and_grad(
                c.loss_fn_of, has_aux=True)(train_params, frozen_params,
                                            batch)
        new_params, new_opt, gnorm = c.apply_grads(grads, opt_state)
        metrics = {"loss": ce, "aux_loss": auxl, "grad_norm": gnorm,
                   "tokens": cnt}
        return new_params, new_opt, metrics

    fn = jax.shard_map(
        step_body, mesh=c.mesh,
        in_specs=(c.train_specs, c.frozen_specs, c.opt_specs, c.bspecs),
        out_specs=(c.train_specs, c.opt_specs, c.metric_specs))
    return jax.jit(fn, donate_argnums=(0, 2))


def _build_piped(bundle, c):
    """Steady-state cross-step body: finalize the carried epilogue of
    step i-1 (producing the updated params this step's forward
    consumes), then run this step's microbatch loop and emit the next
    carry. The epilogue collectives at the top have no data dependency
    on the batch, so they overlap the first microbatch's forward
    prologue under the latency-hiding scheduler."""
    carry_specs, pack, unpack = _carry_io(bundle)

    def step_body(train_params, frozen_params, opt_state, carry, batch):
        g_acc, pending = unpack(carry)
        new_params, new_opt, gnorm = c.apply_grads(
            c.fold(g_acc, pending), opt_state)
        g_acc2, pending2, ce_sum = c.accumulate_async(
            new_params, frozen_params, c.ce_zero(new_opt), batch)
        metrics = {"loss": ce_sum / c.nm, "aux_loss": jnp.float32(0),
                   "grad_norm": gnorm, "tokens": jnp.float32(1)}
        return new_params, new_opt, pack(g_acc2, pending2), metrics

    fn = jax.shard_map(
        step_body, mesh=c.mesh,
        in_specs=(c.train_specs, c.frozen_specs, c.opt_specs, carry_specs,
                  c.bspecs),
        out_specs=(c.train_specs, c.opt_specs, carry_specs, c.metric_specs))
    return jax.jit(fn, donate_argnums=(0, 2, 3))


def build_train_prime(bundle):
    """Pipeline-fill step: run the microbatch loop against the CURRENT
    parameters and defer the whole epilogue into the first carry.
    Parameters and optimizer state are left untouched (the caller keeps
    them for the first piped step); grad_norm is reported as 0 until the
    first finalize computes it."""
    c = _build_parts(bundle)
    if not c.use_xstep:
        raise ValueError("cross-step pipeline is not live for this run "
                         "(see core/schedule.py:cross_step_enabled)")
    carry_specs, pack, _ = _carry_io(bundle)

    def step_body(train_params, frozen_params, opt_state, batch):
        g_acc, pending, ce_sum = c.accumulate_async(
            train_params, frozen_params, c.ce_zero(opt_state), batch)
        metrics = {"loss": ce_sum / c.nm, "aux_loss": jnp.float32(0),
                   "grad_norm": jnp.float32(0), "tokens": jnp.float32(1)}
        return pack(g_acc, pending), metrics

    fn = jax.shard_map(
        step_body, mesh=c.mesh,
        in_specs=(c.train_specs, c.frozen_specs, c.opt_specs, c.bspecs),
        out_specs=(carry_specs, c.metric_specs))
    return jax.jit(fn)


def build_train_flush(bundle):
    """Pipeline-drain step: finalize the outstanding carry (the last
    step's epilogue) with no forward attached. Run once at the end of
    training and before any checkpoint save, so persisted state is
    always post-update."""
    c = _build_parts(bundle)
    if not c.use_xstep:
        raise ValueError("cross-step pipeline is not live for this run "
                         "(see core/schedule.py:cross_step_enabled)")
    carry_specs, _, unpack = _carry_io(bundle)

    def step_body(train_params, opt_state, carry):
        g_acc, pending = unpack(carry)
        new_params, new_opt, gnorm = c.apply_grads(
            c.fold(g_acc, pending), opt_state)
        return new_params, new_opt, {"grad_norm": gnorm}

    fn = jax.shard_map(
        step_body, mesh=c.mesh,
        in_specs=(c.train_specs, c.opt_specs, carry_specs),
        out_specs=(c.train_specs, c.opt_specs, {"grad_norm": P()}))
    return jax.jit(fn, donate_argnums=(0, 1, 2))
