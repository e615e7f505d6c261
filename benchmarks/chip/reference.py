"""Plain float32 reference machinery, common to every model family.

It imports nothing of the program and takes nothing the program made.
A family (``families/<family>.py``) gives its model: the parameter
leaves (``param_specs``) and ``reference_model``, an ordered list of
layer groups (``Group``) and the leaves of the embedding, final norm and
head (``Model``). This module gives what the families share: the leaves'
initialisation, the pieces of a decoder (RMSNorm, RoPE, blocked causal
attention, the blocked loss), the fp8 control, and the driver that
trains a ``Model`` layer by layer.

Weights are drawn again from the seed by the rule the program documents
(``repro.core.partition.init_params``): one key per parameter leaf,
split from ``key(seed)`` in the order of the leaves' sorted paths;
normal leaves are N(0, 1) / sqrt(fan_in) (the embedding N(0, 0.02^2)),
norms are ones, biases and LoRA B are zeros; every leaf is stored in
bf16, and the optimizer's f32 master starts as that bf16 value.

Training: the gradient of the mean cross-entropy over the tokens the
mask keeps, clipped to a global norm, then AdamW with bias correction
and decoupled weight decay on every trainable leaf of two or more
stored dimensions except LoRA adapters (stacked norm scales included:
the program stores a layer's scales as one [layers, d] leaf). The
forward runs on the bf16 copy of the f32 masters, as the configuration
states its weights (bf16, f32 masters); every operation is computed in
f32 with matmuls at ``highest`` precision.

It runs layer by layer so that it fits beside nothing else on the chip:
the forward walks the groups in order and keeps each layer's input, the
backward recomputes one layer at a time under ``jax.vjp``, and attention
and the loss run in blocks of rows. On several devices the arrays are
spread over a one-axis mesh and XLA partitions the plain program.

``precision="fp8"`` is the control: every matmul and attention product
takes its operands (and, in the backward, its incoming gradient) rounded
to float8 e4m3 with one scale per tensor, the step below the bf16 that
the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

F32, BF16 = jnp.float32, jnp.bfloat16
Q_BLOCK = 512           # attention query rows per block
LOSS_BLOCK = 1024       # loss rows per block


class LeafSpec(NamedTuple):
    path: str           # dotted path, e.g. "blocks.<stack>.attn.wq"
    shape: tuple
    init: str           # normal | embed | ones | zeros
    trainable: bool


class Group(NamedTuple):
    """A stack of layers of one kind: its leaves are the ones under
    ``prefix``, each stored as [layers, ...]."""
    prefix: str         # the stack's path, e.g. "blocks.<stack>"
    layers: int
    layer: Callable     # layer(x, p, ein) -> x; p: one layer's weights
    #                     by their path under the prefix, e.g. "attn.wq"


class Model(NamedTuple):
    """A family's reference model: embedding, groups, final norm, head."""
    groups: List[Group]     # in the order the forward runs them
    embed: str              # the embedding table's leaf, [V, D]
    final_norm: str
    head: str               # the head's leaf, [D, V]; tied, the embedding
    #                         table itself, used transposed
    norm_eps: float         # the final norm's epsilon

    @property
    def tied(self) -> bool:
        return self.head == self.embed


def leaf_keys(seed: int, n: int):
    return jax.random.split(jax.random.key(seed), max(n, 1))


def init_leaf(key, spec: LeafSpec):
    """The leaf's initial value: drawn in f32, stored in bf16, returned
    as the f32 master that starts from it."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, F32)
    if spec.init == "ones":
        return jnp.ones(spec.shape, F32)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = 0.02 if spec.init == "embed" else 1.0 / math.sqrt(max(fan_in, 1))
    return to_bf16(jax.random.normal(key, spec.shape, F32) * scale)


@functools.partial(jax.jit, static_argnums=2)
def moved_norm(w, key, spec: LeafSpec):
    """|w - the leaf's initial value|, the initial value drawn again."""
    return jnp.sqrt(jnp.sum(jnp.square(w.astype(F32) - init_leaf(key, spec))))


def to_bf16(x):
    """Round f32 to bf16's precision, kept in f32. Written as
    ``reduce_precision`` because XLA may drop an f32 -> bf16 -> f32
    round trip of ``astype`` as excess precision (the TPU compiler
    does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# ---------------------------------------------------------------------------
# Matmuls: f32 at highest precision, or the fp8 control
# ---------------------------------------------------------------------------

def _round_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


@jax.custom_vjp
def _q_operand(x):
    return _round_fp8(x)


_q_operand.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_grad(y):
    return y


_q_grad.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g),))


def _einsum(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b,
                                             precision="highest")
    if precision == "fp8":
        return lambda spec, a, b: _q_grad(jnp.einsum(
            spec, _q_operand(a), _q_operand(b), precision="highest"))
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Pieces of a decoder
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: [B, S, heads, hd]; rotates the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, ein):
    """Causal GQA attention, a block of query rows at a time.
    q: [B, S, H, hd]; k, v: [B, S, KV, hd]."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 else 1
    qb = q.reshape(B, nb, S // nb, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        i, qi = args
        rows = i * (S // nb) + jnp.arange(S // nb)
        s = ein("bqhd,bkhd->bhqk", qi, k) / math.sqrt(hd)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


def loss_sums(x, final_norm, head, labels, mask, eps, ein):
    """(sum of masked token cross-entropies, count), blocks of rows."""
    B, S, D = x.shape
    h = rms_norm(x, final_norm, eps)
    nb = S // LOSS_BLOCK if S % LOSS_BLOCK == 0 else 1

    @jax.checkpoint
    def block(args):
        hi, li, mi = args
        logits = ein("bsd,dv->bsv", hi, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, li[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(mi, lse - picked, 0.0))

    def split(a):
        return a.reshape((B, nb, S // nb) + a.shape[2:]).swapaxes(0, 1)

    total = jnp.sum(jax.lax.map(block, (split(h), split(labels), split(mask))))
    return total, jnp.sum(mask.astype(F32))


# ---------------------------------------------------------------------------
# The layer-by-layer driver
# ---------------------------------------------------------------------------

def group_leaves(model: Model, specs: List[LeafSpec]) -> Dict[str, List[str]]:
    """{group prefix: the paths of its leaves}; every leaf belongs to a
    group or is the embedding, final norm or head."""
    top = {model.embed, model.final_norm, model.head}
    out = {g.prefix: [s.path for s in specs
                      if s.path.startswith(g.prefix + ".")]
           for g in model.groups}
    claimed = top.union(*out.values())
    stray = [s.path for s in specs if s.path not in claimed]
    if stray:
        raise ValueError(f"leaves in no group of the model: {stray}")
    return out


def layerwise(model: Model, specs: List[LeafSpec], trainable, ein, mesh,
              row):
    """The model's loss and its gradient by every leaf in ``trainable``,
    one layer at a time: ``grad(master, ids, labels, mask) -> (loss,
    {path: gradient})``, where ``master`` holds every leaf by path and
    the batch is placed by ``row``."""
    members = group_leaves(model, specs)
    rep = NamedSharding(mesh, P())

    def bf16_value(w):
        # the bf16 copy the forward runs on, whole on every device
        return jax.lax.with_sharding_constraint(to_bf16(w.astype(F32)), rep)

    def rows(x):
        return jax.lax.with_sharding_constraint(x, row)

    def passes(group: Group):
        n = len(group.prefix) + 1

        def weights(stack, l):
            return {p[n:]: bf16_value(stack[p][l]) for p in stack}

        @jax.jit
        def fwd(x, stack, l):
            return rows(group.layer(rows(x), weights(stack, l), ein))

        @jax.jit
        def bwd(x, train_stack, frozen_stack, l, dy):
            def f(x_, tp):
                p = weights(frozen_stack, l)
                p.update({k[n:]: bf16_value(v) for k, v in tp.items()})
                return rows(group.layer(rows(x_), p, ein))
            tp = {k: v[l] for k, v in train_stack.items()}
            _, vjp = jax.vjp(f, x, tp)
            return vjp(dy)
        return fwd, bwd

    fns = [passes(g) for g in model.groups]

    @functools.partial(jax.jit, donate_argnums=0)
    def put(buf, g, l):
        return buf.at[l].set(g)

    def head_of(w):
        w = bf16_value(w)
        return w.T if model.tied else w

    @jax.jit
    def top(x, final_norm, hw, labels, mask):
        def f(x_, fn, hw_):
            s, c = loss_sums(rows(x_), bf16_value(fn), head_of(hw_),
                             labels, mask, model.norm_eps, ein)
            return s / jnp.maximum(c, 1.0)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, final_norm, hw)

    @jax.jit
    def embed(table, ids):
        return rows(jnp.take(bf16_value(table), ids, axis=0))

    @jax.jit
    def embed_grad(table, ids, dx):
        return jnp.zeros(table.shape, F32).at[ids].add(dx)

    def grad(master, ids, labels, mask):
        stacks = [({p: master[p] for p in members[g.prefix] if p in trainable},
                   {p: master[p] for p in members[g.prefix]
                    if p not in trainable}) for g in model.groups]
        xs = [embed(master[model.embed], ids)]
        for g, (fwd, _), (ts, fs) in zip(model.groups, fns, stacks):
            for l in range(g.layers):
                xs.append(fwd(xs[-1], {**ts, **fs}, jnp.int32(l)))
        loss, (dx, g_fn, g_head) = top(xs[-1], master[model.final_norm],
                                       master[model.head], labels, mask)
        xs.pop()
        grads = {p: jnp.zeros_like(v) for ts, _ in stacks
                 for p, v in ts.items()}
        if model.final_norm in trainable:
            grads[model.final_norm] = g_fn
        for g, (_, bwd), (ts, fs) in reversed(list(zip(model.groups, fns,
                                                       stacks))):
            for l in reversed(range(g.layers)):
                dx, g_l = bwd(xs.pop(), ts, fs, jnp.int32(l), dx)
                for p in ts:
                    grads[p] = put(grads[p], g_l[p], jnp.int32(l))
                del g_l
        if model.embed in trainable:
            g_e = embed_grad(master[model.embed], ids, dx)
            # tied: the head's gradient is already the table's
            grads[model.embed] = g_e + g_head if model.tied else g_e
            if not model.tied:
                grads[model.head] = g_head
        return loss, grads
    return grad


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

class Result(NamedTuple):
    losses: List[float]
    grad_norms: List[Dict[str, float]]     # per step, clipped, per leaf
    change_norms: Dict[str, float]         # |master_T - master_0| per leaf


def _lr(opt: dict, t: int) -> float:
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    decay = {"constant": 1.0, "linear": 1.0 - frac,
             "cosine": 0.5 * (1 + math.cos(math.pi * frac))}[opt["schedule"]]
    return opt["lr"] * warm * decay


def _sharding(mesh, shape):
    """Spread an array over the mesh along its largest dimension that
    divides evenly; replicate it where none does."""
    n = mesh.size
    dims = [i for i in np.argsort(shape)[::-1] if shape[i] % n == 0]
    spec = [None] * len(shape)
    if n > 1 and dims:
        spec[dims[0]] = "x"
    return NamedSharding(mesh, P(*spec))


def train(family, cfg: dict, job: dict, mix: dict, seed: int, steps: int,
          devices, precision: str = "f32") -> Result:
    """``steps`` training steps of ``family``'s model of ``cfg`` from the
    seed's weights on the seed's traffic (``traffic.PackedLM``)."""
    from benchmarks.chip.traffic import PackedLM
    if job["mesh"]["model"] != 1:
        raise ValueError("the reference draws weights at the program's "
                         "shapes for a model axis of 1 only")
    peft, opt = job.get("peft") or None, job["optimizer"]
    mesh = Mesh(np.asarray(devices), ("x",))
    specs = family.param_specs(cfg, peft)
    keys = leaf_keys(seed, len(specs))
    data = PackedLM(mix, cfg["vocab_size"], seed)
    batches = [data.batch_np(t) for t in range(steps)]
    B = batches[0]["ids"].shape[0]
    row = NamedSharding(mesh, P("x") if B % mesh.size == 0 else P())

    with jax.default_matmul_precision("highest"):
        master = {}
        for k, s in zip(keys, specs):
            # frozen leaves stay in bf16, which holds their values exactly
            dtype = F32 if s.trainable else BF16
            master[s.path] = jax.jit(
                lambda k_, s_=s, dt=dtype: init_leaf(k_, s_).astype(dt),
                out_shardings=_sharding(mesh, s.shape))(k)
        trainable = [s.path for s in specs if s.trainable]
        moments = {p: (jnp.zeros_like(master[p]), jnp.zeros_like(master[p]))
                   for p in trainable}
        grad = layerwise(family.reference_model(cfg, peft), specs,
                         frozenset(trainable), _einsum(precision), mesh, row)

        @jax.jit
        def sq(g):
            return jnp.sum(jnp.square(g.astype(F32)))

        @jax.jit
        def adamw(m, v, w, g, scale, lr, t, wd):
            g = g * scale
            m = opt["b1"] * m + (1 - opt["b1"]) * g
            v = opt["b2"] * v + (1 - opt["b2"]) * g * g
            upd = (m / (1 - opt["b1"] ** t)) / (
                jnp.sqrt(v / (1 - opt["b2"] ** t)) + opt["eps"])
            return m, v, w - lr * (upd + wd * w)

        losses, grad_norms = [], []
        for t in range(1, steps + 1):
            b = batches[t - 1]
            ids, labels, mask = (jax.device_put(b[k], row)
                                 for k in ("ids", "labels", "mask"))
            loss, grads = grad(master, ids, labels, mask)
            losses.append(float(loss))
            norms = {p: float(sq(g)) for p, g in grads.items()}
            total = math.sqrt(sum(norms.values()))
            scale = min(1.0, opt["grad_clip"] / max(total, 1e-12))
            grad_norms.append({p: math.sqrt(n) * scale
                               for p, n in norms.items()})
            lr = _lr(opt, t)
            for p in trainable:
                m, v = moments[p]
                wd = (opt["weight_decay"] if len(master[p].shape) >= 2
                      and "_lora_" not in p else 0.0)
                m, v, master[p] = adamw(m, v, master[p], grads.pop(p),
                                        F32(scale), F32(lr), F32(t), F32(wd))
                moments[p] = (m, v)
        del moments
        change = {s.path: float(moved_norm(master[s.path], k, s))
                  for k, s in zip(keys, specs) if s.trainable}
    return Result(losses, grad_norms, change)
