"""Device time by program scope, the program's own host spans, and its
counters, for the traced window of one run.

The program names its layers with ``jax.named_scope`` (``SCOPES``). A
scope changes nothing in the compiled step but the ``op_name`` metadata
of its instructions, for example::

    jit(step_body)/transpose(jvp(stack))/while/body/closed_call/
        checkpoint/rematted_computation/mlp/dot_general

That one string gives the innermost program scope (``mlp``), the pass
(``transpose(`` is the backward, ``jvp(`` the forward, neither the
optimizer's epilogue) and whether the op is the forward recomputed in
the backward (``rematted_computation``). An instruction the compiler
made, with no metadata of its own, takes that of its neighbours
(``metadata``). Trace ops are matched to instructions by name, as the
roofline of ``trace.py`` matches them.

The program's host spans (``PROGRAM_SPANS``) are kept in a list of their
own: the benchmark's window and its idle gaps are ``trace.py``'s alone.
``RunState.counters`` (set-up seconds per phase, the memory peak at the
end of set-up, program lowerings per step) is carried as it is.

``of(run)`` builds all of this once per traced run, for the readers
under ``metrics/``. It finds the run's program state and trace directory
in the frame of ``harness.run`` that holds ``run``, because the reader
interface passes ``run`` alone and ``trace.RunTrace`` keeps neither.
Against a program without scopes, spans or counters every such reading
is None.
"""
from __future__ import annotations

import glob
import inspect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Tuple

import jax

from benchmarks.chip import trace

SCOPES = ("stack", "attention", "mlp", "moe", "mamba", "xattn", "rwkv_tm",
          "rwkv_cm", "fcdp.gather1", "fcdp.gather2", "embed", "loss",
          "optimizer")
PROGRAM_SPANS = ("data.get", "data.make", "data.place")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC = re.compile(r'loc\("([^"]*)"')
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")
_DEPTH = 64          # how far ``metadata`` looks for a neighbour's name


# ---------------------------------------------------------------------------
# op_name metadata of a compiled step
# ---------------------------------------------------------------------------

@dataclass
class Metadata:
    """What a compiled step's text says of its instructions, by name."""
    op_name: Dict[str, str]
    collectives: FrozenSet[str]   # collectives, and fusions that run one


def metadata(hlo_text: str) -> Metadata:
    """The ``op_name`` of each instruction of a compiled module's text.
    An instruction without one of its own (one the compiler made: a
    fusion, a copy, a split dot, a reduce-scatter lowered to an
    all-reduce) takes the fullest of those in the computations it calls
    (``calls=``, ``to_apply=``), else that of its first operand that has
    one, else that of its first user that has one of its own.
    Parameters' labels (``train_params[0]``) name no op and are not
    taken."""
    own: Dict[str, str] = {}
    sub: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    opcode: Dict[str, str] = {}
    body: Dict[str, List[str]] = defaultdict(list)   # computation -> its
    comp = None            # instructions in text order (the ROOT is last)
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t", "") and line.rstrip().endswith("{"):
            words = line.split()
            comp = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        opcode[name] = m.group(2)
        o = _OP_NAME.search(line)
        if o and "/" in o.group(1):
            own[name] = o.group(1)
        sub[name] = _CALLED.findall(line)
        close = line.find(")", m.end())
        operands[name] = _OPERAND.findall(line[m.end():close])
        if comp is not None:
            body[comp].append(name)

    memo: Dict[str, Optional[str]] = {}

    def down(name: str, depth: int = 0) -> Optional[str]:
        if name in own or name in memo or depth > _DEPTH:
            return own.get(name) or memo.get(name)
        memo[name] = None                 # a cycle reads as no name
        inner = [n for c in sub.get(name, ()) for n in reversed(body[c])]
        found = [got for n in inner if (got := down(n, depth + 1))]
        if found:
            memo[name] = max(found, key=lambda x: x.count("/"))
        else:
            memo[name] = next((got for n in operands.get(name, ())
                               if (got := down(n, depth + 1))), None)
        return memo[name]

    out = {n: got for n in operands if (got := down(n))}
    users: Dict[str, List[str]] = defaultdict(list)
    for n, ops in operands.items():
        for o in ops:
            users[o].append(n)

    def up(name: str, depth: int = 0) -> Optional[str]:
        named = [own[u] for u in users.get(name, ()) if u in own]
        if named or depth > _DEPTH:
            return named[0] if named else None
        return next((got for u in users.get(name, ())
                     if (got := up(u, depth + 1))), None)

    for n in operands:
        if n not in out and (got := up(n)):
            out[n] = got

    def runs_collective(name: str, depth: int = 0) -> bool:
        if opcode.get(name, "").startswith(trace.COLLECTIVES):
            return True
        return depth < 4 and any(runs_collective(n, depth + 1)
                                 for c in sub.get(name, ()) if opcode[name]
                                 == "fusion" for n in body[c])

    return Metadata(out, frozenset(n for n in opcode if runs_collective(n)))


def compiled_text(fn, *args) -> str:
    """The compiled text of the jitted ``fn`` at ``args``, with the
    ``op_name`` metadata of this program. JAX's persistent compilation
    cache keys a program without its metadata, so the same step compiled
    earlier with other scopes (by a checkout of the program without
    them, sharing the cache) comes back from it under the same
    instruction names. Where its metadata names a program scope and the
    program does not, or the other way round, the step is compiled again
    with that cache off."""
    lowered = fn.lower(*args)
    text = lowered.compile().as_text()
    program = lowered.as_text(debug_info=True)
    if (any(map(scope_of, _LOC.findall(program)))
            == any(map(scope_of, _OP_NAME.findall(text)))):
        return text
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()      # the lowering keeps its executable in memory
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _unwrap(segment: str) -> str:
    """``transpose(jvp(stack))`` -> ``stack``."""
    m = _WRAPPED.fullmatch(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPED.fullmatch(segment)
    return segment


def scope_of(op_name: str) -> Optional[str]:
    """The innermost program scope of an ``op_name``, or None."""
    for seg in reversed(op_name.split("/")):
        seg = _unwrap(seg)
        if seg in SCOPES:
            return seg
    return None


def pass_of(op_name: str) -> str:
    """``backward`` under a transpose, ``forward`` under a JVP (the
    loss's differentiation), else ``other`` (the optimizer's epilogue)."""
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def is_remat(op_name: str) -> bool:
    """The forward recomputed in the backward."""
    return "rematted_computation" in op_name


# ---------------------------------------------------------------------------
# Reduction of a trace by scope
# ---------------------------------------------------------------------------

@dataclass
class ScopeReduction:
    """Per-chip device time of one traced window, in ns: ops of the sync
    line (``XLA Ops``) that start inside the window, containers
    (``while``) left out. Host-memory ops (S(5): the host cache's
    offload and reload, and waits for them) belong to no scope here:
    ``host_cache.*`` reads them."""
    scope: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # "<scope> <pass>" (scope "-" for none, "host" for host-memory ops)
    passes: Dict[str, Dict[str, int]] = field(default_factory=dict)
    remat: Dict[str, int] = field(default_factory=dict)
    unscoped: Dict[str, int] = field(default_factory=dict)
    # collectives under fcdp.gather1, in flight or on the core, a union
    gather1: Dict[str, int] = field(default_factory=dict)
    ops: Dict[str, int] = field(default_factory=dict)  # "<scope> <base>",
    #                                                    mean over chips

    @property
    def scoped(self) -> bool:
        """Whether any op of the window ran under a program scope."""
        return any(any(v.values()) for v in self.scope.values())


def reduce_scopes(tr: trace.Trace, md: Metadata) -> ScopeReduction:
    """Device time by innermost program scope, by scope and pass, of the
    forward recomputed in the backward, and of the ops under no scope,
    per chip; and the stage-1 gather's collectives."""
    lo, hi = tr.window()
    red = ScopeReduction()
    ops_time: Dict[str, int] = defaultdict(int)
    for chip, ops in tr.ops.items():
        scope = dict.fromkeys(SCOPES, 0)
        passes: Dict[str, int] = defaultdict(int)
        remat = unscoped = 0
        gather1 = []
        for o in ops:
            kind = trace.classify(o)
            op_name = md.op_name.get(o.name, "")
            sc = scope_of(op_name)
            if sc == "fcdp.gather1" and (kind == "collective"
                                         or o.name in md.collectives):
                gather1.append((o.start, o.end))
            if (o.on_async_line or kind == "container"
                    or not lo <= o.start < hi):
                continue
            dur = o.end - o.start
            label = "host" if kind == "host" else sc or "-"
            passes[f"{label} {pass_of(op_name)}"] += dur
            ops_time[f"{label} {trace.base_name(o.name)}"] += dur
            if kind == "host":
                continue
            if is_remat(op_name):
                remat += dur
            if sc is None:
                unscoped += dur
            else:
                scope[sc] += dur
        red.scope[chip] = scope
        red.passes[chip] = dict(passes)
        red.remat[chip] = remat
        red.unscoped[chip] = unscoped
        red.gather1[chip] = trace.length(trace.clip(trace.union(gather1),
                                                    lo, hi))
    n = max(len(tr.ops), 1)
    red.ops = {k: v // n for k, v in ops_time.items()}
    return red


def program_spans(path: str) -> List[Tuple[str, int, int]]:
    """The program's host spans of an ``.xplane.pb`` trace, by start."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events if e.name in PROGRAM_SPANS]
    return sorted(spans, key=lambda s: s[1])


# ---------------------------------------------------------------------------
# What the readers see
# ---------------------------------------------------------------------------

@dataclass
class ScopedRun:
    """The program's own view of one traced window (``base``): device
    time by scope, its host spans inside the window and its counters
    (None from a program that keeps none)."""
    base: trace.RunTrace
    red: ScopeReduction
    spans: List[Tuple[str, int, int]]   # program host spans in the window
    counters: Optional[dict]

    @property
    def steps(self) -> int:
        return self.base.steps

    def per_step_ms(self, per_chip: Dict[str, int], agg=None) -> float:
        return self.base.per_step_ms(per_chip, agg)

    def scope_ms(self, name: str) -> Optional[float]:
        """Device ms per step under scope ``name``, mean over chips; None
        where no op ran under it."""
        per_chip = {c: s[name] for c, s in self.red.scope.items()}
        if not any(per_chip.values()):
            return None
        return self.per_step_ms(per_chip)

    def span_ms(self, name: str) -> Optional[float]:
        """Mean host ms of the program span ``name`` in the window."""
        d = [e - s for n, s, e in self.spans if n == name]
        return 1e-6 * sum(d) / len(d) if d else None

    def window_lowerings(self) -> Optional[int]:
        """Program lowerings of the window's steps: the last ``steps``
        calls of ``do_train_step``."""
        per_call = (self.counters or {}).get("step_lowerings")
        if per_call is None or len(per_call) < self.steps:
            return None
        return sum(list(per_call)[len(per_call) - self.steps:])

    def table(self, top: int = 25) -> dict:
        """ms per step by scope and pass, recomputed, and the ``top``
        ops by scope (mean over chips): the breakdown to read by hand."""
        keys = sorted({k for p in self.red.passes.values() for k in p})
        ms = lambda ns: ns / 1e6 / max(self.steps, 1)  # noqa: E731
        ops = sorted(self.red.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"by_scope_and_pass_ms": {
                    k: self.per_step_ms({c: p.get(k, 0) for c, p in
                                         self.red.passes.items()})
                    for k in keys},
                "remat_ms": self.per_step_ms(self.red.remat),
                "ops_ms": {k: ms(v) for k, v in ops}}

    @classmethod
    def read(cls, tdir: str, st, base: trace.RunTrace) -> "ScopedRun":
        """From the trace in ``tdir`` and the compiled text of the run's
        train step, as ``trace.RunTrace.read`` reads them."""
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
        tr = trace.Trace.from_xplane(path)
        text = compiled_text(st.step_fn, st.train_p, st.frozen_p, st.opt,
                             st.loader.get(0))
        lo, hi = tr.window()
        return cls(base=base, red=reduce_scopes(tr, metadata(text)),
                   spans=[s for s in program_spans(path) if lo <= s[1] < hi],
                   counters=getattr(st, "counters", None))


def of(run: "trace.RunTrace") -> Optional[ScopedRun]:
    """The ScopedRun of the traced run ``run``, built on first use; None
    outside ``harness.run`` (where the program state is not found)."""
    got = getattr(run, "_scoped", None)
    if got is not None:
        return got
    frame = inspect.currentframe()
    while frame is not None:
        loc = frame.f_locals
        if loc.get("ctx") is run and "st" in loc and loc.get("tdir"):
            got = ScopedRun.read(loc["tdir"], loc["st"], run)
            run._scoped = got
            return got
        frame = frame.f_back
    return None
