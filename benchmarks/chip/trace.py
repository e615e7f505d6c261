"""Reduction of a profiler trace of the window to per-layer numbers.

The TPU profiler writes, for each chip, a plane ``/device:TPU:<n>`` with
the lines ``XLA Modules`` (one event per program run), ``XLA Ops`` (the
ops the core runs, one after another; a ``while`` op spans the ops of
its body) and ``Async XLA Ops`` (asynchronous copies and collectives,
from their start to their completion). Every op event is named by its
HLO instruction text, ``%<name> = <shape> <opcode>(...)``. The host
plane holds the benchmark's spans (``bench.input``, ``bench.step``,
``bench.loss_read``) on the same clock.

Ops are classified by what they do:

- ``host``: ops that read or write host memory (memory space ``S(5)``):
  the fcdp host cache's offload (``dynamic-update-slice``) and reload
  (``dynamic-slice``), in flight on the async line, and the core's waits
  for them (``*-done``);
- ``collective``: all-gather, reduce-scatter, all-reduce, all-to-all and
  collective-permute, in flight or as the core's part of them;
- ``matmul``: ops whose HLO holds a dot or convolution;
- ``wait``: the core waiting for any other async copy;
- ``container``: ``while``, ``conditional`` and ``call``, which span
  other ops and count for nothing of their own;
- ``compute``: every other op.

A time "exposed" is the part of a union of intervals during which no
``matmul`` or ``compute`` op runs on that chip.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

_NAME = re.compile(r"%?([\w.\-]+) = (.*?)\s([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")
CONTAINERS = ("while", "conditional", "call")
SPANS = ("bench.input", "bench.step", "bench.loss_read")


@dataclass(frozen=True)
class Op:
    name: str           # HLO instruction name
    opcode: str
    start: int          # ns
    end: int
    host: bool          # touches host memory space S(5)
    on_async_line: bool


def parse_op(text: str, start: int, dur: int, on_async_line: bool) -> Op:
    m = _NAME.match(text)
    name, opcode = (m.group(1), m.group(3)) if m else (text.lstrip("%"), "")
    return Op(name, opcode, int(start), int(start + dur), "S(5)" in text,
              on_async_line)


def base_name(name: str) -> str:
    """An instruction name without its numeric and clone suffixes."""
    return re.sub(r"(\.(\d+|clone))+$", "", name)


def classify(op: Op, matmul_names=frozenset()) -> str:
    if op.opcode in CONTAINERS:
        return "container"
    if op.host:
        return "host"
    base = base_name(op.name)
    if any(base.startswith(c) or op.opcode.startswith(c)
           for c in COLLECTIVES):
        return "collective"
    if op.opcode.endswith("-done"):
        return "wait"
    if op.name in matmul_names:
        return "matmul"
    return "compute"


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: List[Interval]) -> int:
    return sum(e - s for s, e in merged)


def clip(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def minus(a: List[Interval], b: List[Interval]) -> int:
    """Length of merged ``a`` not covered by merged ``b``."""
    covered, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return length(a) - covered


def gaps(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ---------------------------------------------------------------------------
# A trace: ops per chip and host spans
# ---------------------------------------------------------------------------

@dataclass
class Trace:
    ops: Dict[str, List[Op]]                  # chip -> ops, both lines
    modules: Dict[str, List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]]         # the benchmark's host spans

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops: Dict[str, List[Op]] = {}
        modules: Dict[str, list] = {}
        spans = []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                chip = plane.name.split("/device:", 1)[1]
                ops[chip], modules[chip] = [], []
                for line in plane.lines:
                    if line.name in ("XLA Ops", "Async XLA Ops"):
                        a = line.name == "Async XLA Ops"
                        ops[chip] += [parse_op(e.name, e.start_ns,
                                               e.duration_ns, a)
                                      for e in line.events]
                    elif line.name == "XLA Modules":
                        modules[chip] += [(e.name, int(e.start_ns),
                                           int(e.start_ns + e.duration_ns))
                                          for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                              for e in line.events if e.name in SPANS]
        return cls(ops, modules, sorted(spans, key=lambda s: s[1]))

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        """A trace kept as JSON (``to_json``), e.g. a recorded test case."""
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls({c: [Op(*o) for o in v] for c, v in d["ops"].items()},
                   {c: [tuple(m) for m in v] for c, v in d["modules"].items()},
                   [tuple(s) for s in d["spans"]])

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": {c: [dataclasses.astuple(o) for o in v]
                               for c, v in self.ops.items()},
                       "modules": self.modules, "spans": self.spans}, f)

    def window(self) -> Interval:
        """From the first span's start to the last span's end."""
        if self.spans:
            return self.spans[0][1], max(e for _, _, e in self.spans)
        ends = [(s, e) for ms in self.modules.values() for _, s, e in ms]
        return min(s for s, _ in ends), max(e for _, e in ends)


@dataclass
class Reduction:
    """Per-chip numbers of one traced window (all in ns)."""
    window: Interval
    busy: Dict[str, int] = field(default_factory=dict)
    host: Dict[str, int] = field(default_factory=dict)
    host_exposed: Dict[str, int] = field(default_factory=dict)
    collective: Dict[str, int] = field(default_factory=dict)
    collective_exposed: Dict[str, int] = field(default_factory=dict)
    op_time: Dict[str, int] = field(default_factory=dict)   # "kind base"
    idle_gaps: List[Tuple[str, int]] = field(default_factory=list)


def reduce_trace(tr: Trace, matmul_names=frozenset()) -> Reduction:
    lo, hi = tr.window()
    red = Reduction((lo, hi))
    op_time: Dict[str, int] = defaultdict(int)
    for chip, ops in tr.ops.items():
        kinds = [(classify(o, matmul_names), o) for o in ops]
        sync = [(k, o) for k, o in kinds if not o.on_async_line]
        leaf = union((o.start, o.end) for k, o in sync if k != "container")
        work = union((o.start, o.end) for k, o in sync
                     if k in ("matmul", "compute"))
        host = union((o.start, o.end) for k, o in kinds if k == "host")
        coll = union((o.start, o.end) for k, o in kinds if k == "collective")
        leaf, work = clip(leaf, lo, hi), clip(work, lo, hi)
        host, coll = clip(host, lo, hi), clip(coll, lo, hi)
        red.busy[chip] = length(leaf)
        red.host[chip] = length(union((o.start, o.end) for k, o in kinds
                                      if k == "host" and o.on_async_line
                                      and lo <= o.start < hi))
        red.host_exposed[chip] = minus(host, work)
        red.collective[chip] = length(coll)
        red.collective_exposed[chip] = minus(coll, work)
        for k, o in sync:
            if k != "container" and lo <= o.start < hi:
                op_time[f"{k} {base_name(o.name)}"] += o.end - o.start
        if chip == min(tr.ops):
            red.idle_gaps = [(_span_at(tr.spans, (s + e) // 2), e - s)
                             for s, e in gaps(leaf, lo, hi)]
    n = max(len(tr.ops), 1)
    red.op_time = {k: v // n for k, v in op_time.items()}
    return red


def _span_at(spans, t: int) -> str:
    for name, s, e in spans:
        if s <= t < e:
            return name
    return "none"


def roofline(tr: Trace, costs, module: str, peak_flops: float,
             peak_bw: float) -> Optional[dict]:
    """Matmul ops of the train-step module: sum of their least times
    (the larger of FLOPs over peak and bytes over bandwidth) over the sum
    of their measured times. None where no such op ran."""
    need = dur = flops = nbytes = 0.0
    for chip, ops in tr.ops.items():
        runs = union((s, e) for name, s, e in tr.modules.get(chip, ())
                     if name.split("(")[0] == module)
        for o in ops:
            c = costs.get(o.name)
            if o.on_async_line or c is None or not c.flops:
                continue
            if not any(s <= o.start < e for s, e in runs):
                continue
            need += max(c.flops / peak_flops, c.bytes / peak_bw) * 1e9
            dur += o.end - o.start
            flops += c.flops
            nbytes += c.bytes
    if not dur:
        return None
    return {"share": 100.0 * need / dur,
            "bound": "compute" if flops / peak_flops >= nbytes / peak_bw
            else "bytes", "flops": flops, "bytes": nbytes, "seconds": dur / 1e9}


# ---------------------------------------------------------------------------
# What the metric readers see
# ---------------------------------------------------------------------------

@dataclass
class RunTrace:
    """The traced window of one run, as the readers under ``metrics/``
    see it. Times per step are means over the chips unless a reader
    says otherwise."""
    steps: int
    red: Reduction
    input_s: List[float]
    matmul: Optional[dict]
    compiled_bytes: Optional[int]

    @property
    def window_s(self) -> float:
        return (self.red.window[1] - self.red.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(self.red.busy.values()) / max(len(self.red.busy), 1) / 1e9

    def per_step_ms(self, per_chip: Dict[str, int], agg=None) -> float:
        vals = list(per_chip.values())
        v = (agg or (lambda x: sum(x) / len(x)))(vals)
        return v / 1e6 / max(self.steps, 1)

    def breakdown(self) -> dict:
        ops = sorted(self.red.op_time.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.red.idle_gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in idle]}

    @classmethod
    def read(cls, tdir: str, st, cell, win, devices) -> "RunTrace":
        from benchmarks.chip import flops
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
        tr = Trace.from_xplane(path)
        compiled = st.step_fn.lower(st.train_p, st.frozen_p, st.opt,
                                    st.loader.get(0)).compile()
        text = compiled.as_text()
        costs = flops.hlo_op_costs(text)
        module = text.split("\n", 1)[0].split()[1].rstrip(",")
        peaks = flops.peaks(devices[0].device_kind)
        ma = compiled.memory_analysis()
        compiled_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                          - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        matmul_names = frozenset(k for k, c in costs.items() if c.flops)
        return cls(steps=win.steps, red=reduce_trace(tr, matmul_names),
                   input_s=list(win.input_s),
                   matmul=roofline(tr, costs, module, peaks["bf16_flops"],
                                   peaks["hbm_bytes_per_s"]),
                   compiled_bytes=compiled_bytes)


def remove(tdir: str) -> None:
    shutil.rmtree(tdir, ignore_errors=True)
