"""The trace reduction, on a trace recorded on a v5e chip and trimmed to
one step boundary (``testdata/``), and on small cases made by hand."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import trace

DATA = Path(__file__).resolve().parent / "testdata"


def sweep(intervals_by_kind, lo, hi):
    """Time in [lo, hi) by which kinds are active, by a sweep over the
    interval ends (a second way to the numbers the reduction gives)."""
    events = []
    for kind, ivs in intervals_by_kind.items():
        for s, e in ivs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                events += [(s, 1, kind), (e, -1, kind)]
    events.sort()
    active = {k: 0 for k in intervals_by_kind}
    out, t = {}, lo
    for x, d, kind in events:
        key = frozenset(k for k, n in active.items() if n > 0)
        out[key] = out.get(key, 0) + x - t
        active[kind] += d
        t = x
    key = frozenset(k for k, n in active.items() if n > 0)
    out[key] = out.get(key, 0) + hi - t
    return out


def test_interval_arithmetic_by_hand():
    a = trace.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert a == [(0, 20), (30, 45)]
    assert trace.length(a) == 35
    b = trace.union([(15, 35), (44, 60)])
    assert trace.minus(a, b) == (15 - 0) + (44 - 35)
    assert trace.gaps(a, -5, 50) == [(-5, 0), (20, 30), (45, 50)]
    assert trace.clip(a, 10, 35) == [(10, 20), (30, 35)]


HOST = ("%dynamic-slice-start.8 = ((bf16[36,2048,11008]{2,1,0:T(8,128)(2,1)"
        "S(5)}, s32[]), bf16[1,2048,11008]{2,1,0}, u32[]{:S(2)}) "
        "async-start(%get-tuple-element.7064), calls=%async_computation.19")


@pytest.mark.parametrize("text,on_async,kind", [
    (HOST, True, "host"),
    ("%dynamic-update-slice-done = bf16[36,2048,256]{1,2,0:S(5)} "
     "async-done(%dynamic-update-slice-start)", False, "host"),
    ("%while.3 = (s32[], bf16[36,2048,8]{1,2,0:S(5)}) while(%tuple.1), "
     "condition=%cond, body=%body", False, "container"),
    ("%all-gather.12 = bf16[4096,12800]{1,0} all-gather(%p), "
     "dimensions={0}", False, "collective"),
    ("%all-reduce-scatter.2 = bf16[1024,4096]{1,0} fusion(%a), "
     "kind=kOutput, calls=%f", False, "collective"),
    ("%collective-permute-done.1 = bf16[8]{0} "
     "collective-permute-done(%collective-permute-start.1)", False,
     "collective"),
    ("%copy-done.125 = f32[64]{0:S(1)} copy-done(%copy-start.125)", False,
     "wait"),
    ("%convolution_multiply_fusion.39 = bf16[4096,11008]{1,0} fusion(%a, "
     "%b), kind=kOutput, calls=%fused", False, "matmul"),
    ("%add_rsqrt_fusion.7 = f32[4096]{0} fusion(%a), kind=kLoop, "
     "calls=%fused", False, "compute"),
])
def test_classify(text, on_async, kind):
    op = trace.parse_op(text, 100, 50, on_async)
    assert trace.classify(op, {"convolution_multiply_fusion.39"}) == kind


@pytest.fixture(scope="module", params=["trace_qwen_1chip"])
def recorded(request):
    meta = json.loads((DATA / f"{request.param}.meta.json").read_text())
    tr = trace.Trace.from_json(str(DATA / f"{request.param}.json.gz"))
    return tr, meta


def test_recorded_trace_busy_exposed_and_kinds(recorded):
    tr, meta = recorded
    names = frozenset(meta["matmul_names"])
    red = trace.reduce_trace(tr, names)
    lo, hi = red.window
    assert (lo, hi) == tr.window()
    for chip, ops in tr.ops.items():
        kinds = {}
        for o in ops:
            kinds.setdefault(trace.classify(o, names), []).append(o)
        # the recorded window holds every kind the reduction separates
        assert {"host", "matmul", "compute", "container"} <= set(kinds)
        sync = {k: [(o.start, o.end) for o in v if not o.on_async_line]
                for k, v in kinds.items()}
        by = sweep({"busy": [iv for k, v in sync.items()
                             if k != "container" for iv in v]}, lo, hi)
        assert red.busy[chip] == by[frozenset({"busy"})]
        work = sync.get("matmul", []) + sync.get("compute", [])
        host = [(o.start, o.end) for o in kinds["host"]]
        by = sweep({"host": host, "work": work}, lo, hi)
        assert red.host_exposed[chip] == by.get(frozenset({"host"}), 0)
        assert 0 < red.host_exposed[chip] < red.host[chip] <= hi - lo
        idle = sum(g for _, g in red.idle_gaps)
        if chip == min(tr.ops):
            assert idle == (hi - lo) - red.busy[chip]
            assert {lbl for lbl, _ in red.idle_gaps} <= set(trace.SPANS) | {
                "none"}


def test_roofline_share_of_the_recorded_matmuls(recorded):
    tr, meta = recorded
    from benchmarks.chip.flops import OpCost
    costs = {n: OpCost(*c) for n, c in meta["costs"].items()}
    got = trace.roofline(tr, costs, meta["module"], 197e12, 819e9)
    assert got is not None and 0 < got["share"] <= 100
    assert got["bound"] in ("compute", "bytes")
    assert trace.roofline(tr, costs, "no_such_module", 197e12, 819e9) is None
