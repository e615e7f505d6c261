"""Byte and operation counts of compiled programs, and the table of peaks.

``hlo_op_costs`` reads a compiled program's text and gives, for each
instruction that runs as one device op, the FLOPs of the dot and
convolution instructions it holds and the bytes it reads and writes,
for the matmul roofline. The FLOPs rule of ``mfu``, counted from a
configuration's shapes, is its family's (``families/<family>.py``
``model_flops_per_token``).
"""
from __future__ import annotations

import json
import math
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, NamedTuple, Optional

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Compiled-program costs
# ---------------------------------------------------------------------------

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
          "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_SHAPE = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*[({].*")


class OpCost(NamedTuple):
    flops: float      # dot / convolution FLOPs held by the op
    bytes: float      # operands read plus result written


def _dims(s: str):
    return [int(x) for x in s.split(",") if x]


def _shapes(text: str):
    """[(dtype, dims, in_hbm)] of every array shape written in ``text``.
    An array whose layout names a memory space ``S(n)`` lives outside
    HBM (on-chip memory, or the host's for n = 5)."""
    return [(t, _dims(d), "S(" not in lay)
            for t, d, lay in _SHAPE.findall(text) if t in _BYTES]


def _nbytes(shapes) -> int:
    """HBM bytes of these arrays."""
    return sum(_BYTES[t] * math.prod(d) for t, d, hbm in shapes if hbm)


def _split_call(rhs: str):
    """``<shape> <opcode>(<operands>)<attrs>`` -> (shape, opcode,
    operands, attrs), with balanced parentheses."""
    i = 0
    if rhs.startswith("("):                      # tuple-shaped result
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.index(" ")
    shape, rest = rhs[:i], re.sub(r"/\*.*?\*/", "", rhs[i:]).lstrip()
    j = rest.index("(")
    opcode = rest[:j]
    depth = 0
    for k in range(j, len(rest)):
        depth += (rest[k] == "(") - (rest[k] == ")")
        if depth == 0:
            break
    return shape, opcode, rest[j + 1:k], rest[k + 1:]


def _operands(operands: str, shapes: Dict[str, str]):
    """Shapes of a call's operands: written inline, or looked up by name
    (compiled programs print operands as bare names)."""
    out = []
    for op in _split_top(operands):
        inline = _shapes(op)
        if inline:
            out.append(inline[0])
            continue
        name = op.strip().lstrip("%")
        got = _shapes(shapes.get(name, ""))
        out.append(got[0] if got else None)
    return out


def _split_top(text: str):
    """Split at the commas outside any brackets."""
    parts, depth, cur = [], 0, []
    for c in text:
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        if c == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(c)
    if "".join(cur).strip():
        parts.append("".join(cur))
    return parts


def _dot_flops(shape, ops, attrs) -> float:
    out = _shapes(shape)[0][1]
    lhs = ops[0][1]
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
    k = math.prod(lhs[i] for i in _dims(m.group(1))) if m else 1
    return 2.0 * math.prod(out) * k


def _window(attrs: str, n: int):
    """Per spatial dim (size, stride, pad_lo, pad_hi, lhs_dil, rhs_dil)."""
    m = re.search(r"window=\{([^}]*)\}", attrs)
    fields = dict(kv.split("=", 1) for kv in (m.group(1).split() if m else ()))

    def per_dim(key, default):
        if key not in fields:
            return [default] * n
        return [int(x) if "_" not in x else tuple(map(int, x.split("_")))
                for x in fields[key].split("x")]
    pads = per_dim("pad", (0, 0))
    return list(zip(per_dim("size", 1), per_dim("stride", 1),
                    [p[0] for p in pads], [p[1] for p in pads],
                    per_dim("lhs_dilate", 1), per_dim("rhs_dilate", 1)))


def _taps(in_size, out_size, size, stride, lo, lhs_dil, rhs_dil) -> int:
    """(output position, window tap) pairs that land on a real input
    element rather than padding or a dilation hole."""
    n = 0
    for o in range(out_size):
        for k in range(size):
            p = o * stride + k * rhs_dil - lo
            if p >= 0 and p % lhs_dil == 0 and p // lhs_dil < in_size:
                n += 1
    return n


def _conv_flops(shape, ops, attrs) -> float:
    """2 x multiply-adds of a convolution, counting only window taps on
    real input: XLA writes some dots as convolutions whose padded window
    covers one input element per output position."""
    out = _shapes(shape)[0][1]
    lhs, kernel = ops[0][1], ops[1][1]
    m = re.search(r"dim_labels=([^_]*)_([^-]*)->([^,\s]*)", attrs)
    lhs_l, ker_l, out_l = m.groups()
    spatial = sorted(c for c in lhs_l if c.isdigit())
    macs = math.prod(out[i] for i, c in enumerate(out_l) if c == "b")
    macs *= out[out_l.index("f")] * kernel[ker_l.index("i")]
    for (size, stride, lo, _, ld, rd), d in zip(_window(attrs, len(spatial)),
                                                spatial):
        macs *= _taps(lhs[lhs_l.index(d)], out[out_l.index(d)], size, stride,
                      lo, ld, rd)
    return 2.0 * macs


def hlo_op_costs(hlo_text: str) -> Dict[str, OpCost]:
    """{instruction name: OpCost} for every instruction of the program.

    A fusion's FLOPs are those of the dots and convolutions inside the
    computation it calls (nested fusions included); its bytes are its
    own operands and result. A while loop's body runs as ops of its own,
    so a loop instruction counts nothing of its body."""
    comps: Dict[str, list] = {}
    shapes: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or s.startswith("//") or s.startswith("HloModule"):
            continue
        if s == "}":
            cur = None
            continue
        if cur is None:
            m = _HEADER.match(s)
            if m and s.endswith("{"):
                cname = m.group(1)
                cur = comps.setdefault(cname, [])
            continue
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        name = lhs.replace("ROOT", "").strip().lstrip("%")
        if lhs.strip().startswith("ROOT"):
            roots[cname] = name
        try:
            shape, opcode, operands, attrs = _split_call(rhs)
        except ValueError:
            continue
        shapes[name] = shape
        cur.append((name, shape, opcode, operands, attrs))

    memo: Dict[str, float] = {}

    def comp_flops(cname: str) -> float:
        if cname not in memo:
            memo[cname] = 0.0
            memo[cname] = sum(own_flops(*ins[1:])
                              for ins in comps.get(cname, ()))
        return memo[cname]

    def own_flops(shape, opcode, operands, attrs) -> float:
        if opcode == "dot":
            return _dot_flops(shape, _operands(operands, shapes), attrs)
        if opcode == "convolution":
            return _conv_flops(shape, _operands(operands, shapes), attrs)
        if opcode == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", attrs)
            return comp_flops(m.group(1)) if m else 0.0
        return 0.0

    users: Dict[str, Dict[str, list]] = {}
    for cname, instrs in comps.items():
        u = users[cname] = defaultdict(list)
        for i in instrs:
            for k, op in enumerate(_split_top(i[3])):
                u[op.strip().lstrip("%")].append((i, k))
    params = {c: {int(re.search(r"\d+", i[3]).group()): i[0]
                  for i in instrs if i[2] == "parameter"}
              for c, instrs in comps.items()}

    def sliced_read(cname: str, name: str) -> Optional[float]:
        """Bytes read of value ``name`` of computation ``cname`` where
        every use only slices it (through bitcasts and nested fusions);
        None where some use reads it whole."""
        total = 0.0
        for i, k in users[cname][name]:
            if i[2] in ("dynamic-slice", "slice", "gather") and k == 0:
                total += _nbytes(_shapes(i[1]))
                continue
            if i[2] == "bitcast":
                got = sliced_read(cname, i[0])
            elif i[2] == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", i[4])
                inner = m.group(1) if m else ""
                p = params.get(inner, {}).get(k)
                got = sliced_read(inner, p) if p else None
            else:
                got = None
            if got is None:
                return None
            total += got
        return total

    def written(cname: str, name: str) -> float:
        i = next((x for x in comps[cname] if x[0] == name), None)
        if i is None:
            return 0.0
        if i[2] == "dynamic-update-slice":
            return _nbytes(_shapes(shapes.get(
                _split_top(i[3])[1].strip().lstrip("%"), "")))
        if i[2] == "tuple":
            return sum(written(cname, op.strip().lstrip("%"))
                       for op in _split_top(i[3]))
        return float(_nbytes(_shapes(i[1])))

    def fusion_bytes(cname: str, operands: str, shape: str) -> float:
        """What a fusion moves to and from HBM: a parameter that is only
        sliced is read as its slices; a result that updates a buffer in
        place is written as its update."""
        total = 0.0
        for k, op in enumerate(_operands(operands, shapes)):
            if op is None:
                continue
            p = params.get(cname, {}).get(k)
            got = sliced_read(cname, p) if p else None
            total += _nbytes([op]) if got is None else got
        root = roots.get(cname)
        return total + (written(cname, root) if root
                        else _nbytes(_shapes(shape)))

    out: Dict[str, OpCost] = {}
    for instrs in comps.values():
        for name, shape, opcode, operands, attrs in instrs:
            m = re.search(r"calls=%?([\w.\-]+)", attrs)
            if opcode == "fusion" and m:
                nbytes = fusion_bytes(m.group(1), operands, shape)
            else:
                ops = [o for o in _operands(operands, shapes) if o]
                nbytes = float(_nbytes(_shapes(shape)) + _nbytes(ops))
            out[name] = OpCost(own_flops(shape, opcode, operands, attrs),
                               nbytes)
    return out
