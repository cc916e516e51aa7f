"""Loop-aware cost analysis of compiled HLO text.

Why this exists (Perf iteration 0 — "fix the measurement"): XLA's
``compiled.cost_analysis()`` on the host backend reports each while-loop
*body* ONCE, but scan-over-layers executes it ``n_layers`` times (and the
SSD chunk scan nests another loop inside).  Roofline terms computed from
the raw numbers under-count every looped op by 28–61×.  This analyzer
walks the HLO call graph and multiplies loop bodies by their trip counts.

Model (mirrors the TPU execution model):

  * flops       — 2·M·N·K per ``dot`` (from the inline operand shapes and
    ``lhs_contracting_dims``), counted wherever the dot lives (fusion
    bodies included);
  * bytes       — per *top-level* op: output bytes + inline operand bytes.
    Ops inside fusion computations are NOT counted (a fusion is one kernel;
    its HBM traffic is its call-site operands + outputs — the same model
    XLA uses for TPU);
  * collectives — output bytes of all-gather / all-reduce / reduce-scatter
    / all-to-all / collective-permute, scaled by enclosing trip counts;
  * while       — trip count parsed from the loop condition's integer
    constant (scan canonical form ``ind < N``), then
    ``cost += trip × (cost(body) + cost(cond))``.

Shapes in partitioned HLO are per-device, so all results are per-device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

__all__ = ["analyze_hlo", "HloCost"]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_TOKEN = re.compile(r"\b(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([\d,]*)\]")
_OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_CALL_ATTR = re.compile(r"(?:calls|to_apply|body)=%?([\w\.\-]+)")
_COND_ATTR = re.compile(r"condition=%?([\w\.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONSTANT_INT = re.compile(r"constant\((\d+)\)")


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _shape_numel(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


@dataclasses.dataclass
class _Op:
    name: str
    rhs: str

    @property
    def opcode(self) -> str:
        # first bare word followed by '(' after the output type spec
        m = re.search(r"\)?\s*([a-z][\w\-]*)\(", self.rhs)
        return m.group(1) if m else ""

    def shapes(self):
        return _SHAPE_TOKEN.findall(self.rhs)

    def out_shape(self):
        s = self.shapes()
        return s[0] if s else None

    def operand_refs(self) -> list:
        """%name references inside the op's argument list (scheduled HLO
        omits inline operand types, so shapes come from the def-site map)."""
        m = re.search(r"[a-z][\w\-]*\(", self.rhs)
        if not m:
            return []
        start = m.end() - 1
        depth = 0
        end = start
        for i in range(start, len(self.rhs)):
            c = self.rhs[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        args = self.rhs[start:end]
        return re.findall(r"%([\w\.\-]+)", args)


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    def scaled(self, k: float) -> "HloCost":
        return HloCost(self.flops * k, self.bytes * k, self.collective_bytes * k,
                       {c: int(n * k) for c, n in self.collective_counts.items()})

    def __iadd__(self, o: "HloCost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.collective_bytes += o.collective_bytes
        for c, n in o.collective_counts.items():
            self.collective_counts[c] = self.collective_counts.get(c, 0) + n
        return self


def _parse_computations(text: str) -> Dict[str, List[_Op]]:
    comps: Dict[str, List[_Op]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        s = line.rstrip()
        if not s:
            continue
        # computation header: "%name (args...) -> type {"   (args may nest
        # parens for tuple types, so match greedily up to "-> ... {")
        m = re.match(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(.*->\s*.*\{\s*$", s)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if s.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_LINE.match(s)
        if om:
            comps[cur].append(_Op(om.group(1), om.group(2)))
    return comps


def _dot_flops(op: _Op, shape_map: Dict[str, tuple]) -> float:
    out = op.out_shape()
    if out is None:
        return 0.0
    _, out_dims = out
    refs = op.operand_refs()
    lhs_dims = None
    if len(op.shapes()) >= 2:  # inline operand type present
        lhs_dims = op.shapes()[1][1]
    elif refs and refs[0] in shape_map:
        lhs_dims = shape_map[refs[0]][1]
    if lhs_dims is None:
        return 0.0
    m = _CONTRACT.search(op.rhs)
    contraction = 1
    if m:
        lhs = [int(d) for d in lhs_dims.split(",") if d]
        for idx in m.group(1).split(","):
            if idx:
                contraction *= lhs[int(idx)]
    return 2.0 * _shape_numel(out_dims) * contraction


def _fusion_flops(comp: List[_Op], shape_map: Dict[str, tuple]) -> float:
    return sum(_dot_flops(op, shape_map) for op in comp if op.opcode == "dot")


def _fusion_bytes(call_op: _Op, comp: List[_Op],
                  shape_map: Dict[str, tuple]) -> int:
    """HBM traffic of one fused kernel, modeled the way a TPU executes it:

      * a parameter consumed ONLY through dynamic-slice/gather inside the
        fusion contributes the *sliced* bytes (scan-over-layers reads one
        layer's weights per step, not the whole (L, …) stack);
      * a fusion rooted in dynamic-update-slice writes its update region
        in place (the scan ys write-back) — the big buffer parameter is
        neither read nor rewritten;
      * everything else: full operand reads + output write.
    """
    fmap = {op.name: op.out_shape() for op in comp if op.out_shape()}
    params = {}
    for op in comp:
        if op.opcode == "parameter":
            m = re.search(r"parameter\((\d+)\)", op.rhs)
            if m:
                params[op.name] = int(m.group(1))

    root = comp[-1] if comp else None
    dus_buffer = dus_update = None
    if root is not None and root.opcode == "dynamic-update-slice":
        refs = root.operand_refs()
        if len(refs) >= 2:
            dus_buffer, dus_update = refs[0], refs[1]

    sliced: Dict[str, int] = {}
    full: set = set()
    for op in comp:
        code = op.opcode
        if code in ("parameter", "constant"):
            continue
        if code in ("dynamic-slice", "gather"):
            refs = op.operand_refs()
            if refs:
                out = op.out_shape()
                sliced[refs[0]] = sliced.get(refs[0], 0) + (
                    _shape_bytes(*out) if out else 0)
            continue
        if op is root and dus_buffer is not None:
            continue  # handled below
        for r in op.operand_refs():
            full.add(r)
    if dus_update is not None:
        full.add(dus_update)

    total = 0
    for pname in params:
        if pname == dus_buffer:
            continue  # in-place: untouched region costs nothing
        if pname in full:
            sh = fmap.get(pname)
            total += _shape_bytes(*sh) if sh else 0
        elif pname in sliced:
            total += sliced[pname]

    out = call_op.out_shape()
    out_b = _shape_bytes(*out) if out else 0
    if dus_update is not None:
        upd_sh = fmap.get(dus_update)
        if upd_sh is not None:
            out_b = _shape_bytes(*upd_sh)  # write the update region only
    return total + out_b


def _trip_count(cond_ops: List[_Op]) -> int:
    best = 1
    for op in cond_ops:
        for m in _CONSTANT_INT.finditer(op.rhs):
            best = max(best, int(m.group(1)))
    return best


_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "after-all", "partition-id"}


_SLICE_READS_OUTPUT_ONLY = {"dynamic-slice", "gather"}
_UPDATE_OPS = {"dynamic-update-slice", "scatter"}


def _op_bytes(op: _Op, shape_map: Dict[str, tuple]) -> int:
    """HBM traffic model per op: output bytes + operand bytes (def-site
    shapes).  Slice/gather ops read only the sliced region (≈ output), and
    update ops touch ~2× the update region (read+write) — charging the full
    operand would bill a 32-layer stacked weight tensor on every per-layer
    dynamic-slice, 32× over (found while hillclimbing llama decode;
    EXPERIMENTS.md §Perf iteration A4)."""
    out = op.out_shape()
    out_b = _shape_bytes(*out) if out else 0
    code = op.opcode
    if code in _SLICE_READS_OUTPUT_ONLY:
        return 2 * out_b  # read region + write output
    if code in _UPDATE_OPS:
        # update tensor: operand 1 for dynamic-update-slice, operand 2 for
        # scatter (positional HLO convention); fall back to output size
        refs = op.operand_refs()
        pos = 1 if code == "dynamic-update-slice" else 2
        upd_b = out_b
        if len(refs) > pos and refs[pos] in shape_map:
            upd_b = _shape_bytes(*shape_map[refs[pos]])
        return 3 * min(upd_b, out_b)  # read + write region + indices slack
    b = out_b
    for ref in op.operand_refs():
        sh = shape_map.get(ref)
        if sh is not None:
            b += _shape_bytes(*sh)
    return b


def _cost_of(comp_name: str, comps: Dict[str, List[_Op]],
             shape_map: Dict[str, tuple], memo: Dict[str, HloCost]) -> HloCost:
    if comp_name in memo:
        return memo[comp_name]
    memo[comp_name] = HloCost()  # cycle guard
    total = HloCost()
    for op in comps.get(comp_name, []):
        code = op.opcode
        out = op.out_shape()
        out_b = _shape_bytes(*out) if out else 0

        if code == "while":
            body = _CALL_ATTR.search(op.rhs)
            cond = _COND_ATTR.search(op.rhs)
            trip = _trip_count(comps.get(cond.group(1), [])) if cond else 1
            inner = HloCost()
            if body:
                inner += _cost_of(body.group(1), comps, shape_map, memo)
            if cond:
                inner += _cost_of(cond.group(1), comps, shape_map, memo)
            total += inner.scaled(trip)
            continue

        if code == "fusion":
            called = _CALL_ATTR.search(op.rhs)
            if called:
                fcomp = comps.get(called.group(1), [])
                total.flops += _fusion_flops(fcomp, shape_map)
                total.bytes += _fusion_bytes(op, fcomp, shape_map)
            else:
                total.bytes += _op_bytes(op, shape_map)
            continue

        if code in ("call", "custom-call", "conditional"):
            called = _CALL_ATTR.search(op.rhs)
            if called:
                total += _cost_of(called.group(1), comps, shape_map, memo)
            total.bytes += _op_bytes(op, shape_map)
            continue

        if code in _COLLECTIVES:
            total.collective_bytes += out_b
            total.collective_counts[code] = total.collective_counts.get(code, 0) + 1
            total.bytes += _op_bytes(op, shape_map)
            continue

        if code == "dot":
            total.flops += _dot_flops(op, shape_map)
            total.bytes += _op_bytes(op, shape_map)
            continue

        if code in _SKIP_BYTES or not code:
            continue
        total.bytes += _op_bytes(op, shape_map)

    memo[comp_name] = total
    return total


def analyze_hlo(text: str) -> HloCost:
    comps = _parse_computations(text)
    # module-wide def-site shape map (scheduled HLO omits operand types)
    shape_map: Dict[str, tuple] = {}
    for ops in comps.values():
        for op in ops:
            out = op.out_shape()
            if out is not None:
                shape_map[op.name] = out
    entry = None
    m = re.search(r"ENTRY\s+%?([\w\.\-]+)", text)
    if m:
        entry = m.group(1)
    else:
        for name in comps:
            if name.startswith("main"):
                entry = name
    if entry is None:
        raise ValueError("no ENTRY computation found")
    memo: Dict[str, HloCost] = {}
    return _cost_of(entry, comps, shape_map, memo)
