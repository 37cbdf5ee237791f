"""Compute-plane observability: per-op roofline attribution, collective
traffic accounting, and recompile forensics.

PR 10's critical path proved the flagship round is 99.9% device-wait —
and that is where the host-side instruments stop. This module looks
INSIDE the compiled program: after a jitted engine/serving program
compiles, it walks the optimized HLO (plus ``compiled.cost_analysis()``
/ ``memory_analysis()`` as cross-checks) and emits, per op:

* operand/output shapes and analytical FLOPs + bytes accessed,
* arithmetic intensity and a compute- vs memory-bound classification
  against the per-device-kind peaks table
  (:data:`profiler.DEVICE_PEAKS`: bf16 FLOP/s and HBM bandwidth),
* a roofline-predicted execution time (``max(flops/peak, bytes/bw)``)
  and its share of the program's predicted device time, plus a
  predicted whole-program MFU,

as a schema-validated ``kind: roofline`` JSONL record and registry
gauges. Fusions are the attribution unit (their internals never touch
memory — boundary bytes, summed inner FLOPs); ``while`` bodies are
multiplied by XLA's ``known_trip_count`` (falling back to the loop
condition's comparison constant), so a scanned conv stream attributes
its true repeated cost. Collectives (all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute) get a wire-byte
estimate per execution from the standard ring-algorithm factors and the
parsed replica groups — the accounting the multi-chip weak-scaling
bench reads.

On a CPU mesh there is no HBM: the machine-balance entry is a nominal
host value and every prediction is STATIC-ONLY — shapes, FLOPs, bytes,
intensities and collective bytes are exact, the time/MFU columns are a
model, not a measurement. The record says so (``static_only: true``)
and the capture logs it loudly once.

Capture is OPT-IN (``obs_roofline: true``): it AOT-lowers and compiles
the dispatched program once per (name, abstract-shape signature), which
is an extra backend compile the compile-once tests would otherwise
trip on. Recompile FORENSICS, by contrast, is always on and free: every
dispatch records its abstract arg signature (shapes/dtypes, never
values), and when the compile counter increments past the pinned
expectation — one compile per program — the changed leaves are emitted
as a ``kind: recompile`` record, so a compile-once regression names the
shape that moved instead of failing a bare counter assertion.

``scripts/roofline_report.py`` renders the records: top-N ops by
predicted time, per-operand-shape aggregation of the conv stream,
bound-class split, collective-bytes table, ``--compare`` across runs or
device counts, and a ``--min-attr`` coverage gate.
"""

from __future__ import annotations

import collections
import logging
import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import metrics as obs_metrics
from . import profiler as obs_profiler

logger = logging.getLogger(__name__)

# machine balance: peak FLOP/s and HBM bandwidth both come from the one
# table, ``profiler.DEVICE_PEAKS``, keyed by exact ``device_kind``;
# together they give the balance (flops/byte) every op's arithmetic
# intensity classifies against.

_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
          "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
          "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
          "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1}

_cfg = {"default_enabled": False, "max_ops": 64}


def set_default_enabled(on: bool) -> None:
    """Process default for the ``obs_roofline`` knob (``configure``);
    engines read their own args first and fall back to this."""
    _cfg["default_enabled"] = bool(on)


def default_enabled() -> bool:
    return _cfg["default_enabled"]


@dataclass
class MachineBalance:
    device_kind: str
    peak_tflops: Optional[float]
    hbm_gbps: Optional[float]
    static_only: bool

    @property
    def flops_per_byte(self) -> Optional[float]:
        if not self.peak_tflops or not self.hbm_gbps:
            return None
        return (self.peak_tflops * 1e12) / (self.hbm_gbps * 1e9)


_static_warned = [False]


def machine_balance(device=None) -> MachineBalance:
    """Peak FLOP/s + HBM bandwidth for a jax device. A CPU (or unknown)
    kind degrades LOUDLY to static-only predictions — the table's host
    entry keeps the ranking meaningful, but time/MFU columns are a
    model, and the record carries ``static_only: true``."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = str(device.device_kind)
    peak = obs_profiler.peak_tflops(device)
    bw = obs_profiler.hbm_gbps(device)
    static = kind == "cpu" or peak is None or bw is None
    if static and not _static_warned[0]:
        _static_warned[0] = True
        logger.warning(
            "roofline: device kind %r has no measured machine balance — "
            "predictions are STATIC-ONLY (shapes/FLOPs/bytes exact, "
            "time/MFU a model); re-capture on TPU for real numbers", kind)
    return MachineBalance(kind, peak, bw, static)


# ---------------------------------------------------------------------------
# optimized-HLO text parser. The compiled module is the per-device SPMD
# program; computations arrive as named blocks, entry last. We keep it
# deliberately tolerant: an unparseable line is skipped and surfaces in
# the record's attribution share instead of crashing a capture.

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\s*\{")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(\(.*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][a-z0-9\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)')
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_METADATA_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALL_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _shape_bytes(shapes: Sequence[Tuple[str, Tuple[int, ...]]]) -> float:
    total = 0.0
    for dt, dims in shapes:
        total += _BYTES.get(dt, 4) * float(np_prod(dims))
    return total


def np_prod(dims: Sequence[int]) -> int:
    p = 1
    for d in dims:
        p *= int(d)
    return p


@dataclass
class HloOp:
    name: str
    opcode: str
    out_shapes: List[Tuple[str, Tuple[int, ...]]]
    operand_shapes: List[Tuple[str, Tuple[int, ...]]]
    attrs: str
    operand_text: str = ""
    op_name: str = ""
    calls: List[str] = field(default_factory=list)
    cond: Optional[str] = None
    trip_count: Optional[int] = None


def _split_operands(line: str, start: int) -> Tuple[str, str]:
    """Split ``opcode(OPERANDS), ATTRS`` at the top-level closing paren.
    Returns (operand_text, attrs_text)."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i], line[i + 1:]
    return line[start + 1:], ""


def _resolve_operand_shapes(ops: List[HloOp]) -> None:
    """XLA prints an operand as its shape and name (``f32[8,16]{1,0} %a``)
    or, since the jax 0.9 line, as the name alone (``%a``). Where no
    inline shape was printed, look the names up in the computation's own
    instruction table — every operand is an instruction (or parameter)
    of the same computation."""
    table = {op.name: op.out_shapes for op in ops}
    for op in ops:
        if op.operand_shapes:
            continue
        for name in _OPERAND_NAME_RE.findall(op.operand_text):
            op.operand_shapes.extend(table.get(name, ()))


def parse_hlo(text: str) -> Tuple[Dict[str, List[HloOp]], Optional[str]]:
    """Parse optimized HLO text into ``{computation: [HloOp]}`` plus the
    entry computation's name. Tolerant: unmatched lines are skipped."""
    comps: Dict[str, List[HloOp]] = {}
    entry: Optional[str] = None
    cur: Optional[List[HloOp]] = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            name = m.group(2)
            cur = comps.setdefault(name, [])
            if m.group(1):
                entry = name
            continue
        if line.startswith("}"):
            if cur is not None:
                _resolve_operand_shapes(cur)
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_RE.match(line)
        if om is None:
            continue
        opname, shape_text, opcode = om.group(1), om.group(2), om.group(3)
        operands, attrs = _split_operands(line, om.end() - 1)
        op = HloOp(
            name=opname, opcode=opcode,
            out_shapes=_parse_shapes(shape_text),
            operand_shapes=_parse_shapes(operands),
            attrs=attrs, operand_text=operands)
        mm = _METADATA_RE.search(attrs)
        if mm:
            op.op_name = mm.group(1)
        if opcode in ("fusion", "call", "while", "reduce", "sort", "map",
                      "scatter", "reduce-window", "conditional",
                      "select-and-scatter", "all-reduce", "reduce-scatter"):
            op.calls = _CALL_RE.findall(attrs)
            cm = _COND_RE.search(attrs)
            if cm:
                op.cond = cm.group(1)
        if opcode == "while":
            tm = _TRIP_RE.search(attrs)
            if tm:
                op.trip_count = int(tm.group(1))
        cur.append(op)
    return comps, entry


def _cond_trip_count(comps: Dict[str, List[HloOp]],
                     cond: Optional[str]) -> Optional[int]:
    """Fallback trip count when ``known_trip_count`` is absent: the
    canonical counted-loop condition is a single scalar
    ``compare(counter, constant N), direction=LT`` — read N. Only
    trusted when the condition has exactly one integer constant."""
    if not cond or cond not in comps:
        return None
    has_lt = any(op.opcode == "compare" and "direction=LT" in op.attrs
                 for op in comps[cond])
    if not has_lt:
        return None
    consts = []
    for op in comps[cond]:
        if op.opcode == "constant" and op.out_shapes \
                and op.out_shapes[0][0].startswith(("s", "u")):
            m = re.fullmatch(r"\s*(\d+)\s*", op.operand_text)
            if m:
                consts.append(int(m.group(1)))
    return consts[0] if len(consts) == 1 else None


# --- analytical per-op cost model ------------------------------------------

# elementwise opcodes: 1 flop per output element (transcendentals are a
# handful of hardware ops but roofline-wise they stay bandwidth-bound at
# these intensities; precision here buys nothing)
_ELEMENTWISE = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "compare", "select", "and", "or", "xor", "not", "exp",
    "expm1", "log", "log1p", "tanh", "sqrt", "rsqrt", "cbrt", "power",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "sign",
    "cosine", "sine", "tan", "atan2", "is-finite", "clamp", "remainder",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "popcnt", "clz", "erf", "logistic", "stochastic-convert",
))

# pure data movement: 0 flops, bytes from shapes
_MOVEMENT = frozenset((
    "copy", "transpose", "reshape", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "gather", "scatter",
    "reverse", "convert", "bitcast-convert", "iota", "rng-bit-generator",
    "rng", "copy-start", "copy-done",
))

# free at runtime (no materialized traffic of their own). The async
# collectives' "-done" halves are free too: their cost was charged to
# the "-start" op — charging both would double-count every TPU
# collective and deflate attributed_share on the platform that matters.
_FREE = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
    "all-reduce-done", "all-gather-done", "reduce-scatter-done",
    "all-to-all-done", "collective-permute-done", "async-done",
))

COLLECTIVE_OPCODES = frozenset((
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "all-reduce-start",
    "all-gather-start", "reduce-scatter-start", "all-to-all-start",
    "collective-permute-start",
))


def _out_elems(op: HloOp) -> float:
    return float(sum(np_prod(d) for _, d in op.out_shapes)) or 0.0


def _dot_flops(op: HloOp) -> Optional[float]:
    if len(op.operand_shapes) < 1:
        return None
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    if not m:
        return None
    lhs = op.operand_shapes[0][1]
    contracting = [int(i) for i in m.group(1).split(",") if i]
    k = np_prod([lhs[i] for i in contracting if i < len(lhs)])
    return 2.0 * _out_elems(op) * float(k)


def _conv_flops(op: HloOp) -> Optional[float]:
    m = re.search(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)", op.attrs)
    if not m or len(op.operand_shapes) < 2:
        return None
    kern_labels = m.group(2)
    kern = op.operand_shapes[1][1]
    if len(kern_labels) != len(kern):
        return None
    spatial = 1
    in_feat = 1
    for lab, dim in zip(kern_labels, kern):
        if lab == "i":
            in_feat = dim
        elif lab != "o":
            spatial *= dim
    return 2.0 * _out_elems(op) * float(spatial) * float(in_feat)


def _comp_flops(comps: Dict[str, List[HloOp]], name: str,
                memo: Dict[str, float]) -> float:
    """Total analytical FLOPs of one computation, descending through
    fusions/calls (while bodies inside a fusion are impossible; while at
    computation level is handled by the attribution walk)."""
    if name in memo:
        return memo[name]
    memo[name] = 0.0  # cycle guard
    total = 0.0
    for op in comps.get(name, ()):
        fl, _known = _op_flops(op, comps, memo)
        total += fl or 0.0
    memo[name] = total
    return total


def _op_flops(op: HloOp, comps: Dict[str, List[HloOp]],
              memo: Dict[str, float]) -> Tuple[Optional[float], bool]:
    """(flops, known) for ONE op. ``known=False`` marks an opcode the
    model has no formula for (custom-call): bytes-only attribution."""
    oc = op.opcode
    if oc in _FREE or oc in _MOVEMENT:
        return 0.0, True
    if oc in _ELEMENTWISE:
        return _out_elems(op), True
    if oc == "dot":
        fl = _dot_flops(op)
        return (fl, True) if fl is not None else (0.0, False)
    if oc == "convolution":
        fl = _conv_flops(op)
        return (fl, True) if fl is not None else (0.0, False)
    if oc in ("fusion", "call", "map"):
        return sum(_comp_flops(comps, c, memo) for c in op.calls), True
    if oc in ("reduce", "reduce-window", "select-and-scatter"):
        return float(sum(np_prod(d) for _, d in op.operand_shapes)), True
    if oc == "sort":
        n = _out_elems(op)
        return n * max(math.log2(max(n, 2.0)), 1.0), True
    if oc in COLLECTIVE_OPCODES:
        # the reduction adds; wire time is modeled separately
        return _out_elems(op), True
    if oc == "custom-call":
        return 0.0, False
    # unknown opcode: elementwise-ish guess, flagged
    return _out_elems(op), False


# ops that read only a window of their (possibly huge) first operand —
# charging the full operand would let a per-slot dynamic-slice of the
# whole client-data array dwarf the conv stream it feeds
_WINDOW_READS = frozenset(("slice", "dynamic-slice", "gather"))
# ops that write only the update region of an aliased buffer
_WINDOW_WRITES = frozenset(("dynamic-update-slice", "scatter"))


def _op_bytes(op: HloOp) -> float:
    """Boundary memory traffic: operands read + outputs written. For a
    fusion this is exactly the roofline-correct figure — fused
    intermediates never touch memory. Window ops (slice / gather /
    dynamic-update-slice) are charged the window, not the buffer."""
    if op.opcode in _WINDOW_READS:
        return 2.0 * _shape_bytes(op.out_shapes)
    if op.opcode in _WINDOW_WRITES and len(op.operand_shapes) >= 2:
        return 2.0 * _shape_bytes(op.operand_shapes[1:2])
    return _shape_bytes(op.operand_shapes) + _shape_bytes(op.out_shapes)


def _fusion_bytes(comps: Dict[str, List[HloOp]], op: HloOp) -> float:
    """A fusion's traffic is its boundary — EXCEPT parameters consumed
    only through window reads (a fused ``dynamic-slice`` of the stacked
    client data reads one slice per iteration, not the stack). Charge
    those parameters their windows."""
    body = comps.get(op.calls[0]) if op.calls else None
    if not body:
        return _op_bytes(op)
    total = _shape_bytes(op.out_shapes)
    windowed: Dict[str, float] = {}
    for inner in body:
        if inner.opcode != "parameter":
            continue
        consumers = [o for o in body
                     if re.search(r"%" + re.escape(inner.name) + r"\b",
                                  o.operand_text)]
        if consumers and all(o.opcode in _WINDOW_READS
                             for o in consumers):
            windowed[inner.name] = sum(
                _shape_bytes(o.out_shapes) for o in consumers)
    # parameters line up with the fusion's operands by index; the ones
    # we re-priced subtract their full size and add their window
    params = [o for o in body if o.opcode == "parameter"]
    for p in params:
        size = _shape_bytes(p.out_shapes)
        total += windowed.get(p.name, size)
    return total


def _group_size(op: HloOp, n_devices: int) -> int:
    m = _GROUPS_RE.search(op.attrs)
    if m:
        return max(len([x for x in m.group(1).split(",") if x]), 1)
    return max(int(n_devices), 1)


def _collective_wire_bytes(op: HloOp, n_devices: int) -> Tuple[int, float]:
    """(group_size, per-device wire bytes) for one execution, from the
    standard ring-algorithm factors. Payload = operand bytes (result
    bytes for all-gather, whose output is the concatenation)."""
    g = _group_size(op, n_devices)
    oc = op.opcode.replace("-start", "")
    if oc == "all-gather":
        # the concatenated result; the async "-start" form's output is a
        # (operand, result) tuple, so take the LARGEST output shape, not
        # the sum, or wire bytes inflate by payload/g
        payload = max((_shape_bytes([s]) for s in op.out_shapes),
                      default=0.0)
    else:
        payload = _shape_bytes(op.operand_shapes)
    if g <= 1:
        return g, 0.0
    frac = (g - 1) / g
    if oc == "all-reduce":
        return g, 2.0 * frac * payload
    if oc in ("all-gather", "reduce-scatter", "all-to-all"):
        return g, frac * payload
    if oc in ("collective-permute", "collective-broadcast"):
        return g, payload
    return g, frac * payload


# ---------------------------------------------------------------------------
# attribution walk


@dataclass
class OpRow:
    name: str
    opcode: str
    op_name: str
    out: str
    operands: List[str]
    flops: float
    bytes: float
    mult: int
    known: bool
    loop_estimated: bool
    group: int = 0           # collective group size (0 = not one)
    wire_bytes: float = 0.0  # collective per-device wire bytes

    def shape_key(self) -> str:
        return f"{self.opcode}({','.join(self.operands)})->{self.out}"


def _fmt_shape(s: Tuple[str, Tuple[int, ...]]) -> str:
    dt, dims = s
    return f"{dt}[{','.join(str(d) for d in dims)}]"


def attribute(comps: Dict[str, List[HloOp]], entry: str,
              n_devices: int = 1) -> List[OpRow]:
    """Flatten the entry computation into costed leaf rows: fusions are
    one row each (boundary bytes, summed inner FLOPs), while bodies are
    multiplied by their trip count, free ops dropped."""
    memo: Dict[str, float] = {}
    rows: List[OpRow] = []

    def walk(comp: str, mult: int, loop_est: bool) -> None:
        for op in comps.get(comp, ()):
            oc = op.opcode
            if oc in _FREE:
                continue
            if oc == "while":
                trip = op.trip_count
                est = False
                if trip is None:
                    trip = _cond_trip_count(comps, op.cond)
                if trip is None:
                    trip, est = 1, True
                for body in op.calls:
                    walk(body, mult * max(trip, 1), loop_est or est)
                continue
            if oc == "conditional":
                # branch cost is data-dependent; attribute the branches
                # once (upper-bound-ish, rare in our programs)
                for body in op.calls:
                    walk(body, mult, True)
                continue
            if oc == "call":
                for body in op.calls:
                    walk(body, mult, loop_est)
                continue
            flops, known = _op_flops(op, comps, memo)
            nbytes = (_fusion_bytes(comps, op) if oc == "fusion"
                      else _op_bytes(op))
            if not flops and not nbytes:
                continue
            row = OpRow(
                name=op.name, opcode=oc, op_name=op.op_name,
                out=",".join(_fmt_shape(s) for s in op.out_shapes[:2]),
                operands=[_fmt_shape(s) for s in op.operand_shapes[:4]],
                flops=float(flops or 0.0), bytes=float(nbytes),
                mult=int(mult), known=bool(known),
                loop_estimated=bool(loop_est))
            if oc in COLLECTIVE_OPCODES:
                row.group, row.wire_bytes = _collective_wire_bytes(
                    op, n_devices)
            rows.append(row)

    walk(entry, 1, False)
    return rows


# ---------------------------------------------------------------------------
# analysis → record


def _xla_totals(compiled) -> Tuple[Optional[float], Optional[float]]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return None, None
        fl = ca.get("flops")
        by = ca.get("bytes accessed")
        return (float(fl) if fl is not None else None,
                float(by) if by is not None else None)
    except Exception:
        return None, None


def analyze_compiled(program: str, compiled, *, device=None,
                     n_devices: int = 1,
                     max_ops: Optional[int] = None) -> Dict[str, Any]:
    """Walk one compiled program into the ``kind: roofline`` record
    payload. Never raises on a parse gap — unattributed cost shows up in
    ``attributed_share`` instead."""
    bal = machine_balance(device)
    text = compiled.as_text()
    comps, entry = parse_hlo(text)
    rows = attribute(comps, entry, n_devices) if entry else []

    peak_fs = (bal.peak_tflops or 0.0) * 1e12
    bw_bs = (bal.hbm_gbps or 0.0) * 1e9

    def row_time(r: OpRow) -> float:
        t_c = (r.flops * r.mult / peak_fs) if peak_fs else 0.0
        t_m = (r.bytes * r.mult / bw_bs) if bw_bs else 0.0
        return max(t_c, t_m)

    total_flops = sum(r.flops * r.mult for r in rows)
    total_bytes = sum(r.bytes * r.mult for r in rows)
    times = [row_time(r) for r in rows]
    predicted_s = sum(times)
    mem_t = comp_t = unknown_t = 0.0
    balance = bal.flops_per_byte
    op_rows: List[Dict[str, Any]] = []
    for r, t in zip(rows, times):
        intensity = (r.flops / r.bytes) if r.bytes else None
        if not r.known:
            cls = "unknown"
            unknown_t += t
        elif balance is None or intensity is None:
            cls = "memory"
            mem_t += t
        elif intensity >= balance:
            cls = "compute"
            comp_t += t
        else:
            cls = "memory"
            mem_t += t
        op_rows.append({
            "name": r.name, "op": r.opcode, "op_name": r.op_name,
            "out": r.out, "operands": r.operands,
            "flops": r.flops * r.mult, "bytes": r.bytes * r.mult,
            "mult": r.mult,
            "intensity": (round(intensity, 4) if intensity is not None
                          else None),
            "bound": cls,
            "time_s": t,
            "share": (t / predicted_s) if predicted_s else 0.0,
            "estimated": bool(r.loop_estimated or not r.known),
        })
    op_rows.sort(key=lambda d: d["time_s"], reverse=True)
    cap = _cfg["max_ops"] if max_ops is None else int(max_ops)
    if cap and len(op_rows) > cap:
        rest = op_rows[cap:]
        op_rows = op_rows[:cap]
        op_rows.append({
            "name": "(other)", "op": "(other)", "op_name": "",
            "out": "", "operands": [],
            "flops": sum(d["flops"] for d in rest),
            "bytes": sum(d["bytes"] for d in rest), "mult": 1,
            "intensity": None, "bound": "mixed",
            "time_s": sum(d["time_s"] for d in rest),
            "share": sum(d["share"] for d in rest),
            "estimated": False,
        })

    colls: Dict[Tuple[str, str, int], Dict[str, Any]] = {}
    for r in rows:
        if not r.group:
            continue
        key = (r.opcode, ",".join(r.operands), r.group)
        ent = colls.setdefault(key, {
            "op": r.opcode.replace("-start", ""),
            "operands": r.operands, "group": r.group,
            "count": 0, "payload_bytes": 0.0, "wire_bytes": 0.0})
        ent["count"] += r.mult
        ent["payload_bytes"] += _collective_payload(r)
        ent["wire_bytes"] += r.wire_bytes * r.mult
    coll_rows = sorted(colls.values(), key=lambda d: d["wire_bytes"],
                       reverse=True)
    coll_total = sum(d["wire_bytes"] for d in coll_rows)

    xla_flops, xla_bytes = _xla_totals(compiled)
    mem_stats = _memory_stats(compiled)
    # computed even static-only: a useful ranking number, and the record
    # carries the static_only flag that labels it as a model
    predicted_mfu = None
    if peak_fs and predicted_s:
        predicted_mfu = total_flops / predicted_s / peak_fs
    attributed = 1.0 - (unknown_t / predicted_s if predicted_s else 0.0)
    rec: Dict[str, Any] = {
        "program": str(program),
        "device_kind": bal.device_kind,
        "n_devices": int(n_devices),
        "static_only": bool(bal.static_only),
        "peak_tflops": bal.peak_tflops,
        "hbm_gbps": bal.hbm_gbps,
        "balance_flops_per_byte": (round(balance, 2)
                                   if balance is not None else None),
        "total_flops": float(total_flops),
        "total_bytes": float(total_bytes),
        "predicted_s": float(predicted_s),
        "predicted_mfu": (round(predicted_mfu, 5)
                          if predicted_mfu is not None else None),
        "attributed_share": round(attributed, 5),
        "memory_bound_share": round(mem_t / predicted_s, 5)
        if predicted_s else 0.0,
        "compute_bound_share": round(comp_t / predicted_s, 5)
        if predicted_s else 0.0,
        "collective_wire_bytes": float(coll_total),
        "xla_flops": xla_flops,
        "xla_bytes": xla_bytes,
        "ops": op_rows,
        "collectives": coll_rows,
    }
    if mem_stats:
        rec.update(mem_stats)
    return rec


def _collective_payload(r: OpRow) -> float:
    # payload per execution × loop multiplier. The row's bytes field is
    # operands + outputs; payload ≈ half of that for the symmetric
    # collectives we model.
    return r.mult * r.bytes / 2.0


def _memory_stats(compiled) -> Dict[str, Any]:
    try:
        ms = compiled.memory_analysis()
        return {
            "arg_bytes": float(getattr(ms, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(ms, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ms, "temp_size_in_bytes", 0)),
        }
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# per-engine dispatch tracker: opt-in roofline capture + always-on
# recompile forensics at the `_traced` / serving-dispatch seam.

# most recent recompile-forensics records, process-wide: the
# xla_compile_counter fixture prints these when a compile-once
# assertion fails, so the failure names the shape that moved
_recent_recompiles: collections.deque = collections.deque(maxlen=16)

# last roofline record per program name, process-wide (bench legs read
# collective totals from here without re-parsing the run log)
_reports: Dict[str, Dict[str, Any]] = {}


def recent_recompiles() -> List[Dict[str, Any]]:
    return list(_recent_recompiles)


def report(program: str) -> Optional[Dict[str, Any]]:
    return _reports.get(program)


def reports() -> Dict[str, Dict[str, Any]]:
    return dict(_reports)


def _leaf_desc(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return f"py:{type(leaf).__name__}"
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


# leaf-path strings memoized per treedef: the serving decode step calls
# dispatch_signature once per generated token, and keystr's per-leaf
# string building is the expensive half — structure repeats, so pay it
# once per distinct treedef
_path_cache: Dict[Any, List[str]] = {}


def dispatch_signature(args: Any) -> Tuple[Tuple[str, str], ...]:
    """Abstract signature of a dispatch's args: (tree path, shape/dtype)
    per leaf — values never recorded. Cheap enough for every dispatch
    (it is what makes recompile forensics free at default knobs)."""
    import jax
    try:
        leaves, td = jax.tree_util.tree_flatten(args)
        paths = _path_cache.get(td)
        if paths is None:
            if len(_path_cache) > 128:   # bounded: treedefs per process
                _path_cache.clear()
            flat = jax.tree_util.tree_flatten_with_path(args)[0]
            paths = [jax.tree_util.keystr(p) for p, _ in flat]
            _path_cache[td] = paths
        return tuple(zip(paths, (_leaf_desc(l) for l in leaves)))
    except Exception:
        leaves = jax.tree_util.tree_leaves(args)
        return tuple((f"[{i}]", _leaf_desc(l))
                     for i, l in enumerate(leaves))


class DispatchTracker:
    """Per-engine-instance compute-plane seam. ``signature`` +
    ``observe`` give recompile forensics on every dispatch;
    ``maybe_capture`` does the opt-in AOT roofline capture (once per
    (program, signature) — call it BEFORE the dispatch so donated
    buffers are still alive, and BEFORE snapshotting the compile
    counter so its AOT compile is not charged to the dispatch)."""

    def __init__(self, enabled: Optional[bool] = None,
                 n_devices: int = 1, device: Any = None):
        self.enabled = (bool(enabled) if enabled is not None
                        else _cfg["default_enabled"])
        self.n_devices = int(n_devices)
        self.device = device
        self._sigs: Dict[str, Tuple[Tuple[str, str], ...]] = {}
        self._compiles: Dict[str, int] = {}
        # SET of captured signatures per program: a shape-alternating
        # program (the exact pathology this plane diagnoses) must pay
        # one AOT compile per distinct signature, not one per dispatch
        self._captured: Dict[str, set] = {}

    # --- roofline capture (opt-in) -------------------------------------
    def maybe_capture(self, program: str, fn: Any, args: Sequence[Any],
                      sig: Optional[Tuple] = None) -> Optional[Dict[str, Any]]:
        if not self.enabled:
            return None
        if sig is None:
            sig = dispatch_signature(tuple(args))
        seen = self._captured.setdefault(program, set())
        if sig in seen:
            return None
        seen.add(sig)
        try:
            compiled = fn.lower(*args).compile()
            rec = analyze_compiled(program, compiled, device=self.device,
                                   n_devices=self.n_devices)
        except Exception as e:  # capture must never sink a run
            logger.warning("roofline capture of %r failed (%s: %s)",
                           program, type(e).__name__, e)
            return None
        _reports[program] = rec
        from .. import mlops
        mlops._emit("roofline", rec)
        obs_metrics.record_roofline(
            program, rec.get("predicted_mfu"),
            rec.get("memory_bound_share"),
            rec.get("collective_wire_bytes"))
        logger.info(
            "roofline[%s]: %d ops, predicted %s, mfu %s, memory-bound "
            "share %.2f, collective wire bytes %.0f%s",
            program, len(rec["ops"]),
            f"{rec['predicted_s'] * 1e3:.3f} ms",
            rec["predicted_mfu"], rec["memory_bound_share"],
            rec["collective_wire_bytes"],
            " (STATIC-ONLY: cpu balance)" if rec["static_only"] else "")
        return rec

    # --- recompile forensics (always on) -------------------------------
    def observe(self, program: str, sig: Tuple[Tuple[str, str], ...],
                compiles: int) -> Optional[Dict[str, Any]]:
        """Record a dispatch's signature; when the compile counter
        incremented PAST the pinned expectation (one compile per
        program), emit the ``kind: recompile`` forensics record naming
        the changed abstract shapes."""
        prev = self._sigs.get(program)
        self._sigs[program] = sig
        if compiles <= 0:
            return None
        total = self._compiles.get(program, 0) + int(compiles)
        self._compiles[program] = total
        if prev is None:
            return None   # the expected first compile
        changed: List[Dict[str, Any]] = []
        old = dict(prev)
        new = dict(sig)
        for path in new:
            if path not in old:
                changed.append({"arg": path, "was": None,
                                "now": new[path]})
            elif old[path] != new[path]:
                changed.append({"arg": path, "was": old[path],
                                "now": new[path]})
        for path in old:
            if path not in new:
                changed.append({"arg": path, "was": old[path],
                                "now": None})
        note = None
        if not changed:
            note = ("no abstract-shape change — cache miss from a new "
                    "callable, jit options, or sharding change")
        rec = {"program": str(program), "compiles": int(compiles),
               "total_compiles": int(total), "expected": 1,
               "changed": changed, "note": note}
        from .. import mlops
        mlops._emit("recompile", rec)
        obs_metrics.record_recompile(program)
        _recent_recompiles.append(rec)
        logger.warning(
            "recompile forensics[%s]: %d compile(s) past the pinned "
            "expectation; changed: %s", program,
            compiles, changed or note)
        return rec
