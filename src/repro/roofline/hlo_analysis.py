"""Static analyzer for SPMD-partitioned HLO text.

`compiled.cost_analysis()` visits while bodies ONCE, so for scan-over-
layers programs (every model here) its FLOP/byte counts are ~depth×
too low, and it reports nothing about collectives.  This module re-derives
the three roofline inputs directly from `compiled.as_text()`:

  * flops      — 2·|result|·|contraction| per `dot`, × loop trip counts
                 (trip counts read from the while op's backend_config
                 `known_trip_count`, falling back to the condition's
                 comparison constant),
  * hbm_bytes  — Σ (operand + result sizes) over *top-level* ops — i.e.
                 fusion boundaries, which is exactly XLA's definition of
                 what goes to HBM; zero-cost ops (gte/tuple/parameter/
                 bitcast/constant) excluded, × trip counts,
  * collective_bytes — per collective kind, with ring-model link-byte
                 factors and replica-group sizes parsed per op.

All shapes in the partitioned module are per-device, so every number this
produces is per-chip — matching the roofline denominators.

Validated in `tests/test_roofline.py`: a scanned and an unrolled version
of the same network produce identical FLOP counts, and hand-computable
matmuls match exactly.
"""
from __future__ import annotations

import dataclasses
import math
import re
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")
_OP_RE = re.compile(r"\s*([\w\-]+)\(")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE_RE = re.compile(r"condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count["\s:{]+n["\s:]+\"?(\d+)')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_ZERO_COST = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "after-all", "iota", "partition-id", "replica-id", "reshape",
    "broadcast",
}

# Ops the TPU backend fuses into neighbours (CPU HLO leaves them top-level,
# which would overstate HBM traffic ~3-5×).  Excluding them makes hbm_bytes
# a *fusion-optimistic* model — the methodology caveat documented in
# docs/benchmarks.md.
_FUSED_ON_TPU = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "rsqrt", "sqrt", "power", "select", "compare",
    "and", "or", "not", "xor", "convert", "clamp", "floor", "ceil",
    "round-nearest-even", "round-nearest-afz", "sign", "is-finite", "copy",
    "reverse", "slice", "concatenate", "pad", "transpose", "cosine", "sine",
    "shift-left", "shift-right-logical", "shift-right-arithmetic", "expm1",
    "remainder", "atan2", "cbrt", "erf", "real", "imag", "stochastic-convert",
}

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string; tuples summed (layout braces ignored)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


def _matching_paren(s: str, i: int) -> int:
    depth = 0
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                return j
    return len(s) - 1


@dataclasses.dataclass
class OpDef:
    name: str
    type_str: str
    opcode: str
    args: str
    line: str


def parse_def(line: str) -> OpDef | None:
    m = _NAME_RE.match(line)
    if not m:
        return None
    i = m.end()
    if i >= len(line):
        return None
    if line[i] == "(":  # tuple result type
        j = _matching_paren(line, i)
        type_str = line[i : j + 1]
        k = j + 1
    else:
        sp = line.find(" ", i)
        if sp < 0:
            return None
        type_str = line[i:sp]
        k = sp
    om = _OP_RE.match(line[k:])
    if not om:
        return None
    opcode = om.group(1)
    astart = k + om.end() - 1
    aend = _matching_paren(line, astart)
    return OpDef(m.group(1), type_str, opcode, line[astart + 1 : aend], line)


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: dict[str, float] = dataclasses.field(default_factory=dict)
    coll_counts: dict[str, float] = dataclasses.field(default_factory=dict)
    hbm_by_op: dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, other: "CompCost", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + v * mult
        for k, v in other.coll_counts.items():
            self.coll_counts[k] = self.coll_counts.get(k, 0) + v * mult
        for k, v in other.hbm_by_op.items():
            self.hbm_by_op[k] = self.hbm_by_op.get(k, 0.0) + v * mult

    def _hbm(self, op: str, nbytes: float) -> None:
        self.hbm_bytes += nbytes
        self.hbm_by_op[op] = self.hbm_by_op.get(op, 0.0) + nbytes

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())


def split_computations(text: str) -> tuple[dict[str, list[str]], str]:
    """→ ({name: body lines}, entry_name)."""
    comps: dict[str, list[str]] = {}
    entry = ""
    cur: list[str] | None = None
    cur_name = ""
    for line in text.splitlines():
        if cur is None:
            m = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\{\s*$", line)
            if m and ("->" in line or m.group(1)):
                cur_name = m.group(2)
                cur = []
                if m.group(1):
                    entry = cur_name
        else:
            if line.startswith("}"):
                comps[cur_name] = cur
                cur = None
            else:
                cur.append(line)
    return comps, entry


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _collective_link_bytes(op: str, result_bytes: int,
                           operand_bytes: int, n: int) -> float:
    """Ring-model bytes crossing a link per device."""
    frac = (n - 1) / max(n, 1)
    if op == "all-reduce":
        return 2.0 * result_bytes * frac
    if op == "all-gather":
        return result_bytes * frac
    if op == "reduce-scatter":
        return operand_bytes * frac
    if op == "all-to-all":
        return result_bytes * frac
    if op == "collective-permute":
        return float(result_bytes)
    return 0.0


def analyze_hlo(text: str, default_group: int = 1) -> CompCost:
    comps, entry = split_computations(text)
    if not entry:
        raise ValueError("no ENTRY computation found")
    memo: dict[str, CompCost] = {}

    def trip_count(line: str, cond_name: str) -> int:
        m = _TRIP_RE.search(line)
        if m:
            return int(m.group(1))
        for ln in comps.get(cond_name, []):
            m = _CONST_RE.search(ln)
            if m:
                return int(m.group(1))
        return 1

    def operand_bytes(args: str, types: dict[str, str],
                      producers: dict[str, "OpDef"] | None = None) -> int:
        """Sum operand sizes, looking *through* bf16→f32 legalization:
        the CPU backend has no native bf16, so it inserts convert
        fusions that a TPU build would not have — the true HBM read is
        the convert's INPUT, not its f32 output."""
        total = 0
        for om in re.finditer(r"%([\w\.\-]+)", args):
            name2 = om.group(1)
            if producers:
                for _ in range(3):  # look through convert chains
                    d2 = producers.get(name2)
                    if d2 is None:
                        break
                    if d2.opcode == "convert" or (
                            d2.opcode == "fusion" and "convert" in d2.name):
                        m2 = re.match(r"\s*%([\w\.\-]+)", d2.args)
                        if m2 and shape_dims(d2.type_str) == shape_dims(
                                types.get(m2.group(1), "")):
                            name2 = m2.group(1)
                            continue
                    break
            t = types.get(name2)
            if t:
                total += shape_bytes(t)
        return total

    def cost_of(name: str) -> CompCost:
        if name in memo:
            return memo[name]
        memo[name] = CompCost()  # break cycles defensively
        c = CompCost()
        lines = comps.get(name, [])
        types: dict[str, str] = {}
        producers: dict[str, OpDef] = {}
        defs: list[OpDef] = []
        for ln in lines:
            d = parse_def(ln)
            if d:
                defs.append(d)
                types[d.name] = d.type_str
                producers[d.name] = d
        # computation parameters also carry types (parameter(0) defs) —
        # already included via parse_def above.
        for d in defs:
            op = d.opcode
            if op in _ZERO_COST:
                continue
            if op == "while":
                wm = _WHILE_RE.search(d.line)
                if wm:
                    t = trip_count(d.line, wm.group(1))
                    c.add(cost_of(wm.group(2)), t)
                    c.add(cost_of(wm.group(1)), t)
                continue
            if op == "scatter":
                # in-place aliased: traffic = updates (read) + touched rows
                # (read-modify-write) + indices; NOT the whole buffer
                parts = [pm.group(1) for pm in
                         re.finditer(r"%([\w\.\-]+)", d.args)]
                upd = shape_bytes(types.get(parts[-1], "")) if parts else 0
                idx = shape_bytes(types.get(parts[1], "")) if len(parts) > 2 else 0
                c._hbm(op, 3 * upd + idx)
                continue
            if op in ("call", "conditional", "map", "sort", "reduce",
                      "reduce-window", "select-and-scatter",
                      "custom-call", "async-start"):
                cm = _CALLS_RE.search(d.line)
                if cm:
                    c.add(cost_of(cm.group(1)))
                bm = _BRANCH_RE.search(d.line)
                if bm:
                    for cn in re.split(r",\s*", bm.group(1)):
                        c.add(cost_of(cn.strip().lstrip("%")))
                c._hbm(op, operand_bytes(d.args, types) + shape_bytes(d.type_str))
                continue
            if op == "fusion":
                if "convert" in d.name:
                    continue  # CPU bf16→f32 legalization; absent on TPU
                # one HBM round trip; internals are on-chip by definition
                c._hbm(op, operand_bytes(d.args, types, producers)
                       + shape_bytes(d.type_str))
                continue
            base = op.removesuffix("-start").removesuffix("-done")
            if base in COLLECTIVE_KINDS:
                if op.endswith("-done"):
                    continue  # counted at -start
                n = _group_size(d.line, default_group)
                ob = operand_bytes(d.args, types, producers)
                rb = shape_bytes(d.type_str)
                b = _collective_link_bytes(base, rb, ob, n)
                c.coll_bytes[base] = c.coll_bytes.get(base, 0.0) + b
                c.coll_counts[base] = c.coll_counts.get(base, 0) + 1
                c._hbm(op, ob + rb)
                continue
            if op == "dynamic-update-slice":
                # aliased in-place update: only the update slice moves
                # (read-modify-write), not the full buffer
                um = re.match(r"\s*%[\w\.\-]+,\s*%([\w\.\-]+)", d.args)
                ub = shape_bytes(types.get(um.group(1), "")) if um else 0
                c._hbm(op, 2 * ub)
                continue
            if op in ("dynamic-slice", "gather"):
                # reads only the addressed window, not the whole operand
                c._hbm(op, 2 * shape_bytes(d.type_str))
                continue
            if op == "dot":
                dims = shape_dims(d.type_str)
                # operands may be printed bare (%lhs, %rhs) or typed
                # (f32[16,32]{1,0} %lhs, ...) depending on the XLA version,
                # so locate the first operand name rather than anchoring
                lm = re.search(r"%([\w\.\-]+)", d.args)
                contract = 1
                cm = _CONTRACT_RE.search(d.line)
                if lm and cm and lm.group(1) in types and cm.group(1):
                    ldims = shape_dims(types[lm.group(1)])
                    for ci in cm.group(1).split(","):
                        ci = int(ci)
                        if ci < len(ldims):
                            contract *= ldims[ci]
                c.flops += 2.0 * math.prod(dims) * contract
            elif op == "convolution":
                c.flops += 2.0 * math.prod(shape_dims(d.type_str))
            if op not in _FUSED_ON_TPU:
                c._hbm(op, operand_bytes(d.args, types, producers)
                       + shape_bytes(d.type_str))
        memo[name] = c
        return c

    total = CompCost()
    total.add(cost_of(entry))
    return total
