"""Loop-aware cost analysis of compiled (post-SPMD, post-optimization) HLO.

``compiled.cost_analysis()`` counts while/scan bodies exactly ONCE, which
silently drops the layer-scan, microbatch-accumulation, CE-chunk and
flash-attention-block trip counts — i.e. nearly all of the FLOPs in this
framework.  This module walks the HLO text instead:

- computations are parsed into instructions with a per-computation symbol
  table (instruction -> shape);
- ``while`` bodies are multiplied by their ``known_trip_count`` backend
  config (fallback: the constant in the condition's compare);
- ``fusion``/``call`` recurse into their called computations (FLOPs inside,
  HBM traffic only at the fusion boundary — post-fusion operands/results are
  exactly the tensors that cross HBM);
- ``conditional`` takes the max across branches;
- collectives are tallied separately with ring-traffic multipliers
  (all-reduce 2x operand, reduce-scatter/all-to-all/permute 1x operand,
  all-gather 1x result) — these feed the ICI roofline term.

Shapes in the partitioned module are per-device shards, so all outputs are
per-chip, matching the per-chip roofline denominators.
"""
from __future__ import annotations

import dataclasses
import re

# TPU v5e hardware constants (assignment-specified).
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~50 GB/s/link)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}
# ~1 flop per output element
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "rsqrt", "sqrt", "power",
    "logistic", "cosine", "sine", "select", "compare", "and", "or", "xor",
    "not", "floor", "ceil", "round-nearest-afz", "clamp", "remainder",
    "shift-left", "shift-right-logical", "shift-right-arithmetic", "atan2",
    "expm1", "log1p", "sign", "convert", "reduce", "exponential-minus-one",
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems(shape_str: str) -> int:
    total = 0
    for _, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    rest: str            # operand list + attributes (raw text)


def _parse_instr(line: str) -> Instr | None:
    """Manual parse — tuple types may contain '/*index=N*/' comments and
    nested parens that defeat regexes."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[1:eq]
    rest = s[eq + 3:]
    if rest.startswith("("):                       # tuple type: balance parens
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        typ, rem = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        typ, rem = rest[:sp], rest[sp + 1:].lstrip()
    par = rem.find("(")
    if par <= 0:
        return None
    opcode = rem[:par]
    if not re.fullmatch(r"[\w\-]+", opcode):
        return None
    return Instr(name, typ, opcode, rem[par + 1:])


class HloModule:
    def __init__(self, text: str):
        self.computations: dict[str, list[Instr]] = {}
        self.entry: str | None = None
        self._parse(text)

    def _parse(self, text: str) -> None:
        cur: list[Instr] | None = None
        for line in text.splitlines():
            if not line or line.startswith(("HloModule", "  ", "\t")) and cur is None \
               and not line.strip().startswith(("%", "ROOT")):
                pass
            hdr = _COMP_HDR.match(line)
            if hdr and line.rstrip().endswith("{"):
                name = hdr.group(2)
                cur = []
                self.computations[name] = cur
                if hdr.group(1):
                    self.entry = name
                continue
            if line.startswith("}"):
                cur = None
                continue
            if cur is None:
                continue
            ins = _parse_instr(line)
            if ins is not None:
                cur.append(ins)


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_kind: dict = dataclasses.field(default_factory=dict)

    def __add__(self, o: "Cost") -> "Cost":
        kinds = dict(self.coll_by_kind)
        for k, v in o.coll_by_kind.items():
            kinds[k] = kinds.get(k, 0.0) + v
        return Cost(self.flops + o.flops, self.bytes + o.bytes,
                    self.coll_bytes + o.coll_bytes, kinds)

    def __mul__(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k, self.coll_bytes * k,
                    {kk: v * k for kk, v in self.coll_by_kind.items()})


class CostWalker:
    def __init__(self, module: HloModule):
        self.m = module
        self._memo: dict[tuple[str, bool], Cost] = {}

    def _operand_shapes(self, instr: Instr, table: dict[str, str]) -> list[str]:
        # operand names appear before attribute text; attributes also contain
        # %names (calls= etc.) — restrict to the parenthesised operand list.
        depth, end = 1, max(len(instr.rest) - 1, 0)
        for i, ch in enumerate(instr.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        oper_text = instr.rest[:end]
        return [table[n] for n in _OPERAND_RE.findall(oper_text) if n in table]

    def comp_cost(self, name: str, top_level: bool) -> Cost:
        """top_level=True counts HBM traffic at instruction boundaries;
        inside fusions only FLOPs are accumulated."""
        key = (name, top_level)
        if key in self._memo:
            return self._memo[key]
        instrs = self.m.computations.get(name, [])
        table = {i.name: i.shape for i in instrs}
        total = Cost()
        for ins in instrs:
            total = total + self._instr_cost(ins, table, top_level)
        self._memo[key] = total
        return total

    def _dot_flops(self, ins: Instr, table: dict[str, str]) -> float:
        ops = self._operand_shapes(ins, table)
        result_elems = _shape_elems(ins.shape)
        k = 1
        mc = _LHS_CONTRACT_RE.search(ins.rest)
        if mc and ops:
            lhs_dims_m = _SHAPE_RE.search(ops[0])
            if lhs_dims_m:
                lhs_dims = [int(d) for d in lhs_dims_m.group(2).split(",") if d]
                for ci in mc.group(1).split(","):
                    if ci:
                        k *= lhs_dims[int(ci)]
        return 2.0 * result_elems * k

    def _instr_cost(self, ins: Instr, table: dict[str, str],
                    top_level: bool) -> Cost:
        op = ins.opcode
        c = Cost()
        base = op.replace("-start", "").replace("-done", "")
        if base in _COLLECTIVES:
            if op.endswith("-done"):
                return c
            opshapes = self._operand_shapes(ins, table)
            opbytes = sum(_shape_bytes(s) for s in opshapes)
            resbytes = _shape_bytes(ins.shape)
            traffic = {"all-gather": resbytes, "all-reduce": 2 * opbytes,
                       "reduce-scatter": opbytes, "all-to-all": opbytes,
                       "collective-permute": opbytes}[base]
            c.coll_bytes += traffic
            c.coll_by_kind[base] = c.coll_by_kind.get(base, 0.0) + traffic
            if top_level:  # collectives also read/write HBM
                c.bytes += opbytes + resbytes
            return c
        if op == "while":
            body = _BODY_RE.search(ins.rest)
            cond = _COND_RE.search(ins.rest)
            trips = 1
            mt = _TRIP_RE.search(ins.rest)
            if mt:
                trips = int(mt.group(1))
            else:
                trips = self._cond_trips(cond.group(1)) if cond else 1
            sub = self.comp_cost(body.group(1), top_level=True) if body else Cost()
            cond_cost = self.comp_cost(cond.group(1), top_level=True) if cond else Cost()
            return (sub + cond_cost) * trips
        if op == "conditional":
            mb = _BRANCHES_RE.search(ins.rest)
            if mb:
                branches = _OPERAND_RE.findall(mb.group(1))
                costs = [self.comp_cost(b, top_level=True) for b in branches]
                if costs:
                    return max(costs, key=lambda x: max(x.flops, x.bytes))
            return c
        if op == "convert":
            # XLA-CPU materialises bf16<->f32 dot-operand converts as
            # standalone ops (hoisting loop-invariant ones into while
            # carries); TPU consumes bf16 natively in the MXU and fuses any
            # residual converts into producers/consumers.  Count FLOP-free,
            # byte-free.  (Without this, a 32k-decode step "reads" the KV
            # cache 30x over through f32 copies that do not exist on TPU.)
            return c
        if op in ("fusion", "call", "custom-call", "map", "reduce-window",
                  "scatter", "reduce", "sort"):
            target = _CALLS_RE.search(ins.rest) or _TO_APPLY_RE.search(ins.rest)
            inner_instrs = []
            if target and target.group(1) in self.m.computations:
                inner = self.comp_cost(target.group(1), top_level=False)
                inner_instrs = self.m.computations[target.group(1)]
                c.flops += inner.flops
                c.coll_bytes += inner.coll_bytes
                for k, v in inner.coll_by_kind.items():
                    c.coll_by_kind[k] = c.coll_by_kind.get(k, 0.0) + v
            if top_level:
                # pure-convert fusions are the same CPU artifact as bare
                # converts: no TPU traffic
                if inner_instrs and all(
                        i.opcode in ("parameter", "convert", "bitcast")
                        for i in inner_instrs):
                    return c
                opshapes = self._operand_shapes(ins, table)
                resbytes = _shape_bytes(ins.shape)
                opbytes = [
                    _shape_bytes(s) for s in opshapes]
                # In-place cache-update fusions: a fused dynamic-update-slice
                # whose result aliases the big operand only truly moves the
                # update slice (read) + slice (write), not the whole buffer.
                dus = [i for i in inner_instrs
                       if i.opcode == "dynamic-update-slice"]
                slicing = [i for i in inner_instrs
                           if i.opcode in ("dynamic-slice", "gather")]
                if dus and opbytes and any(b >= resbytes for b in opbytes):
                    # in-place cache update: traffic = the update slice (+
                    # small operands).  Buffer-sized operands are the alias
                    # target and/or CPU-artifact f32 shadows of it — neither
                    # moves on TPU.
                    inner_table = {i.name: i.shape for i in inner_instrs}
                    upd = 0
                    for d in dus:
                        dops = self._operand_shapes(d, inner_table)
                        if len(dops) >= 2:
                            upd += _shape_bytes(dops[1])
                    c.bytes += sum(b for b in opbytes if b < resbytes) + 2 * upd
                elif slicing and opbytes and max(opbytes) > 4 * max(resbytes, 1):
                    # slice/gather fusions read ~the slice, not the buffer
                    big = max(opbytes)
                    c.bytes += 2 * resbytes + sum(opbytes) - big
                else:
                    c.bytes += resbytes + sum(opbytes)
            return c
        if op in ("dynamic-slice", "gather"):
            if top_level:
                c.bytes += 2 * _shape_bytes(ins.shape)
            return c
        if op == "copy":
            # same-type copies are loop double-buffering / donation copies
            # that TPU aliases away; layout-CHANGING copies (transposes)
            # move real bytes.
            if top_level:
                ops_ = self._operand_shapes(ins, table)
                if not (ops_ and ops_[0] == ins.shape):
                    c.bytes += _shape_bytes(ins.shape) + sum(
                        _shape_bytes(s) for s in ops_)
            return c
        if op == "dynamic-update-slice":
            if top_level:
                opshapes = self._operand_shapes(ins, table)
                upd = _shape_bytes(opshapes[1]) if len(opshapes) >= 2 else 0
                c.bytes += 2 * upd
            return c
        if op == "dot":
            c.flops += self._dot_flops(ins, table)
        elif op == "convolution":
            # depthwise/pointwise convs only in this framework; approximate
            # as 2 * result_elems * (spatial window) — window unknown from
            # text reliably; use result elems * 8 as a bounded estimate.
            c.flops += 8.0 * _shape_elems(ins.shape)
        elif op in _ELEMENTWISE:
            c.flops += float(_shape_elems(ins.shape))
        if top_level and op not in _SKIP_BYTES_OPS:
            c.bytes += _shape_bytes(ins.shape)
            c.bytes += sum(_shape_bytes(s) for s in self._operand_shapes(ins, table))
        return c

    def _cond_trips(self, cond_name: str) -> int:
        instrs = self.m.computations.get(cond_name, [])
        for ins in instrs:
            if ins.opcode == "constant":
                mm = re.search(r"constant\((\d+)\)", "constant(" + ins.rest)
                if mm:
                    return int(mm.group(1))
        return 1

    def entry_cost(self) -> Cost:
        assert self.m.entry is not None
        return self.comp_cost(self.m.entry, top_level=True)


@dataclasses.dataclass
class Roofline:
    flops: float                # per-device, loop-trip-aware
    bytes_accessed: float       # per-device HBM traffic (post-fusion)
    coll_bytes: float           # per-device collective link traffic
    coll_by_kind: dict
    xla_flops: float = 0.0      # raw cost_analysis (scan bodies once)
    xla_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Ideal-overlap lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "bytes_per_device": self.bytes_accessed,
            "collective_bytes_per_device": self.coll_bytes,
            "collective_by_kind": self.coll_by_kind,
            "xla_cost_analysis_flops": self.xla_flops,
            "xla_cost_analysis_bytes": self.xla_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_lower_bound_s": self.step_time_s,
        }


def analyze_text(hlo_text: str) -> Cost:
    return CostWalker(HloModule(hlo_text)).entry_cost()


def analyze(compiled) -> Roofline:
    ca = compiled.cost_analysis()
    cost = analyze_text(compiled.as_text())
    return Roofline(
        flops=cost.flops,
        bytes_accessed=cost.bytes,
        coll_bytes=cost.coll_bytes,
        coll_by_kind=cost.coll_by_kind,
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
    )
