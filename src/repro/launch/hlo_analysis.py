"""Trip-count-weighted cost analysis of compiled (post-SPMD, per-device) HLO.

Why this exists: ``compiled.cost_analysis()`` counts every computation ONCE —
a jax.lax.scan over 80 layers contributes its body a single time (verified:
an 8-step scan reports exactly 1/8 the flops of its unrolled twin).  Scanned
layer stacks, microbatch accumulation loops, and SSM chunk scans are exactly
how this framework keeps HLO compact, so module-level cost analysis is off
by orders of magnitude.  Fortunately XLA annotates optimized while ops with
``backend_config={"known_trip_count":{"n":...}}``.

This module parses the HLO text into computations, propagates execution
multiplicity through while/call/fusion/conditional edges, and accumulates:

  flops        2 * prod(result) * prod(contracted) per dot; prod(result) per
               arithmetic elementwise op; prod(operand) per reduce
  bytes        operand + result buffer bytes of top-level ops (fusion bodies
               excluded — their internals never touch HBM)
  collectives  result-buffer bytes of all-reduce / all-gather /
               reduce-scatter / all-to-all / collective-permute, by kind

All numbers are per-device (the module is post-partitioning).  Validated in
tests against cost_analysis on scan-free graphs and against the trip-count
identity on scanned ones.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "exponential",
    "exponential-minus-one", "tanh", "log", "log-plus-one", "negate",
    "maximum", "minimum", "select", "sqrt", "rsqrt", "logistic", "sine",
    "cosine", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "abs", "sign", "atan2", "clamp", "erf",
}

_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "after-all", "partition-id", "replica-id"}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?(%[\w\.\-]+)\s*=\s*(.+?)\s([\w\-]+)\(")


def _shapes(segment: str):
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype in _DTYPE_BYTES:
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            yield dtype, n


def _buf_bytes(segment: str) -> int:
    return sum(_DTYPE_BYTES[dt] * n for dt, n in _shapes(segment))


def _elems(segment: str) -> int:
    return sum(n for _, n in _shapes(segment))


class HloModule:
    def __init__(self, text: str):
        self.comps: dict[str, list] = {}
        self.entry = None
        self.result_type: dict[str, str] = {}
        self.roots: dict[str, tuple] = {}
        self._parse(text)

    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            line = raw.rstrip()
            stripped = line.strip()
            # computation header: "[ENTRY] %name (args...) -> ret {"
            # args may contain nested parens (tuple types), so key off the
            # "-> ... {" tail and take the first token as the name.
            if (
                stripped.endswith("{")
                and "->" in stripped
                and "=" not in stripped.split("(")[0]
            ):
                toks = stripped.split()
                is_entry = toks[0] == "ENTRY"
                name = (toks[1] if is_entry else toks[0]).lstrip("%").rstrip("(")
                # names may appear as "%name" or "%name.N (" fused together
                name = name.split("(")[0]
                cur = name
                self.comps[cur] = []
                if is_entry:
                    self.entry = cur
                continue
            if cur is None:
                continue
            if line.strip() == "}":
                cur = None
                continue
            m = _INSTR_RE.match(line)
            if m:
                is_root, iname, type_str, opcode = m.groups()
                self.comps[cur].append((iname, type_str, opcode, line))
                self.result_type[iname] = type_str
                if is_root:
                    self.roots[cur] = (iname, type_str, opcode, line)

    # ------------------------------------------------------- multiplicity
    def multiplicities(self) -> dict[str, float]:
        mult: dict[str, float] = defaultdict(float)
        fusion_bodies: set[str] = set()
        mult[self.entry] = 1.0
        order = [self.entry]
        seen = {self.entry}
        # breadth-first over call edges; HLO call graphs are acyclic
        i = 0
        while i < len(order):
            comp = order[i]
            i += 1
            for iname, type_str, opcode, line in self.comps.get(comp, []):
                targets: list[tuple[str, float]] = []
                if opcode == "while":
                    trip = 1.0
                    mt = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line)
                    if mt:
                        trip = float(mt.group(1))
                    mb = re.search(r"body=(%?[\w\.\-]+)", line)
                    mc = re.search(r"condition=(%?[\w\.\-]+)", line)
                    if mb:
                        targets.append((mb.group(1).lstrip("%"), trip))
                    if mc:
                        targets.append((mc.group(1).lstrip("%"), trip + 1))
                elif opcode == "fusion":
                    mf = re.search(r"calls=(%?[\w\.\-]+)", line)
                    if mf:
                        body = mf.group(1).lstrip("%")
                        fusion_bodies.add(body)
                        targets.append((body, 1.0))
                elif opcode == "conditional":
                    for mm in re.finditer(r"(?:branch_computations=\{([^}]*)\}|true_computation=(%?[\w\.\-]+)|false_computation=(%?[\w\.\-]+))", line):
                        blob = mm.group(1) or ""
                        for b in blob.split(","):
                            b = b.strip().lstrip("%")
                            if b:
                                targets.append((b, 1.0))
                        for g in (mm.group(2), mm.group(3)):
                            if g:
                                targets.append((g.lstrip("%"), 1.0))
                else:
                    mt = re.search(r"to_apply=(%?[\w\.\-]+)", line)
                    if mt:
                        # reduce/sort/map/scatter scalar bodies: negligible,
                        # but keep the edge for completeness
                        targets.append((mt.group(1).lstrip("%"), 1.0))
                    mc2 = re.search(r"calls=(%?[\w\.\-]+)", line)
                    if mc2 and opcode == "call":
                        targets.append((mc2.group(1).lstrip("%"), 1.0))
                for tname, factor in targets:
                    if tname in self.comps:
                        mult[tname] += mult[comp] * factor
                        if tname not in seen:
                            seen.add(tname)
                            order.append(tname)
        self._fusion_bodies = fusion_bodies
        return dict(mult)

    # ------------------------------------------------------------- costs
    def _dot_flops(self, comp: str, type_str: str, line: str) -> float:
        res_elems = _elems(type_str)
        mdim = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
        # lhs operand = first top-level argument of "dot(%a, %b)"; its type
        # is the ref's recorded result type
        lhs = re.search(r"\bdot\(\s*(%?[\w\.\-]+)", line)
        lhs_type = self.result_type.get(lhs.group(1), "") if lhs else ""
        contract = 1
        if mdim:
            dims_m = _SHAPE_RE.search(lhs_type)
            if dims_m and dims_m.group(2):
                lhs_dims = [int(d) for d in dims_m.group(2).split(",")]
                for ci in mdim.group(1).split(","):
                    if ci != "":
                        contract *= lhs_dims[int(ci)]
        return 2.0 * res_elems * contract

    def _operand_bytes_list(self, line: str) -> list:
        m = re.search(r"\((.*)\)", line)
        if not m:
            return []
        return [
            _buf_bytes(self.result_type.get(ref, ""))
            for ref in re.findall(r"%[\w\.\-]+", m.group(1))
        ]

    def _operand_bytes(self, line: str) -> int:
        return sum(self._operand_bytes_list(line))

    def _fusion_io_bytes(self, line: str, type_str: str) -> int:
        """Fusion IO with in-place/windowed patterns recognized.

        A fusion parameter that the body only touches through slicing ops
        (dynamic-slice / slice / gather / DUS target) costs its *window*
        bytes, not the whole buffer — otherwise every per-layer KV-cache
        read/write bills the entire stacked cache (observed 10x bytes
        inflation on decode cells).  A root dynamic-update-slice aliases its
        target, so the result is free (window already charged)."""
        mf = re.search(r"calls=(%?[\w\.\-]+)", line)
        body = mf.group(1).lstrip("%") if mf else None
        instrs = self.comps.get(body, []) if body else []
        root = self.roots.get(body) if body else None

        # def-map inside the body; chase convert/bitcast/copy chains — the
        # CPU backend emulates bf16 by wrapping real ops in f32 converts,
        # which must not hide the in-place structure (absent on real TPU).
        defs = {iname: (t, op, line) for iname, t, op, line in instrs}

        def chase(name):
            seen = 0
            while name in defs and defs[name][1] in ("convert", "bitcast", "copy") and seen < 8:
                refs = re.findall(r"%[\w\.\-]+", defs[name][2].split("(", 1)[1])
                if not refs:
                    break
                name = refs[0]
                seen += 1
            return name

        ordinal: dict[str, int] = {}
        for iname, t, op, line in instrs:
            if op == "parameter":
                mo = re.search(r"parameter\((\d+)\)", line)
                if mo:
                    ordinal[iname] = int(mo.group(1))

        def as_param(ref):
            return ordinal.get(chase(ref))

        windowed: dict[int, float] = {}
        full_use: set = set()
        aliased: set = set()
        for iname, t, op, line in instrs:
            if op in ("parameter", "convert", "bitcast", "copy"):
                continue
            refs = re.findall(r"%[\w\.\-]+", line.split("(", 1)[1] if "(" in line else "")
            if op in ("dynamic-slice", "slice", "gather") and refs:
                o = as_param(refs[0])
                if o is not None:
                    windowed[o] = windowed.get(o, 0.0) + 2 * _buf_bytes(t)
                    refs = refs[1:]
            elif op == "dynamic-update-slice" and refs:
                o = as_param(refs[0])
                rb = self._operand_bytes_list(line)
                win = rb[1] if len(rb) > 1 else 0
                if o is not None:
                    windowed[o] = windowed.get(o, 0.0) + 2 * win
                    aliased.add(o)
                    refs = refs[1:]
            for r in refs:
                o = as_param(r)
                if o is not None:
                    full_use.add(o)

        ops_b = self._operand_bytes_list(line)
        total = 0.0
        for i, b in enumerate(ops_b):
            if i in windowed and i not in full_use:
                total += min(b, windowed[i])
            else:
                total += b
        root_is_dus = False
        if root is not None:
            root_is_dus = defs.get(chase(root[0]), ("", root[2], ""))[1] == "dynamic-update-slice"
        if not root_is_dus:
            total += _buf_bytes(type_str)
        return int(total)

    def analyze(self) -> dict:
        mult = self.multiplicities()
        flops = 0.0
        bytes_accessed = 0.0
        coll = defaultdict(float)
        coll_counts = defaultdict(float)
        flops_by_op = defaultdict(float)
        for comp, instrs in self.comps.items():
            w = mult.get(comp, 0.0)
            if w == 0.0:
                continue
            in_fusion = comp in getattr(self, "_fusion_bodies", set())
            for iname, type_str, opcode, line in instrs:
                if opcode in _FREE:
                    continue
                # ---- flops (inside fusions too — they still execute)
                if opcode == "dot":
                    f = self._dot_flops(comp, type_str, line)
                    flops += w * f
                    flops_by_op["dot"] += w * f
                elif opcode in _ELEMENTWISE:
                    f = float(_elems(type_str))
                    flops += w * f
                    flops_by_op["elementwise"] += w * f
                elif opcode == "reduce":
                    f = float(self._operand_bytes(line)) / 4.0  # ~elements
                    flops += w * f
                    flops_by_op["reduce"] += w * f
                elif opcode == "convolution":
                    # not used by these models; coarse: 2 * out * window
                    f = 2.0 * _elems(type_str)
                    flops += w * f
                    flops_by_op["conv"] += w * f
                # ---- bytes (top-level ops only; fusion internals are free).
                # Opcode-aware so in-place/windowed ops aren't charged their
                # whole operand buffers (a decode step would otherwise look
                # like it re-reads the entire KV cache per layer slice).
                if not in_fusion:
                    if opcode in ("while", "conditional", "call", "tuple",
                                  "get-tuple-element", "reshape", "bitcast",
                                  "parameter", "constant"):
                        pass  # control flow & aliasing: no real traffic
                    elif opcode in ("dynamic-slice", "slice", "gather",
                                    "broadcast", "iota"):
                        bytes_accessed += w * 2 * _buf_bytes(type_str)
                    elif opcode == "dynamic-update-slice":
                        ops_b = self._operand_bytes_list(line)
                        upd = ops_b[1] if len(ops_b) > 1 else 0
                        bytes_accessed += w * 2 * upd  # read+write the window
                    elif opcode == "scatter":
                        ops_b = self._operand_bytes_list(line)
                        upd = ops_b[2] if len(ops_b) > 2 else _buf_bytes(type_str)
                        bytes_accessed += w * 2 * upd
                    elif opcode == "fusion":
                        bytes_accessed += w * self._fusion_io_bytes(line, type_str)
                    else:
                        bytes_accessed += w * (
                            _buf_bytes(type_str) + self._operand_bytes(line)
                        )
                # ---- collectives
                base = opcode.replace("-start", "")
                if base in _COLLECTIVES and not opcode.endswith("-done"):
                    b = float(_buf_bytes(type_str))
                    coll[base] += w * b
                    coll_counts[base + "_count"] += w
        out = dict(coll)
        out.update(coll_counts)
        out["total"] = sum(coll.values())
        return {
            "flops": flops,
            "bytes": bytes_accessed,
            "collective": out,
            "flops_by_op": dict(flops_by_op),
        }


def analyze_hlo(text: str) -> dict:
    return HloModule(text).analyze()


def collective_bytes(hlo_text: str) -> dict:
    """Back-compat shim: trip-count-weighted collective bytes by kind."""
    return analyze_hlo(hlo_text)["collective"]
