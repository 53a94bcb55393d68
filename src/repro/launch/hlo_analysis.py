"""Compiled-HLO analysis: collective bytes, loop-aware accounting.

cost_analysis() reports FLOPs/bytes but NOT collective traffic; we parse the
post-SPMD HLO. Operand sizes are derived from each collective's *output*
shape plus op semantics (all-gather output = operand × group, reduce-scatter
output = operand / group, all-reduce/all-to-all/permute output = operand),
with the group size parsed from replica_groups. Collectives inside while
bodies (lax.scan over layers) execute trip-count times but appear once in
text; we multiply through the call graph (while trip count = the largest
integer constant in the loop's condition computation — the scan bound).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from repro.scopes import DEVICE_SCOPES

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|[suc]\d+|f8e4m3fn|f8e5m2)"
                       r"\[([\d,]*)\]")
_OP_RE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                    r"collective-permute)(-start|-done)?\(")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_WHILE_RE = re.compile(r"\bwhile\(")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))


@dataclasses.dataclass
class LoopAwareStats:
    """Trip-count-corrected compute/memory totals.

    XLA's compiled cost_analysis counts a while body ONCE regardless of its
    trip count (verified: a 10-iteration scan of one matmul reports one
    matmul's FLOPs), so for scan-over-layers models it undercounts by ~L.
    We re-derive:
      dot_flops     — 2·M·N·K per dot × loop multiplier
      hbm_bytes     — Σ loop-weighted materialized-buffer bytes (outputs of
                      top-level ops excluding shape-only ops) × 2 (read+write
                      proxy; fusion internals excluded as they stay in
                      registers/VMEM)
    """

    dot_flops: float
    hbm_bytes: float
    transcendental_elems: float
    # traffic inside jax.named_scope("flash_tile") — materialized by XLA CPU
    # fusion but VMEM-resident in the Pallas flash kernel on real TPUs
    flash_tile_bytes: float = 0.0


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """computation name → instruction lines (headers end with '{')."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            head = stripped
            if head.startswith("ENTRY"):
                head = head[len("ENTRY"):].strip()
            name = head.split(" ", 1)[0].split("(", 1)[0].lstrip("%")
            cur = name
            comps[cur] = []
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _group_size(line: str) -> int:
    m = _GROUPS_BRACKET_RE.search(line)
    if m:
        return max(1, int(m.group(2)))  # [n_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(1, len([x for x in m.group(1).split(",") if x.strip()]))
    return 1


def _line_collective(line: str) -> tuple[str, float] | None:
    """(kind, per-execution operand bytes) for a collective def line."""
    m = _OP_RE.search(line)
    if not m or m.group(2) == "-done":
        return None
    eq = line.find("=")
    if eq < 0 or m.start() < eq:
        return None  # the match was in the lhs name, not the opcode
    kind = m.group(1)
    head = line[eq:m.start()]
    out_bytes = sum(_shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(head))
    if out_bytes == 0:
        return None
    g = _group_size(line)
    if kind == "all-gather":
        operand = out_bytes / g
    elif kind == "reduce-scatter":
        operand = out_bytes * g
    else:  # all-reduce, all-to-all, collective-permute
        operand = out_bytes
    return kind, float(operand)


def _trip_count(cond_lines: list[str]) -> int:
    best = 1
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            v = int(m.group(1))
            if v < 2**31 - 1:  # ignore INT_MAX sentinels
                best = max(best, v)
    return best


def _call_graph(comps: dict[str, list[str]]):
    """(calls: comp → [(callee, mult)], multipliers: comp → total mult)."""
    calls: dict[str, list[tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for line in lines:
            if _WHILE_RE.search(line):
                bm = _BODY_RE.search(line)
                cm = _COND_RE.search(line)
                if bm and bm.group(1) in comps:
                    tc = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                    calls[name].append((bm.group(1), tc))
            else:
                for m in _CALLS_RE.finditer(line):
                    if m.group(1) in comps and m.group(1) != name:
                        calls[name].append((m.group(1), 1))
                bm = _BRANCHES_RE.search(line)
                if bm:
                    for c in re.split(r",\s*", bm.group(1)):
                        c = c.strip().lstrip("%")
                        if c in comps and c != name:
                            calls[name].append((c, 1))
    mult: dict[str, float] = defaultdict(float)
    called = {c for lst in calls.values() for c, _ in lst}
    entries = [n for n in comps if n not in called]

    def walk(n, m, seen):
        mult[n] += m
        for c, k in calls.get(n, []):
            if c not in seen:
                walk(c, m * k, seen | {n})

    for e in entries or list(comps):
        walk(e, 1, frozenset())
    return calls, mult


def build_call_graph(hlo_text: str):
    """Parse HLO text into its loop-trip-multiplied call graph.

    Returns ``(comps, calls, mult)``: computation name → instruction
    lines, name → [(callee, trip multiplier)], and name → total execution
    multiplier from every entry. The one shared walk consumed by the
    ``hlo_inspect`` CLI and the ``repro.analysis`` contract checker.
    """
    comps = _split_computations(hlo_text)
    calls, mult = _call_graph(comps)
    return comps, calls, mult


_SKIP_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
             "constant", "copy", "while", "conditional", "custom-call",
             "after-all", "partition-id", "replica-id"}
_TRANSC_OPS = {"exponential", "tanh", "log", "rsqrt", "sqrt", "power",
               "logistic", "sine", "cosine"}


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
                     r"(pred|bf16|f16|f32|f64|[suc]\d+|f8e4m3fn|f8e5m2)"
                     r"\[([\d,]*)\]")


def _prod(dims: list[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _build_symtab(lines: list[str]) -> dict[str, list[int]]:
    """instruction name → output dims (scalar/tuple outputs skipped)."""
    tab: dict[str, list[int]] = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            tab[m.group(1)] = [int(x) for x in m.group(3).split(",") if x]
    return tab


def dot_flops_line(line: str, symtab: dict[str, list[int]] | None = None
                   ) -> float:
    """2·(output elements)·(contraction size); operands are shapeless
    references, so the lhs shape comes from the computation's symtab."""
    mo = re.search(r"=\s*(?:\()?\w+\[([\d,]*)\]", line)
    if not mo:
        return 0.0
    out = 1
    for d in mo.group(1).split(","):
        if d:
            out *= int(d)
    k = 1
    mk = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    lhs_dims: list[int] | None = None
    ops = re.search(r"\bdot\(([^)]*)\)", line)
    if ops and symtab is not None:
        names = [o.strip().lstrip("%") for o in ops.group(1).split(",")]
        if names and names[0] in symtab:
            lhs_dims = symtab[names[0]]
    if lhs_dims is None:  # inline-shaped operands (older dialects)
        shapes = _SHAPE_RE.findall(line[line.find("dot("):])
        if shapes:
            lhs_dims = [int(x) for x in shapes[0][1].split(",") if x]
    if lhs_dims and mk and mk.group(1):
        for ci in mk.group(1).split(","):
            if ci and int(ci) < len(lhs_dims):
                k *= lhs_dims[int(ci)]
    return 2.0 * out * k


_OPCODE_RE = re.compile(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9\-]*)\(")


def op_counts(hlo_text: str) -> dict[str, int]:
    """Opcode → instruction count across every computation.

    Used by the perf-regression guards: the gather-routed convert program
    must contain zero ``scatter`` ops (tests/test_perf_paths.py) — a
    scatter reappearing in the lowered HLO means a ``.at[].set`` crept back
    into the Ordering/Reshaping spine.
    """
    counts: dict[str, int] = defaultdict(int)
    for lines in _split_computations(hlo_text).values():
        for line in lines:
            m = _OPCODE_RE.search(line)
            if m:
                counts[m.group(1)] += 1
    return dict(counts)


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def op_scope(op_name: str, scopes=DEVICE_SCOPES) -> str | None:
    """The scope among ``scopes`` on an ``op_name`` path, or None.

    A path component names a scope bare or as the argument of the
    transforms around it: ``jit(step)/vmap(sample.select)/while`` carries
    ``sample.select``. The program's scopes never nest, so a path that
    carries two of them raises ValueError.

    >>> op_scope("jit(f)/vmap(serve.forward)/while/body/add")
    'serve.forward'
    >>> op_scope("jit(f)/reduce_sum") is None
    True
    """
    found = []
    for part in op_name.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.find("(") + 1:-1]
        if part in scopes and part not in found:
            found.append(part)
    if len(found) > 1:
        raise ValueError(f"op_name {op_name!r} carries the scopes {found}")
    return found[0] if found else None


def op_scopes(hlo_text: str, scopes=DEVICE_SCOPES) -> dict[str, str | None]:
    """Instruction name → the scope among ``scopes`` its ``op_name``
    metadata carries (None: unscoped), for every instruction of every
    computation of a compiled program's text. Profiler op events are
    named ``%<instruction> = ...``, so the map joins them to the trace."""
    out: dict[str, str | None] = {}
    for lines in _split_computations(hlo_text).values():
        for line in lines:
            m = _INSTR_RE.match(line)
            if m:
                n = _OP_NAME_RE.search(line)
                out[m.group(1)] = op_scope(n.group(1), scopes) if n else None
    return out


def loop_aware_stats(hlo_text: str) -> LoopAwareStats:
    comps = _split_computations(hlo_text)
    calls, mult = _call_graph(comps)
    # fusion computations are "internal" — their outputs don't hit HBM;
    # only count top-level materialized buffers. A computation is internal
    # if it's reached via calls/to_apply (not while bodies).
    fusion_internal = set()
    for name, lst in calls.items():
        for callee, m in lst:
            # while bodies materialize via the loop carry; everything else
            # (fusions, reducers) is internal
            pass
    internal = set()
    for name, lines in comps.items():
        for line in lines:
            for m in _CALLS_RE.finditer(line):
                internal.add(m.group(1))

    dot_flops = 0.0
    hbm = 0.0
    transc = 0.0
    flash_tile = 0.0
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        is_internal = name in internal
        symtab = _build_symtab(lines)
        for line in lines:
            opm = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z\-]+)\(", line)
            op = opm.group(1) if opm else None
            if " dot(" in line:
                dot_flops += dot_flops_line(line, symtab) * m
            if op in _TRANSC_OPS and not is_internal:
                mo = re.search(r"=\s*(?:\()?\w+\[([\d,]*)\]", line)
                if mo:
                    n = 1
                    for d in mo.group(1).split(","):
                        if d:
                            n *= int(d)
                    transc += n * m
            if is_internal or op in _SKIP_OPS or op is None:
                continue
            head = line[line.find("="):line.find(op + "(")]
            b = sum(_shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(head))
            # in-place update patterns (dynamic-update-slice, and fusions
            # rooted in one) write only the updated slice, not the carried
            # buffer: subtract the passthrough operand (same dims as out).
            if b and (op == "dynamic-update-slice"
                      or (op == "fusion" and "update-slice" in line)):
                shapes = _SHAPE_RE.findall(head)
                out_dims = ([int(x) for x in shapes[0][1].split(",") if x]
                            if len(shapes) == 1 else None)
                ops_m = re.search(r"\b" + op + r"\(([^)]*)\)", line)
                if out_dims and ops_m:
                    out_elems = max(1, _prod(out_dims))
                    bpe = b / out_elems
                    names = [o.strip().lstrip("%")
                             for o in ops_m.group(1).split(",")]
                    if any(symtab.get(nm) == out_dims for nm in names):
                        upd = sum(_prod(symtab[nm]) for nm in names
                                  if nm in symtab
                                  and symtab[nm] != out_dims)
                        b = min(b, max(upd, out_elems // 64) * bpe)
            hbm += 2.0 * b * m  # write + downstream read proxy
            if "flash_tile" in line:
                flash_tile += 2.0 * b * m
    return LoopAwareStats(dot_flops, hbm, transc, flash_tile)


def collective_bytes(hlo_text: str) -> CollectiveStats:
    comps = _split_computations(hlo_text)
    local: dict[str, list[tuple[str, float]]] = {n: [] for n in comps}
    calls: dict[str, list[tuple[str, int]]] = defaultdict(list)

    for name, lines in comps.items():
        for line in lines:
            col = _line_collective(line)
            if col:
                local[name].append(col)
            if _WHILE_RE.search(line):
                bm = _BODY_RE.search(line)
                cm = _COND_RE.search(line)
                if bm and bm.group(1) in comps:
                    tc = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                    calls[name].append((bm.group(1), tc))
            else:
                for m in _CALLS_RE.finditer(line):
                    if m.group(1) in comps and m.group(1) != name:
                        calls[name].append((m.group(1), 1))
                bm = _BRANCHES_RE.search(line)
                if bm:
                    for c in re.split(r",\s*", bm.group(1)):
                        c = c.strip().lstrip("%")
                        if c in comps and c != name:
                            calls[name].append((c, 1))

    memo: dict[str, dict] = {}

    def agg(name: str, seen: frozenset) -> dict:
        if name in memo:
            return memo[name]
        if name in seen:
            return {}
        out: dict[str, float] = defaultdict(float)
        for kind, b in local[name]:
            out[kind] += b
        for callee, mult in calls.get(name, []):
            for k, v in agg(callee, seen | {name}).items():
                out[k] += v * mult
        memo[name] = dict(out)
        return memo[name]

    called = {c for lst in calls.values() for c, _ in lst}
    entries = [n for n in comps if n not in called]
    totals: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for e in entries or list(comps):
        for k, v in agg(e, frozenset()).items():
            totals[k] += v
    for name in comps:
        for kind, _ in local[name]:
            counts[kind] += 1
    return CollectiveStats(dict(totals), dict(counts))
