"""Developer CLI: loop-aware per-op inspection of a compiled cell's HLO.

A thin front-end over `launch/hlo_analysis.py` — the computation split,
trip-count math, call-graph walk, dot-FLOP and collective accounting all
live there (shared with the `repro.analysis` contract checker); this module
only builds the cell, compiles it, and pretty-prints ranked rows.

PYTHONPATH=src python -m repro.launch.hlo_inspect --arch X --shape Y \
    [--mesh single] [--top 15] [--collectives] [--dump out.txt]
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
import argparse
import re

import jax

from repro.launch.hlo_analysis import (_build_symtab, _line_collective,
                                       build_call_graph, dot_flops_line)


def analyze_collectives(hlo, top=15):
    """Biggest collective ops, loop-weighted."""
    comps, _, mult = build_call_graph(hlo)
    rows = []
    for name, lines in comps.items():
        m = mult.get(name, 1)
        for line in lines:
            col = _line_collective(line)
            if col:
                rows.append((col[1] * m, col[1], m, col[0],
                             line.strip()[:130]))
    rows.sort(key=lambda r: -r[0])
    total = sum(r[0] for r in rows)
    print(f"total collective operand bytes: {total:.3e}")
    for tot, b, m, kind, line in rows[:top]:
        print(f"tot={tot/1e9:8.1f}GB b={b/1e9:6.2f}GB x{m:<5} {kind:18} "
              f"{line[:85]}")


def analyze(hlo, top=15):
    comps, _, mult = build_call_graph(hlo)
    rows = []
    dot_total = 0.0
    for name, lines in comps.items():
        symtab = _build_symtab(lines)
        m = mult.get(name, 1)
        for line in lines:
            mo = re.search(r"%[\w.\-]+ = (?:\()?(\w+)\[([\d,]*)\]", line)
            if not mo:
                continue
            out = 1
            for d in mo.group(2).split(","):
                if d:
                    out *= int(d)
            opm = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z\-]+)\(", line)
            op = opm.group(1) if opm else "?"
            if " dot(" in line:
                dot_total += dot_flops_line(line, symtab) * m
            if op in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "constant", "copy"):
                continue
            rows.append((out * m, out, m, op, line.strip()[:120]))
    rows.sort(key=lambda r: -r[0])
    print(f"loop-aware dot FLOPs: {dot_total:.3e}")
    for tot, out, m, op, line in rows[:top]:
        print(f"tot={tot:.2e} x{m:<4} {op:24} {line[:100]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--dump", help="write HLO text to this path")
    ap.add_argument("--collectives", action="store_true")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell
    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    cell = build_cell(args.arch, args.shape, mesh)
    with jax.set_mesh(mesh):
        # repro: allow-raw-jit — one-shot CLI compile for inspection, not a
        # hot path; nothing caches or re-dispatches this jit.
        comp = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       donate_argnums=cell.donate_argnums
                       ).lower(*cell.args).compile()
    cost = comp.cost_analysis()
    print("cost_analysis flops:", cost.get("flops"))
    print("cost_analysis bytes:", cost.get("bytes accessed"))
    hlo = comp.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(hlo)
    if args.collectives:
        analyze_collectives(hlo, args.top)
    else:
        analyze(hlo, args.top)


if __name__ == "__main__":
    main()
