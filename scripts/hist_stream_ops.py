#!/usr/bin/env python3
"""Which ops of the compiled GBT chain program write an array the size of a
row block's bins one-hot, and which of its gathers read one element a row?

  python scripts/hist_stream_ops.py [--program chain|ensemble --rows N
                                     --cols D --bins B --depth L --chains S
                                     --rounds R --trees T --goss 0|1 --f32
                                     --min-elems E --out FILE]

Compiles ``_gbt_chain_rounds_jit`` for the given shapes (the defaults are
one launch of the ``dense500-xgb`` cell: 166,667 fold rows x 500 columns,
32 bins, depth 10, two GOSS chains, eight rounds, bf16 operands) and walks
the OPTIMISED HLO: every instruction outside a fused computation whose
result has at least ``rows_block x bins x cols`` elements (``rows_block`` =
the rows a tree sees, at most ``ROW_BLOCK``) is printed with its opcode,
shape and layout, the opcodes fused into it, and the ``op_name`` of its
metadata.  The histogram's bins one-hot is (rows_block,
bins x cols): it has to be written once and read by the dot once, so the
listing should hold its producer and nothing else of that size in
``tree.hist`` -- no ``reshape``, ``copy`` or ``transpose`` (PERF.md §6,
PR 29).  Nothing runs: on a machine with a TPU the program is compiled for
it, elsewhere for a DESCRIBED v5e (the TPU compiler is installed with JAX;
on-chip-measurement guide §2), and the first line says which.  The last
line is one JSON object with the counts.

Since PR 31 it also lists every ``gather`` of the program, fused or not,
with its slice sizes and the elements of its result.  ``element_gathers``
counts those whose slices are single elements and whose result has at least
``rows`` of them, the launch's rows: walking ALL rows a row at a time
(``feat[heap]``, ``thresh[heap]``, ``binned[row, f]``, ``leaf[node]``): 4 in
the chain program (the margin update's loop) and 4 in ``predict_ensemble``
before PR 31, none since.  ``element_gathers_of_tree_rows`` lowers the
threshold to the rows ONE tree sees (under GOSS two fifths of them), which
takes in growth's own routing, three a level: 34 before PR 31; the 2 left
are GOSS's own ``g[idx]`` and ``h[idx]``, no routing.  What else is
left moves whole rows (``binned[idx]``, ``binned_T[fid]``) or reads a
level's few slots.
``--program ensemble`` compiles ``predict_ensemble`` instead (the defaults
then: 250,000 rows, 8 trees: one scoring call of the cell).
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: opcodes that only relabel memory (no bytes move)
FREE = {"bitcast", "get-tuple-element", "parameter"}
#: opcodes that ARE a relayout when they stand outside a fusion
RELAYOUT = {"reshape", "copy", "transpose"}

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\](?P<layout>\{[^}]*\})?\s+"
    r"(?P<opcode>[\w\-]+)\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*"
                          r"\(.*\)\s*->.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_SLICE_SIZES = re.compile(r"slice_sizes=\{([\d,]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def computations(hlo: str):
    """``{computation name: [instruction lines]}`` of an HLO module's text."""
    out, cur = {}, None
    for line in hlo.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group("name")
                out[cur] = []
        elif line.strip() == "}":
            cur = None
        else:
            out[cur].append(line)
    return out


def big_ops(hlo: str, min_elems: int):
    """One dict per instruction OUTSIDE a fused computation whose array
    result has at least ``min_elems`` elements."""
    comps = computations(hlo)
    fused = set()
    for lines in comps.values():
        for line in lines:
            if " fusion(" in line:
                fused.update(_CALLS.findall(line))
    rows = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            dims = [int(x) for x in m.group("dims").split(",") if x]
            elems = math.prod(dims) if dims else 1
            if elems < min_elems:
                continue
            opcode = m.group("opcode")
            inner = []
            if opcode == "fusion":
                for callee in _CALLS.findall(line):
                    for fl in comps.get(callee, []):
                        fm = _INSTR.match(fl)
                        if fm and fm.group("opcode") not in (
                                "parameter", "constant", "bitcast",
                                "broadcast", "iota"):
                            inner.append(fm.group("opcode"))
            op_name = _OP_NAME.search(line)
            rows.append({
                "name": m.group("name"), "opcode": opcode,
                "shape": f"{m.group('dtype')}[{m.group('dims')}]",
                "layout": m.group("layout") or "",
                "fused": sorted(set(inner)),
                "op_name": op_name.group(1) if op_name else ""})
    return rows


def gathers(hlo: str):
    """One dict per ``gather`` of the module, fused computations included:
    result shape, its elements, the slice sizes and the ``op_name``."""
    rows = []
    for lines in computations(hlo).values():
        for line in lines:
            m = _INSTR.match(line)
            if not m or m.group("opcode") != "gather":
                continue
            dims = [int(x) for x in m.group("dims").split(",") if x]
            sizes = _SLICE_SIZES.search(line)
            op_name = _OP_NAME.search(line)
            rows.append({
                "name": m.group("name"),
                "shape": f"{m.group('dtype')}[{m.group('dims')}]",
                "elems": math.prod(dims) if dims else 1,
                "slice_sizes": [int(x) for x in sizes.group(1).split(",")
                                if x] if sizes else [],
                "op_name": op_name.group(1) if op_name else ""})
    return rows


def _device():
    """``(device to compile for, where that is)``."""
    import jax

    if jax.default_backend() == "tpu":
        dev = jax.devices()[0]
        return dev, f"attached {dev.device_kind}"
    from jax.experimental import topologies

    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    return dev, f"described {dev.device_kind} (no chip attached)"


def compile_ensemble_program(rows: int, cols: int, depth: int, trees: int):
    """``(optimised HLO text, temporary bytes, where it was compiled for)``
    of one ``predict_ensemble`` call, from shapes alone."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import gbdt_kernels as gk

    dev, where = _device()
    sh = jax.sharding.SingleDeviceSharding(dev)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    nodes = 2 ** depth - 1
    compiled = gk.predict_ensemble.lower(
        arr((rows, cols), jnp.int8), arr((trees, nodes), jnp.int32),
        arr((trees, nodes), jnp.int32),
        arr((trees, nodes + 1, 1), jnp.float32), depth).compile()
    temp = getattr(compiled.memory_analysis(), "temp_size_in_bytes", None)
    return compiled.as_text(), temp, where


def compile_chain_program(rows: int, cols: int, bins: int, depth: int,
                          chains: int, rounds: int, goss: bool, bf16: bool):
    """``(optimised HLO text, the program's temporary bytes, rows a tree
    sees, where it was compiled for)`` of one ``_gbt_chain_rounds_jit``
    launch, from shapes alone."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import gbdt_kernels as gk

    dev, where = _device()
    sh = jax.sharding.SingleDeviceSharding(dev)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    S, f32, i32 = chains, jnp.float32, jnp.int32
    plan = None
    if goss:
        k_top = max(1, int(round(gk.GOSS_TOP_FRAC * rows)))
        k_rest = max(1, int(round(gk.GOSS_REST_FRAC * rows)))
        plan = (k_top, k_rest)
    tree_rows = sum(plan) if plan else rows
    vec = arr((S,), f32)
    lowered = gk._gbt_chain_rounds_jit.lower(
        arr((rows, cols), jnp.int8), arr((rows,), f32), arr((S, rows), f32),
        arr((S, rows), f32), arr((1,), i32), arr((S,), i32), vec, vec, vec,
        vec, vec, vec, rounds, depth, bins, "binary", bf16, False,
        skip_counts=True, goss=plan, goss_seed=arr((), i32),
        chain_ids=arr((S,), i32), round_offset=arr((), i32))
    compiled = lowered.compile()
    temp = getattr(compiled.memory_analysis(), "temp_size_in_bytes", None)
    return compiled.as_text(), temp, tree_rows, where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", default="chain",
                    choices=("chain", "ensemble"))
    ap.add_argument("--rows", type=int, default=None,
                    help="default: 166,667 (chain), 250,000 (ensemble)")
    ap.add_argument("--trees", type=int, default=8,
                    help="trees of the ensemble program")
    ap.add_argument("--cols", type=int, default=500)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--depth", type=int, default=10)
    ap.add_argument("--chains", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--goss", type=int, default=1, choices=(0, 1))
    ap.add_argument("--f32", action="store_true",
                    help="f32 histogram operands (the CPU's form)")
    ap.add_argument("--min-elems", type=int, default=None,
                    help="default: rows_block x bins x cols")
    ap.add_argument("--out", default=None,
                    help="also write the optimised HLO text here")
    a = ap.parse_args(argv)

    if a.program == "ensemble":
        a.rows = a.rows or 250_000
        hlo, temp_bytes, where = compile_ensemble_program(
            a.rows, a.cols, a.depth, a.trees)
        tree_rows = a.rows
    else:
        a.rows = a.rows or 166_667
        hlo, temp_bytes, tree_rows, where = compile_chain_program(
            a.rows, a.cols, a.bins, a.depth, a.chains, a.rounds,
            bool(a.goss), not a.f32)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(hlo)
    from transmogrifai_tpu.models.gbdt_kernels import ROW_BLOCK

    rows_block = min(tree_rows, ROW_BLOCK)
    min_elems = a.min_elems or rows_block * a.bins * a.cols
    ops = big_ops(hlo, min_elems)
    print(f"[hist_stream_ops] compiled for: {where}; program={a.program} "
          f"rows={a.rows} "
          f"tree_rows={tree_rows} rows_block={rows_block} cols={a.cols} "
          f"bins={a.bins} depth={a.depth} chains={a.chains} "
          f"rounds={a.rounds} goss={a.goss} "
          f"operands={'f32' if a.f32 else 'bf16'} min_elems={min_elems}")
    for o in ops:
        fused = f" fused=[{','.join(o['fused'])}]" if o["fused"] else ""
        print(f"{o['opcode']:<10} {o['name']:<28} {o['shape']}{o['layout']}"
              f"{fused}  op_name={o['op_name']}")
    all_gathers = gathers(hlo)
    for g in all_gathers:
        print(f"gather     {g['name']:<28} {g['shape']} "
              f"slice_sizes={g['slice_sizes']} elems={g['elems']}  "
              f"op_name={g['op_name']}")
    single = [g for g in all_gathers
              if all(x == 1 for x in g["slice_sizes"])]
    element = [g for g in single if g["elems"] >= a.rows]
    of_tree_rows = [g for g in single if g["elems"] >= tree_rows]
    moving = [o for o in ops if o["opcode"] not in FREE]
    in_hist = [o for o in moving if "tree.hist" in o["op_name"]]
    relayout = [o for o in in_hist if o["opcode"] in RELAYOUT]
    by_opcode = dict(collections.Counter(o["opcode"] for o in moving))
    print(json.dumps({
        "compiled_for": where, "min_elems": min_elems,
        "program_temp_bytes": temp_bytes,
        "ops_listed": len(ops), "ops_that_move_bytes": len(moving),
        "by_opcode": by_opcode, "in_tree_hist": len(in_hist),
        "relayouts_in_tree_hist": len(relayout),
        "relayout_names": [o["name"] for o in relayout],
        "gathers": len(all_gathers), "element_gathers": len(element),
        "element_gather_names": [g["name"] for g in element],
        "element_gathers_of_tree_rows": len(of_tree_rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
