import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production meshes and record memory/cost/collective analyses.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch deepfm --shape train_batch
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--extra]
#
# Outputs one JSON per cell under experiments/dryrun/ — consumed by
# benchmarks/roofline.py (EXPERIMENTS.md §Dry-run / §Roofline).
# (module docstring intentionally a comment: the XLA_FLAGS lines above must
# stay the first statements, and __future__ imports must lead the file.)

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import registry
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_OP_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=(?:\{\{([^}]*)\}|\[(\d+),(\d+)\]<=)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str):
    """Per-device wire-byte estimate per collective (ring algorithm model)."""
    totals = {op: 0.0 for op in COLLECTIVES}
    counts = {op: 0 for op in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        tuple_body, dtype, dims, op = m.groups()
        if tuple_body is not None:
            out_bytes = sum(_shape_bytes(dt, dm)
                            for dt, dm in _SHAPE_RE.findall(tuple_body))
        else:
            out_bytes = _shape_bytes(dtype, dims)
        g = _GROUPS_RE.search(line)
        group = 1
        if g:
            if g.group(1) is not None:
                # explicit form {{0,1,...},{...}}: first group's member count
                group = len([x for x in g.group(1).split(",") if x.strip()])
            else:
                # iota form [n_groups,group_size]<=[n_devices]
                group = max(int(g.group(3)), 1)
        s = max(group, 2)
        ring = (s - 1) / s
        if op == "all-reduce":
            wire = 2 * ring * out_bytes
        elif op == "all-gather":
            wire = ring * out_bytes
        elif op == "reduce-scatter":
            wire = ring * out_bytes * s  # input is s x output
        elif op == "all-to-all":
            wire = ring * out_bytes
        else:  # collective-permute
            wire = out_bytes
        totals[op] += wire
        counts[op] += 1
    return {"wire_bytes_per_device": totals, "op_counts": counts,
            "total_wire_bytes_per_device": sum(totals.values())}


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    with jax.set_mesh(mesh):
        cell = registry.build_cell(arch, shape, mesh)
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # while-aware static cost walk (XLA cost_analysis counts loop bodies
    # once; scans make it useless — see repro/launch/hlo_cost.py)
    walk = analyze_hlo(hlo)
    coll = parse_collectives(hlo)
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "kind": cell.kind, "notes": cell.notes,
        "model_flops": cell.model_flops,
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "cost": {
            "flops_per_device": walk["flops"],
            "bytes_accessed_per_device": walk["bytes"],
            "xla_flops_per_device_loopbody_once": cost.get("flops", 0.0),
            "xla_bytes_per_device_loopbody_once": cost.get("bytes accessed", 0.0),
            "unknown_trip_loops": walk["unknown_trip_loops"],
        },
        "collectives": {
            "wire_bytes_per_device": walk["collective_ops"],
            "total_wire_bytes_per_device": walk["collective_wire_bytes"],
            "op_counts_loopbody_once": coll["op_counts"],
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    if save_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    print(f"[dryrun] OK {arch} x {shape} x {mesh_name}: "
          f"peak={record['memory']['peak_bytes_per_device']/2**30:.2f}GiB/dev "
          f"flops={record['cost']['flops_per_device']:.3e}/dev "
          f"wire={coll['total_wire_bytes_per_device']/2**20:.1f}MiB/dev "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--extra", action="store_true",
                    help="also run the paper-own CLAX cells")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    cells = (registry.list_cells(include_extra=args.extra) if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, args.out, save_hlo=args.save_hlo)
            except Exception as e:  # noqa: BLE001 - report and continue
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} x {shape} "
                      f"multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print(f"[dryrun] all {len(cells) * len(meshes)} cells compiled")


if __name__ == "__main__":
    main()
