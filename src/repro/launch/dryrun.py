import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell:
    jit(step, in_shardings, out_shardings).lower(*ShapeDtypeStructs).compile()
on the 16x16 single-pod mesh and the (2,16,16) multi-pod mesh, printing
memory_analysis() (fits/doesn't) and cost_analysis() (roofline terms).
Nothing is allocated — inputs are ShapeDtypeStructs, params abstract.

Results land in experiments/dryrun/<cell>__<mesh>.json for EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --all
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh both
    python -m repro.launch.dryrun --arch apsp --single-pod-only
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_arch
from repro.launch.builders import build_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline import HW, analyze_compiled

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch_id: str, shape_id: str, multi_pod: bool, *, save: bool = True,
             verbose: bool = True, skip_existing: bool = False) -> dict:
    arch = get_arch(arch_id)
    cell = arch.cells[shape_id]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch_id}:{shape_id}@{mesh_name}"

    if skip_existing:
        path = os.path.join(OUT_DIR, f"{arch_id}__{shape_id}__{mesh_name}.json")
        if os.path.exists(path):
            with open(path) as f:
                old = json.load(f)
            if old.get("status") in ("ok", "skipped"):
                if verbose:
                    print(f"[cached] {tag}: {old['status']}")
                return old

    if cell.skip_reason:
        rec = {"cell": tag, "status": "skipped", "reason": cell.skip_reason}
        if verbose:
            print(f"[skip] {tag}: {cell.skip_reason}")
        _save(rec, arch_id, shape_id, mesh_name, save)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            dr = build_cell(arch, cell, mesh)
            jitted = jax.jit(
                dr.fn,
                in_shardings=dr.in_shardings,
                out_shardings=dr.out_shardings,
                donate_argnums=dr.donate_argnums,
            )
            lowered = jitted.lower(*dr.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            peak = HW.PEAK_FLOPS_VPU if arch.family == "apsp" else None
            rep = analyze_compiled(dr.name, compiled, hlo, dr.model_flops,
                                   n_chips, peak_flops=peak)
            rec = {
                "cell": tag,
                "status": "ok",
                "note": dr.note,
                "mesh": list(mesh.shape.values()),
                "n_chips": n_chips,
                "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1),
                "memory": _mem_dict(mem),
                "roofline": rep.row(),
                "collectives": rep.coll_bytes,
            }
            if verbose:
                gb = rec["memory"].get("total_gb", float("nan"))
                r = rec["roofline"]
                print(
                    f"[ok]   {tag}  mem/dev={gb:.2f}GB  "
                    f"T(comp/mem/coll)=({r['t_compute_s']:.3e}/"
                    f"{r['t_memory_s']:.3e}/{r['t_collective_s']:.3e})s  "
                    f"bottleneck={r['bottleneck']}  "
                    f"useful={r['useful_flops_ratio']:.2f}  "
                    f"roofline={r['roofline_fraction']:.2f}"
                )
    except Exception as e:  # a failure here is a bug in the system
        rec = {"cell": tag, "status": "FAILED", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        if verbose:
            print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:300]}")
    _save(rec, arch_id, shape_id, mesh_name, save)
    return rec


def _mem_dict(mem) -> dict:
    try:
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
        d = {
            "args_gb": mem.argument_size_in_bytes / 1e9,
            "out_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": getattr(mem, "alias_size_in_bytes", 0) / 1e9,
            "total_gb": (total - getattr(mem, "alias_size_in_bytes", 0)) / 1e9,
        }
        return d
    except AttributeError:  # repro: allow-except-swallow  best-effort repr fallback, no slot state here
        return {"repr": str(mem)[:500]}


def _save(rec: dict, arch_id, shape_id, mesh_name, save: bool):
    if not save:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{arch_id}__{shape_id}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for aid in archs:
        arch = get_arch(aid)
        shapes = [args.shape] if args.shape else list(arch.cells)
        for sid in shapes:
            for mp in meshes:
                rec = run_cell(aid, sid, mp, skip_existing=args.skip_existing)
                st = rec["status"]
                n_ok += st == "ok"
                n_fail += st == "FAILED"
                n_skip += st == "skipped"
    print(f"\ndry-run done: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
