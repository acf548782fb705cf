"""Per-collective attribution for a cell: (op kind, result shape, trip mult,
computation) sorted by per-device bytes.  The §Perf hypothesis generator."""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import re
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_arch
from repro.launch.builders import build_cell
from repro.launch.mesh import make_production_mesh
import repro.roofline.hlo_cost as hc


def diag(arch_id, shape_id, top=20):
    arch = get_arch(arch_id)
    cell = arch.cells[shape_id]
    mesh = make_production_mesh(multi_pod=False)
    with jax.set_mesh(mesh):
        dr = build_cell(arch, cell, mesh)
        c = jax.jit(dr.fn, in_shardings=dr.in_shardings,
                    out_shardings=dr.out_shardings).lower(*dr.args).compile()
    txt = c.as_text()
    comps = hc._parse_module(txt)
    entry = [x for x in comps.values() if x.is_entry][0]

    rows = []

    def visit(name, mult):
        comp = comps.get(name)
        if comp is None:
            return
        for op in comp.ops:
            base = op.opcode.replace("-start", "").replace("-done", "")
            if base in hc._COLL_OPS and not op.opcode.endswith("-done"):
                _, b = hc._shape_elems_bytes(op.shape_str)
                # source op metadata tells us which model op caused it
                meta = re.search(r'op_name="([^"]*)"', op.rest)
                rows.append((b * mult, base, op.shape_str[:60], mult,
                             (meta.group(1) if meta else "?")[:90]))
            if op.opcode == "while":
                t = hc._TRIP.search(op.rest)
                trip = float(t.group(1)) if t else 1.0
                m = re.search(r"body=%?([\w.\-]+)", op.rest)
                if m:
                    visit(m.group(1), mult * trip)
            elif op.opcode == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", op.rest)
                if m:
                    visit(m.group(1), mult)

    visit(entry.name, 1.0)
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"\n### {arch_id}:{shape_id} — {total/1e9:.1f} GB/dev collectives, "
          f"{len(rows)} sites")
    for b, kind, shape, mult, meta in rows[:top]:
        print(f"{b/1e9:9.2f} GB  x{mult:<6.0f} {kind:<18} {shape:<45} {meta}")


if __name__ == "__main__":
    diag(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 20)
