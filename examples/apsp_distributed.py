"""The paper's future-work item, live: multi-device APSP on a fake 8-device
mesh (the same shard_map code a real multi-chip mesh runs).

    PYTHONPATH=src python examples/apsp_distributed.py
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import apsp_distributed
from repro.core.graphgen import generate_np


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh: 2 pods x (2 data x 2 model) = {mesh.size} devices")

    g = generate_np(np.random.default_rng(0), 256, rho=50.0)
    print(f"graph: {g.n_nodes} nodes, {g.n_edges} edges")

    ref = g.h.copy()
    for k in range(g.n_nodes):
        ref = np.minimum(ref, ref[:, k][:, None] + ref[k, :][None, :])

    for method in ("squaring", "fw", "rkleene"):
        t0 = time.time()
        out = np.asarray(apsp_distributed(
            jnp.asarray(g.h), mesh=mesh, method=method, multi_pod=True,
            block_size=32))
        ok = np.allclose(out, ref, equal_nan=True)
        print(f"{method:>9}: {time.time()-t0:5.2f}s  "
              f"{'matches single-device oracle ✓' if ok else 'MISMATCH ✗'}")
        assert ok


if __name__ == "__main__":
    main()
