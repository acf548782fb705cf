"""Distributed APSP.  Multi-device tests run in subprocesses because the
fake-device XLA flag must precede jax init."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_distributed_apsp_all_methods_both_meshes():
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core.distributed import apsp_distributed
        from repro.core.graphgen import generate_np

        def np_fw(h):
            d = h.copy()
            for k in range(d.shape[0]):
                d = np.minimum(d, d[:, k][:, None] + d[k, :][None, :])
            return d

        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        rng = np.random.default_rng(3)
        g = generate_np(rng, 48)
        ref = np_fw(g.h)
        for mesh, mp in ((mesh1, False), (mesh2, True)):
            for method in ("squaring", "fw", "rkleene"):
                out = np.asarray(apsp_distributed(
                    jnp.asarray(g.h), mesh=mesh, method=method,
                    multi_pod=mp, block_size=4))
                assert np.allclose(out, ref, equal_nan=True), (method, mp)
        print("DIST_OK")
    """)
    assert "DIST_OK" in out


@pytest.mark.slow
def test_summa_minplus_matches_local():
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core.distributed import summa_minplus
        from repro.core.semiring import minplus

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = np.where(rng.uniform(size=(32, 32)) < .3, np.inf,
                     rng.uniform(1, 9, (32, 32))).astype(np.float32)
        z = summa_minplus(jnp.asarray(x), jnp.asarray(x), mesh=mesh)
        zr = minplus(jnp.asarray(x), jnp.asarray(x))
        assert np.allclose(np.asarray(z), np.asarray(zr), equal_nan=True)
        print("SUMMA_OK")
    """)
    assert "SUMMA_OK" in out
