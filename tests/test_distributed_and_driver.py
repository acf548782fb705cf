"""Distributed APSP + train-driver fault tolerance.  Multi-device tests run
in subprocesses because the fake-device XLA flag must precede jax init."""

import os
import subprocess
import sys
import tempfile
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


@pytest.mark.slow
def test_compressed_train_step_tracks_plain():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.optim import make_optimizer, warmup_cosine
        from repro.train import (init_train_state, make_train_step,
                                 make_compressed_train_step)
        from repro.models.transformer import LMConfig, init_lm, loss_fn

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        base = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                    vocab=61, param_dtype=jnp.float32,
                    compute_dtype=jnp.float32, attn_chunk=8)
        cfg_c = LMConfig(name="t", batch_axes=("data",), **base)
        cfg_p = LMConfig(name="t", batch_axes=("pod", "data"), **base)
        params, _ = init_lm(jax.random.PRNGKey(0), cfg_c)
        opt = make_optimizer("adamw", warmup_cosine(1e-3, 10, 100))
        step_c = make_compressed_train_step(
            lambda p, b: loss_fn(p, b, cfg_c), opt, mesh,
            lambda b: {"tokens": P("pod"), "labels": P("pod")})
        step_p = make_train_step(lambda p, b: loss_fn(p, b, cfg_p), opt)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 61)
        batch = {"tokens": toks, "labels": toks}
        with jax.set_mesh(mesh):
            bsh = jax.device_put(batch, NamedSharding(mesh, P(("pod","data"), None)))
            s1 = init_train_state(params, opt, n_pods=2)
            s2 = init_train_state(params, opt)
            for _ in range(4):
                s1, m1 = jax.jit(step_c)(s1, bsh)
                s2, m2 = jax.jit(step_p)(s2, bsh)
        d = abs(float(m1["total"]) - float(m2["total"]))
        assert d < 0.05, d
        print("COMPRESS_OK", d)
    """)
    assert "COMPRESS_OK" in out


@pytest.mark.slow
def test_train_driver_checkpoint_resume():
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        r1 = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--arch", "gcn-cora",
             "--steps", "6", "--ckpt-dir", d, "--ckpt-every", "3",
             "--log-every", "3"],
            capture_output=True, text=True, timeout=300, env=env)
        assert r1.returncode == 0, r1.stderr[-2000:]
        r2 = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--arch", "gcn-cora",
             "--steps", "9", "--ckpt-dir", d, "--ckpt-every", "3",
             "--log-every", "3"],
            capture_output=True, text=True, timeout=300, env=env)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "[resume] restored step 6" in r2.stdout


@pytest.mark.slow
def test_elastic_restore_onto_different_mesh():
    """512-chip-state -> 8-fake-device mesh restore (elastic restart)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save_checkpoint, load_checkpoint, restore_onto_mesh
        from repro.sharding import make_shardings

        state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, state)
            flat, _ = load_checkpoint(d)
            mesh = jax.make_mesh((4, 2), ("data", "model"))
            sh = make_shardings(mesh, {"w": P("data", "model")})
            example = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
            restored = restore_onto_mesh(flat, example, sh)
            assert restored["w"].sharding.spec == P("data", "model")
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(state["w"]))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
