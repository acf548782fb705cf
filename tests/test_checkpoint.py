"""Atomic pytree checkpoints: round trip, LATEST pointer, crash safety."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint


def _state():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "opt": {"mu": jnp.ones((3, 4)), "step": jnp.asarray(7)},
    }


def test_checkpoint_roundtrip_and_latest():
    with tempfile.TemporaryDirectory() as d:
        s = _state()
        save_checkpoint(d, 3, s, extra={"data_step": 3})
        save_checkpoint(d, 9, jax.tree.map(lambda x: x + 1, s),
                        extra={"data_step": 9})
        flat, man = load_checkpoint(d)
        assert man["step"] == 9 and man["extra"]["data_step"] == 9
        assert sorted(flat) == ["opt/mu", "opt/step", "params/w"]
        np.testing.assert_array_equal(flat["params/w"],
                                      np.asarray(s["params"]["w"]) + 1)
        np.testing.assert_array_equal(flat["opt/mu"], np.full((3, 4), 2.0))
        assert flat["opt/step"] == 8
        assert {k: str(v.dtype) for k, v in flat.items()} == man["dtypes"]


def test_checkpoint_crash_leaves_no_partial_latest():
    """A stale .tmp_ dir (simulated crash) must not be visible to restore."""
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, _state())
        os.makedirs(os.path.join(d, ".tmp_step_000000002"))
        with open(os.path.join(d, ".tmp_step_000000002", "arrays.npz"), "w") as f:
            f.write("garbage")
        flat, man = load_checkpoint(d)
        assert man["step"] == 1
