"""Train-step factories: grads -> clip -> optimizer, with optional
microbatch accumulation and optional int8 cross-pod gradient compression.

``make_train_step`` is mesh-agnostic (GSPMD handles every axis).
``make_compressed_train_step`` makes the ``pod`` axis *manual* via a
partial-manual shard_map: each pod computes grads on its pod-local batch
(data/model stay auto/GSPMD inside), then the gradients cross the slow
pod-to-pod wire as int8 with per-pod error feedback — the distributed-
optimization trick for DCN-connected pods.  The error-feedback residual is
part of TrainState (leading n_pods dim, sharded P("pod")) so it checkpoints
and restores like everything else.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim import clip_by_global_norm
from repro.optim.compression import compressed_psum

__all__ = [
    "TrainState",
    "init_train_state",
    "train_state_specs",
    "make_train_step",
    "make_compressed_train_step",
]


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array
    err: Any = None          # int8-EF residuals (n_pods, ...) or None


def init_train_state(params, optimizer, *, n_pods: Optional[int] = None) -> TrainState:
    err = None
    if n_pods:
        err = jax.tree.map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params
        )
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        err=err,
    )


def train_state_specs(param_specs, optimizer, *, compressed: bool = False):
    err_specs = None
    if compressed:
        err_specs = jax.tree.map(
            lambda s: P("pod", *tuple(s)),
            param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    return TrainState(
        params=param_specs,
        opt_state=optimizer.state_specs(param_specs),
        step=P(),
        err=err_specs,
    )


def _constrain_like(tree, specs):
    """Constrain a grad pytree to the params' PartitionSpecs (reduce-scatter
    instead of all-reduce at every microbatch boundary; keeps the f32 grad
    accumulator sharded — §Perf llama3 train: 2 x 12.8 TB/step of replicated
    f32 grad all-reduces became 1/256-sized reduce-scatters)."""
    if specs is None:
        return tree
    from repro.models.layers import constrain

    return jax.tree.map(
        lambda g, s: constrain(g, s), tree, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _accumulate_grads(loss_fn, params, batch, microbatches: int, param_specs=None):
    """lax.scan over microbatch slices; returns (loss, metrics, grads)."""

    def resh(x):
        if x.ndim == 0:
            return jnp.broadcast_to(x, (microbatches,))
        b = x.shape[0]
        assert b % microbatches == 0, (b, microbatches)
        return x.reshape((microbatches, b // microbatches) + x.shape[1:])

    mb = jax.tree.map(resh, batch)
    gz = _constrain_like(
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        param_specs,
    )

    def body(carry, b):
        gacc, lacc = carry
        (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
        g = _constrain_like(g, param_specs)
        gacc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), gacc, g)
        gacc = _constrain_like(gacc, param_specs)
        return (gacc, lacc + l), m

    (grads, loss), ms = jax.lax.scan(body, (gz, 0.0), mb)
    grads = jax.tree.map(lambda g: g / microbatches, grads)
    metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)
    return loss / microbatches, metrics, grads


def make_train_step(
    loss_fn: Callable,            # (params, batch) -> (loss, metrics)
    optimizer,
    *,
    microbatches: Optional[int] = None,
    clip_norm: float = 1.0,
    param_specs=None,             # grads constrained to these (ZeRO-friendly)
) -> Callable:
    def train_step(state: TrainState, batch) -> tuple:
        if microbatches and microbatches > 1:
            loss, metrics, grads = _accumulate_grads(
                loss_fn, state.params, batch, microbatches,
                param_specs=param_specs,
            )
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch
            )
            grads = _constrain_like(grads, param_specs)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1,
                       err=state.err),
            metrics,
        )

    return train_step


def make_compressed_train_step(
    loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    batch_spec_fn: Callable,      # batch pytree -> spec pytree (pod-leading)
    *,
    clip_norm: float = 1.0,
) -> Callable:
    """int8 error-feedback cross-pod gradient reduction (manual pod axis)."""
    assert "pod" in mesh.axis_names, "compressed step needs a pod axis"

    def train_step(state: TrainState, batch) -> tuple:
        def pod_body(params, err, b):
            # err arrives as (1, ...) pod-local block
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)

            def red(gl, el):
                r, e = compressed_psum(gl, el[0], "pod")
                return r, e[None]

            out = jax.tree.map(red, g, err)
            g = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
            err = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
            l = jax.lax.pmean(l, "pod")
            m = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), m)
            return l, m, g, err

        param_specs_pod = jax.tree.map(lambda _: P(), state.params)
        err_specs = jax.tree.map(lambda _: P("pod"), state.err)
        fn = jax.shard_map(
            pod_body,
            mesh=mesh,
            in_specs=(param_specs_pod, err_specs, batch_spec_fn(batch)),
            out_specs=(P(), P(), param_specs_pod, err_specs),  # P() prefixes broadcast
            axis_names={"pod"},
            check_vma=False,
        )
        loss, metrics, grads, err = fn(state.params, state.err, batch)

        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1, err=err),
            metrics,
        )

    return train_step
