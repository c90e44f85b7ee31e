"""GPipe-style pipeline parallelism over a mesh axis (default: ``pod``).

The pod axis has the lowest bisection bandwidth of the production mesh and
pipeline parallelism the lowest communication volume per step (one activation
handoff per microbatch per stage boundary), so stages map onto pods.
Fill-drain schedule: T = n_micro + n_stages - 1 ticks; stage handoff is a
single ``ppermute`` (point-to-point, no collective fan-in).

``gpipe_apply`` is schedule-only (activations); the backward pass comes from
differentiating through it — JAX reverses the ppermutes automatically.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


PyTree = Any


def gpipe_apply(stage_fn: Callable[[PyTree, jax.Array], jax.Array],
                stage_params: PyTree, x: jax.Array, n_micro: int,
                mesh: Mesh, axis: str = "pod") -> jax.Array:
    """Run ``n_stages`` chained stages over microbatches of x.

    stage_params: leading axis = stage (sharded over `axis`);
    x: (batch, ...) with batch % n_micro == 0 (replicated over `axis`).
    Returns stage_{S-1}(...stage_0(x)) with the same shape as x.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def shard_fn(params_local, xm_local):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        sidx = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xm_local[0])
        T = n_micro + n_stages - 1

        def tick(carry, t):
            recv, outs = carry
            inp0 = jnp.where(t < n_micro,
                             xm_local[jnp.clip(t, 0, n_micro - 1)], zero)
            inp = jnp.where(sidx == 0, inp0, recv)
            h = stage_fn(params_local, inp)
            recv_next = jax.lax.ppermute(h, axis, perm)
            # last stage emits microbatch t-(n_stages-1)
            oidx = t - (n_stages - 1)
            valid = (sidx == n_stages - 1) & (oidx >= 0)
            outs = jax.lax.cond(
                oidx >= 0,
                lambda o: o.at[jnp.clip(oidx, 0, n_micro - 1)].set(
                    jnp.where(valid, h, o[jnp.clip(oidx, 0, n_micro - 1)])),
                lambda o: o, outs)
            return (recv_next, outs), None

        outs0 = jnp.zeros_like(xm_local)
        (_, outs), _ = jax.lax.scan(tick, (zero, outs0), jnp.arange(T))
        # only the last stage holds real outputs; broadcast via psum
        outs = jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    other = [a for a in mesh.axis_names if a != axis]
    # params: stage axis sharded; x: replicated over `axis`
    pspec = jax.tree_util.tree_map(
        lambda a: P(*([axis] + [None] * (a.ndim - 1))), stage_params)
    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, xm)
    return out.reshape(x.shape)


def split_layers_to_stages(stacked_params: PyTree, n_stages: int) -> PyTree:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def re(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages}"
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree_util.tree_map(re, stacked_params)
