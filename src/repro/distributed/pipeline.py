"""GPipe-style pipeline parallelism via shard_map + ppermute.

An optional ``stage`` mesh axis runs layer groups as pipeline stages;
microbatches stream through with the classic (M + S - 1)-tick schedule.
Each device holds only its stage's weights; activations hop stage->stage
with ``ppermute`` (point-to-point, no broadcast traffic).

This is the third parallelism dimension for the 1000+-node regime (e.g.
(pp=4, data=8, model=16) x pods); the dry-run meshes use (data, model) only,
so pipeline is exercised by tests/examples on small meshes.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,     # (stage_params, x [mb, ...]) -> y [mb, ...]
    stage_params,           # pytree, leaves with leading [S] stage axis
    x: jnp.ndarray,         # [M, mb, ...] microbatched input (stage-0 feed)
    mesh: Mesh,
    *,
    axis: str = "stage",
) -> jnp.ndarray:
    """Returns the last stage's outputs [M, mb, ...]."""
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_stage(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # drop sharded stage dim
        me = jax.lax.axis_index(axis)
        T = M + S - 1
        buf = jnp.zeros_like(xs[0])          # activation entering this stage
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # Stage 0 injects microbatch t (if any) — others use the buffer.
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(me == 0, xs[inject], buf)
            y = stage_fn(params, x_in)
            # Valid iff this stage is processing a real microbatch: stage s
            # works on microbatch (t - s) when 0 <= t - s < M.
            mb = t - me
            valid = (mb >= 0) & (mb < M)
            # Collect at the last stage.
            outs = jax.lax.cond(
                valid & (me == S - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), jnp.maximum(mb, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # Shift activations to the next stage.
            y_masked = jnp.where(valid, y, jnp.zeros_like(y))
            buf = jax.lax.ppermute(
                y_masked, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return buf, outs

        _, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # Stack per-stage outputs; only the last stage's slice is real.
        return outs[None]

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),    # params sharded by stage; x replicated
        out_specs=P(axis),
        check_vma=False,
    )
    outs = fn(stage_params, x)
    return outs[-1]


def split_layers_into_stages(stacked_layer_params, num_stages: int):
    """[L, ...] layer stack -> [S, L/S, ...] stage-major stack."""
    def reshape(a):
        L = a.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return a.reshape((num_stages, L // num_stages) + a.shape[1:])
    return jax.tree.map(reshape, stacked_layer_params)
