"""Error-feedback compressed gradient all-reduce (shard_map).

DP gradient sync is the largest recurring collective in the training pool;
int8 compression with error feedback (residual accumulation) cuts its wire
bytes 2× vs bf16 / 4× vs fp32 with provably-bounded bias (the residual
carries quantization error into the next step).  Implemented as a
``shard_map`` collective over the data axes so XLA emits a real
all-reduce over int32-accumulated int8 payloads.

Used by the launch/train.py driver when ``--compress-grads`` is set; the
scheduler's weight-sync/DP cost models take the compression factor into
account when pricing plans.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(x: jax.Array, axis_name) -> jax.Array:
    """int8-quantized psum: quantize locally, sum int32, dequant by the
    psum'd scale (per-tensor).  Call inside shard_map."""
    q, scale = _quantize(x.astype(jnp.float32))
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    # consistent scale: mean of shards' scales (psum/size)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    s = jax.lax.psum(scale, axis_name) / n
    return total.astype(jnp.float32) * s


def make_compressed_allreduce(mesh: Mesh, axis: str = "data"):
    """Returns f(grads, residual) -> (mean_grads, new_residual): an
    error-feedback int8 all-reduce over ``axis`` for a pytree of
    replicated-over-axis gradients."""

    def one(g, r):
        def body(g_shard, r_shard):
            x = g_shard.astype(jnp.float32) + r_shard
            q, scale = _quantize(x)
            deq = q.astype(jnp.float32) * scale
            new_r = x - deq                      # error feedback
            n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
            total = jax.lax.psum(q.astype(jnp.int32), axis).astype(
                jnp.float32)
            s = jax.lax.psum(scale, axis) / n
            return (total * s / n).astype(g_shard.dtype), new_r

        spec = P(*([None] * g.ndim))
        return shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec),
                         check_vma=False)(g, r)

    def allreduce(grads: Any, residual: Any) -> Tuple[Any, Any]:
        out = jax.tree_util.tree_map(one, grads, residual)
        flat, treedef = jax.tree_util.tree_flatten(
            out, is_leaf=lambda t: isinstance(t, tuple))
        gs = jax.tree_util.tree_unflatten(treedef, [t[0] for t in flat])
        rs = jax.tree_util.tree_unflatten(treedef, [t[1] for t in flat])
        return gs, rs

    return allreduce


def init_residual(grads: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)
