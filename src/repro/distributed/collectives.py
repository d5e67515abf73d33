"""Interconnect-tiered collectives — the vendor-MPI swap, in shard_map.

The paper's MPI leg swaps the container's generic MPI for the host library
that knows the fabric (Aries/InfiniBand).  On a TPU multi-pod the fabric
has two tiers: ICI inside a pod (~50 GB/s/link) and DCN between pods
(~25 Gbit/s/host).  The *reference* collective is a flat all-reduce over
every DP axis; the *native* collective is hierarchical:

    reduce-scatter over ICI (data axis)        1/N-sized shards
    all-reduce over DCN (pod axis) on shards   cross-pod bytes / N
    all-gather over ICI (data axis)

which moves (pod-1)/pod * bytes/N over the thin DCN pipe instead of the
whole tensor — the textbook two-level schedule.  Both are registered as
implementations of the logical `grad_allreduce` op; the runtime swaps
exactly like it swaps kernels (requires_feature="hierarchical_collectives").

Optional int8 gradient compression (error feedback kept by the caller)
applies to the DCN leg only.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.quant import compress_int8

__all__ = [
    "flat_grad_allreduce",
    "hierarchical_grad_allreduce",
    "make_grad_sync",
]


def _pmean_tree(tree: Any, axes) -> Any:
    return jax.tree.map(lambda g: jax.lax.pmean(g, axes), tree)


def flat_grad_allreduce(grads: Any, *, data_axis: str = "data",
                        pod_axis: str | None = None) -> Any:
    """Reference: one flat pmean over all DP axes (what the bundle ships)."""
    axes = (pod_axis, data_axis) if pod_axis else (data_axis,)
    return _pmean_tree(grads, axes)


# the DCN gradient compressor now lives in the shared quant module so
# the serving kernels and the checkpoint schema quantize with the same
# numerics the conformance grid pins; kept under its old private name
# for the call below and existing importers
_compress_int8 = compress_int8


def hierarchical_grad_allreduce(
    grads: Any,
    *,
    data_axis: str = "data",
    pod_axis: str | None = "pod",
    compress_dcn: bool = False,
) -> Any:
    """Native: ICI reduce-scatter -> DCN all-reduce on shards -> ICI
    all-gather.  Falls back to flat pmean when there is no pod axis."""
    if pod_axis is None:
        return _pmean_tree(grads, (data_axis,))

    def one(g: jnp.ndarray) -> jnp.ndarray:
        flat = g.reshape(-1)
        n = jax.lax.axis_size(data_axis)
        pad = (-flat.size) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        # ICI: reduce-scatter over the data axis -> 1/n shard per device
        shard = jax.lax.psum_scatter(flat, data_axis, scatter_dimension=0, tiled=True)
        # DCN: all-reduce the small shard across pods (optionally int8)
        if compress_dcn:
            q, scale = _compress_int8(shard)
            qsum = jax.lax.psum(q.astype(jnp.int32), pod_axis)
            smax = jax.lax.pmax(scale, pod_axis)   # shared conservative scale
            shard = (qsum.astype(jnp.float32) * smax).astype(shard.dtype)
        else:
            shard = jax.lax.psum(shard, pod_axis)
        # ICI: all-gather the reduced shards back
        full = jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
        # sum -> mean over the full DP group
        total = jax.lax.axis_size(data_axis) * jax.lax.axis_size(pod_axis)
        return (full[: g.size].reshape(g.shape) / total).astype(g.dtype)

    return jax.tree.map(one, grads)


def make_grad_sync(
    mesh: jax.sharding.Mesh,
    *,
    native: bool,
    compress_dcn: bool = False,
    data_axis: str = "data",
    pod_axis: str = "pod",
):
    """Build the grad-sync callable for shard_map-style DP training loops.

    Used by tests/benchmarks to compare the two schedules numerically; the
    pjit train path gets the same effect from XLA's partitioner, with the
    schedule choice recorded in the lowered HLO (see benchmarks/table34).
    """
    has_pod = pod_axis in mesh.axis_names
    if native:
        return functools.partial(
            hierarchical_grad_allreduce,
            data_axis=data_axis,
            pod_axis=pod_axis if has_pod else None,
            compress_dcn=compress_dcn,
        )
    return functools.partial(
        flat_grad_allreduce,
        data_axis=data_axis,
        pod_axis=pod_axis if has_pod else None,
    )


def collective_specs(mesh: jax.sharding.Mesh):
    """in/out specs for running grad sync under shard_map on a grads tree
    that is replicated over DP axes and sharded over 'model'."""
    del mesh
    return P(), P()
