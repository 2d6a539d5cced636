"""Hierarchical (tree) collectives — the distributed-runtime realization of
the paper's distributed tree barrier (DESIGN.md §2).

A flat all-reduce over all 512 chips is the "centralized barrier": every
gradient byte crosses the slow inter-pod (DCI) links in full.  The tree
version follows the barrier's gather/release shape:

  gather   reduce-scatter *inside* the pod (fast ICI; each chip ends up
           owning 1/chips_per_pod of the gradient)
  exchange all-reduce of only that shard across the `pod` axis (the single
           parent hop of the binary tree; DCI bytes / chips_per_pod)
  release  all-gather inside the pod (fast ICI broadcast)

Total inter-pod bytes drop from `G * (pods-1)/pods * 2` per chip (flat ring
all-reduce spans the DCI seam) to `G / chips_per_pod * 2` — measured in
EXPERIMENTS.md §Perf via HLO collective parsing.

These functions run *inside shard_map* (axis names bound by the caller's
mesh); `tree_allreduce` is the generic building block, the `_grads` wrappers
close over gradient pytrees.
"""

from __future__ import annotations

import jax


def tree_allreduce(x, *, intra_axes, inter_axis):
    """Hierarchical mean-preserving all-reduce (sum semantics).

    Inside shard_map: reduce-scatter over `intra_axes` (tuple of mesh axis
    names, e.g. ("data",) or ("data", "model")), all-reduce over `inter_axis`
    ("pod"), then all-gather over `intra_axes`.  Falls back to a flat psum if
    the value is too small to scatter."""
    intra = intra_axes if isinstance(intra_axes, (tuple, list)) else (intra_axes,)
    size = 1
    for ax in intra:
        size *= jax.lax.axis_size(ax)
    flat = x.reshape(-1)
    n = flat.shape[0]
    if n % size != 0:  # tiny tensors: flat reduce is cheaper anyway
        out = jax.lax.psum(flat, intra)
        out = jax.lax.psum(out, inter_axis)
        return out.reshape(x.shape)
    # gather phase: each chip ends up with the sum of its 1/size shard
    shard = flat.reshape(size, n // size)
    shard = jax.lax.psum_scatter(shard, intra, scatter_dimension=0,
                                 tiled=False)
    # parent hop: only the shard crosses the inter-pod links
    shard = jax.lax.psum(shard, inter_axis)
    # release phase: broadcast back down the tree
    out = jax.lax.all_gather(shard, intra, axis=0, tiled=False)
    return out.reshape(x.shape)


def flat_psum_grads(grads, axes):
    """Baseline: single-level all-reduce over all replica axes at once."""
    return jax.tree.map(lambda g: jax.lax.psum(g, axes), grads)


def hierarchical_psum_grads(grads, *, intra_axes=("data",), inter_axis="pod"):
    return jax.tree.map(
        lambda g: tree_allreduce(g, intra_axes=intra_axes,
                                 inter_axis=inter_axis), grads)
