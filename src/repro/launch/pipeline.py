"""GPipe-style pipeline parallelism over the ``pod`` axis.

The production meshes put 256 chips in a pod; the multi-pod mesh adds a
``pod`` axis that §Dry-run exercises as a pure data axis.  This module
provides the alternative: treat pods as PIPELINE STAGES — layers are split
into ``n_pods`` contiguous stages, microbatches stream through a
``shard_map`` whose only cross-stage communication is a ``lax.ppermute`` of
the (microbatch, seq, d_model) activation per tick (point-to-point over the
inter-pod DCI links, instead of gradient all-reduces spanning pods).

Differentiable by construction: the transpose of ``ppermute`` is the reverse
permute, so wrapping the pipelined forward in a loss gives pipeline-parallel
*training* gradients from plain ``jax.grad`` (bubble fraction
``(P-1)/(M+P-1)`` as usual for GPipe).

This is a capability + correctness test (tests/test_pipeline.py), not the
default path — the assigned shapes are lowered with the pod axis as data
parallelism, which wins at these batch sizes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """Reshape stacked (L, ...) layer params into (n_stages, L/n_stages, ...)."""
    def rs(a):
        l = a.shape[0]
        if l % n_stages:
            raise ValueError(f"{l} layers not divisible by {n_stages} stages")
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(rs, stacked_params)


def pipeline_apply(stage_fn: Callable, staged_params, x, mesh,
                   axis: str = "pod"):
    """Run ``x``'s microbatches through the layer pipeline.

    stage_fn(stage_params, h) -> h : applies ONE stage's layers.
    staged_params: pytree with leading (n_stages, ...) axis (see split_stages).
    x: (n_micro, mb, ...) microbatched activations (replicated across pods).
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def block(params_local, xb):
        # shard_map gives each pod its stage slice with a leading axis of 1
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        p = lax.axis_index(axis)
        buf = jnp.zeros_like(xb[0])
        outs = jnp.zeros_like(xb)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        for t in range(n_micro + n_stages - 1):
            # stage 0 ingests microbatch t (zeros once the stream dries up)
            feed = xb[t] if t < n_micro else jnp.zeros_like(xb[0])
            buf = jnp.where(p == 0, feed, buf)
            buf = stage_fn(params_local, buf)
            # last stage emits microbatch t-(P-1)
            out_idx = t - (n_stages - 1)
            if 0 <= out_idx < n_micro:
                emit = jnp.where(p == n_stages - 1, buf, jnp.zeros_like(buf))
                outs = outs.at[out_idx].add(emit)
            buf = lax.ppermute(buf, axis, fwd_perm)
        # outputs live on the last pod only; sum-replicate across stages
        return lax.psum(outs, axis)

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(staged_params, x)
