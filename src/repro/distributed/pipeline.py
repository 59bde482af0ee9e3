"""Microbatch pipeline parallelism over the "pod" axis (GPipe-style).

For workloads where cross-pod DP gradient traffic dominates, the pod axis
can instead carry PIPELINE stages: each pod owns a contiguous block of
layers; microbatches stream stage-to-stage via collective_permute
(point-to-point over the inter-pod links), overlapping the transfer of
microbatch i+1 with the compute of microbatch i.

Implemented with shard_map over the "pod" axis: each stage holds its layer
block (params stacked (n_stages, L/n_stages, ...) and sharded on dim 0);
the schedule runs n_micro + n_stages - 1 ticks (fill + steady state +
drain). This is the forward pipeline used for serving/inference scale-out;
for training, the trainer composes it with DP/TP inside each pod.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_forward(mesh, stage_fn: Callable, n_micro: int,
                     axis: str = "pod"):
    """Build a pipelined forward over `axis`.

    stage_fn(stage_params, x) -> x, applied by every stage to whatever
    microbatch currently occupies it.

    Returns fn(stage_params_stacked, x_microbatched):
      stage_params_stacked: (n_stages, ...) sharded on dim 0 over `axis`
      x_microbatched: (n_micro, mb, ...) replicated
      -> (n_micro, mb, ...) outputs (each microbatch processed by ALL
         stages in order).
    """
    n_stages = mesh.shape[axis]

    def pipelined(params, xs):
        def local(params_l, xs_l):
            # params_l: (1, ...) this stage's block; xs_l: full (n_micro,...)
            stage = jax.lax.axis_index(axis)
            p = jax.tree.map(lambda t: t[0], params_l)
            n_ticks = n_micro + n_stages - 1
            mb_shape = xs_l.shape[1:]

            def tick(carry, t):
                buf, outs = carry           # buf: current occupant (mb,...)
                # stage 0 ingests microbatch t (if any)
                src = jnp.where(t < n_micro, t, n_micro - 1)
                fresh = jax.lax.dynamic_index_in_dim(xs_l, src, 0,
                                                     keepdims=False)
                x_in = jnp.where(stage == 0, fresh, buf)
                active = (t >= stage) & (t - stage < n_micro)
                y = stage_fn(p, x_in)
                y = jnp.where(active, y, buf)
                # last stage emits microbatch (t - n_stages + 1)
                emit_idx = t - (n_stages - 1)
                do_emit = (stage == n_stages - 1) & (emit_idx >= 0)
                emit = jnp.maximum(emit_idx, 0)
                cur = jax.lax.dynamic_index_in_dim(outs, emit, 0,
                                                   keepdims=False)
                newval = jnp.where(do_emit, y.astype(outs.dtype), cur)
                outs = jax.lax.dynamic_update_index_in_dim(outs, newval,
                                                           emit, 0)
                # shift: stage s sends to s+1 (ring permute; wraparound
                # harmless — stage 0 overwrites from fresh input)
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
                nxt = jax.lax.ppermute(y, axis, perm)
                return (nxt, outs), None

            # pcast marks carries as axis-varying for the vartype checker
            buf0 = jax.lax.pcast(jnp.zeros(mb_shape, xs_l.dtype), (axis,),
                                 to="varying")
            outs0 = jax.lax.pcast(jnp.zeros_like(xs_l), (axis,),
                                  to="varying")
            (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                        jnp.arange(n_ticks))
            # only the last stage holds valid outputs; replicate them
            outs = jnp.where(stage == n_stages - 1, outs, 0)
            return jax.lax.psum(outs, axis)

        in_specs = (jax.tree.map(lambda _: P(axis), params),
                    P(*([None] * xs.ndim)))
        return shard_map(local, mesh=mesh,
                         in_specs=in_specs,
                         out_specs=P(*([None] * xs.ndim)))(params, xs)

    return pipelined
