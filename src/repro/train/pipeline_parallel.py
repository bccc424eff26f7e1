"""GPipe-style pipeline parallelism on the SAME chain scheduler as the
archival tier (repro.core.pipeline.software_pipeline).

The paper's insight — stream chunks through a chain of nodes, each combining
what it holds with what arrives — *is* pipeline parallelism applied to
storage. Here the roles map back: chain node -> pipeline stage, chunk ->
microbatch, running GF combination -> activations. Stage s processes
microbatch m at tick m + s; ``lax.ppermute`` forwards activations to the
next stage; the backward pass is jax.grad through the shard_map (the
transpose of ppermute is the reverse permute, so autodiff derives the
reverse-schedule backward pipeline for free).

Usage (see tests/test_pipeline_parallel.py):

    stage_params: pytree stacked on a leading [n_stages] axis
    fn = make_pipeline_fn(stage_fn, mesh, n_micro)   # shard_map'd
    y = fn(stage_params, x)        # x (global_batch, ...) -> same shape
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import compat
from repro.core import pipeline as sched

AXIS = "stage"


def _stage_body(stage_fn: Callable, n_micro: int):
    """Body run per stage device under shard_map.

    params: this stage's params (leading [1] from the sharded stack);
    xs: (n_micro, mb, ...) microbatched inputs (replicated; only stage 0
    reads them). Returns (n_micro, mb, ...) outputs (valid on the LAST
    stage; other stages hold partials and are masked by the caller).
    """

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)
        n = lax.axis_size(AXIS)
        idx = lax.axis_index(AXIS)

        def step_fn(wire_in, out, ch, active):
            x_in = jnp.where(idx == 0, xs[ch], wire_in)
            y = stage_fn(params, x_in)
            write = active & (idx == n - 1)
            cur = out[ch]
            out = out.at[ch].set(jnp.where(write, y, cur))
            return y, out

        out = sched.software_pipeline(
            step_fn, jnp.zeros_like(xs[0]), jnp.zeros_like(xs),
            n_micro, AXIS)
        # broadcast the last stage's result to every stage so the output
        # sharding is well-defined (one extra ppermute-free psum of masked
        # data; cheap relative to the stage compute)
        mask = (idx == n - 1).astype(out.dtype)
        return lax.psum(out * mask, AXIS)

    return body


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, n_micro: int):
    """Build a jit-able pipelined apply: (stacked_params, x) -> y.

    ``stage_fn(params_one_stage, x_mb) -> y_mb`` must preserve x's shape
    (a residual-block stack). x (B, ...) is split into ``n_micro``
    microbatches along the batch axis.
    """
    def apply(stacked_params, x):
        B = x.shape[0]
        assert B % n_micro == 0, (B, n_micro)
        xs = x.reshape(n_micro, B // n_micro, *x.shape[1:])
        fn = compat.shard_map(
            _stage_body(stage_fn, n_micro), mesh=mesh,
            in_specs=(P(AXIS), P()), out_specs=P(),
        )
        out = fn(stacked_params, xs)
        return out.reshape(B, *x.shape[1:])

    return apply


def pipeline_loss_fn(stage_fn: Callable, mesh: Mesh, n_micro: int,
                     loss_of: Callable):
    """Pipelined scalar loss: mean over microbatches of loss_of(y, batch)."""
    apply = make_pipeline_fn(stage_fn, mesh, n_micro)

    def loss(stacked_params, x, target):
        y = apply(stacked_params, x)
        return loss_of(y, target)

    return loss
