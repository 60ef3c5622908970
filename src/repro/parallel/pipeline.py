"""GPipe-style pipeline parallelism over a mesh axis (ppermute + microbatch
scan inside shard_map).

Each stage owns a contiguous slice of layers (stacked params sharded over the
stage axis). A step runs M microbatches through S stages in M+S-1 ticks; the
activation handoff is a single collective-permute per tick. Used when a model
doesn't fit even fully sharded (none of the assigned archs needs it at 256
chips — see DESIGN.md §5 — but the machinery is here and tested on 4 host
devices in tests/test_pipeline.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn, stage_params, x_microbatches, mesh,
                   axis="stage"):
    """layer_fn(params_slice, x) -> x; stage_params: leaves (L_per_stage, ...)
    per stage (global leading dim = S * L_per_stage, sharded over ``axis``).
    x_microbatches: (M, mb, ...) replicated. Returns (M, mb, ...) outputs.
    """
    s = mesh.shape[axis]

    def body(stage_p, xs):
        idx = jax.lax.axis_index(axis)
        m = xs.shape[0]
        ticks = m + s - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def stage_compute(p, x):
            def one(xc, lp):
                return layer_fn(lp, xc), None
            y, _ = jax.lax.scan(one, x, p)
            return y

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any)
            feed = jnp.where(t < m, t, m - 1)
            x_in = jnp.where((idx == 0) & (t < m), 1.0, 0.0) * xs[feed] + \
                jnp.where(idx == 0, 0.0, 1.0) * buf
            y = stage_compute(stage_p, x_in)
            # hand off to the next stage; last stage's output is collected
            out_t = t - (s - 1)
            take = (idx == s - 1) & (out_t >= 0) & (out_t < m)
            outs = jax.lax.cond(
                take,
                lambda o: o.at[jnp.clip(out_t, 0, m - 1)].set(y),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % s) for i in range(s)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(m + s - 1))
        # only the last stage holds the outputs; psum-broadcast to all
        if s > 1:
            outs = jax.lax.psum(
                jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = P(axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec, stage_params), P()),
        out_specs=P(), check_vma=False)(stage_params, x_microbatches)
