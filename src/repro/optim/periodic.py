"""Delta-periodic cross-pod gradient synchronization — the paper's second
algorithm mapped onto distributed training (DESIGN.md §3).

The paper replaces per-step spike exchange with rate exchange every Delta
steps. Here: within-pod gradient reduction (cheap ICI) happens every step via
GSPMD; ACROSS pods (expensive DCI) gradients are only accumulated locally and
exchanged every Delta-th step — semantically exact large-batch training with
cross-pod collective bytes divided by Delta (optionally int8-compressed with
error feedback on top).

Mechanics: shard_map manual over ONLY the 'pod' axis (axis_names={'pod'});
'data'/'model' stay automatic inside, so the whole model code is unchanged.
The accumulator carries a leading (1,)-per-pod axis so pod-divergent sums are
representable. Two jitted steps:
  accum_step : grads -> acc (no cross-pod collective in its HLO at all)
  sync_step  : psum(acc, 'pod') (or int8 gather) + AdamW update
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim.optimizer import OptimizerConfig, adamw_update
from repro.parallel import compress
from repro.parallel import sharding as shd


def init_accumulator(params, mesh=None):
    """Per-pod grad accumulator: global leading axis = n_pods (each pod's
    shard_map slice is (1, ...) — pod-divergent sums are representable)."""
    pods = mesh.shape.get("pod", 1) if mesh is not None else 1
    return jax.tree.map(
        lambda p: jnp.zeros((pods,) + p.shape, jnp.float32), params)


def init_error(params, mesh=None):
    pods = mesh.shape.get("pod", 1) if mesh is not None else 1
    return jax.tree.map(
        lambda p: jnp.zeros((pods,) + p.shape, jnp.float32), params)


def make_periodic_steps(api, mesh, opt_cfg: OptimizerConfig, *,
                        compress_int8: bool = False):
    """Returns (accum_step, sync_step). Both jitted closures over mesh.

    accum_step(params, acc, batch)            -> (acc, metrics)
    sync_step(params, opt_state, acc, err)    -> (params, opt, acc, err, stats)
    """
    has_pod = "pod" in mesh.axis_names
    acc_spec = P("pod") if has_pod else P()

    def _loss(p, b):
        with shd.use_mesh(mesh):
            loss, metrics = api.loss(p, b, mesh)
        return loss, metrics

    def accum_body(params, acc, batch):
        (loss, metrics), grads = jax.value_and_grad(
            _loss, has_aux=True)(params, batch)
        acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32)[None], acc, grads)
        out = dict(metrics, loss=loss)
        if has_pod:  # pods see different microbatches; replicate metrics
            out = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), out)
        return acc, out

    def sync_body(params, opt_state, acc, err):
        if has_pod:
            if compress_int8:
                red, err = compress.tree_allreduce_int8(acc, err, "pod")
                grads = jax.tree.map(lambda g: g[0], red)
            else:
                grads = jax.tree.map(
                    lambda a: jax.lax.psum(a, "pod")[0] / mesh.shape["pod"],
                    acc)
        else:
            # no pod axis: every slot of the leading axis holds the same
            # globally-reduced gradient
            grads = jax.tree.map(lambda a: jnp.mean(a, axis=0), acc)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        params, opt_state, stats = adamw_update(params, grads, opt_state,
                                                opt_cfg)
        acc = jax.tree.map(jnp.zeros_like, acc)
        return params, opt_state, acc, err, stats

    if has_pod:
        bspec = {"tokens": P(("pod",), None)}
        accum = jax.jit(jax.shard_map(
            accum_body, mesh=mesh, axis_names={"pod"},
            in_specs=(P(), acc_spec, bspec),
            out_specs=(acc_spec, P()), check_vma=False))
        sync = jax.jit(jax.shard_map(
            sync_body, mesh=mesh, axis_names={"pod"},
            in_specs=(P(), P(), acc_spec, acc_spec),
            out_specs=(P(), P(), acc_spec, acc_spec, P()), check_vma=False))
    else:
        accum = jax.jit(accum_body)
        sync = jax.jit(sync_body)
    return accum, sync
