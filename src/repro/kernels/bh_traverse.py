"""Pallas TPU kernel: phase-B Barnes-Hut traversal — frontier expansion +
Gumbel-max sampling + leaf member selection in one pass per query block.

Paper Fig. 11 attributes ~55% of the optimized runtime to Barnes-Hut
computation; this kernel keeps its whole working set — the stacked subtree
levels (counts + centroids), the leaf membership table, and the subtree's
neuron data — VMEM-resident while a block of queries runs the full restart
loop, instead of re-streaming (Q, F) frontier temporaries through HBM every
expansion round like the reference lowering does.

The kernel body executes ``repro.connectome.traverse.phase_b_core``, whose
restart driver runs every row of the block through the restart cap; the
reference path runs the same ``expand_and_sample`` over the rows still
searching only, then the same member selection. Both share the ``bh_gauss``
MXU distance identity (|x|^2+|y|^2-2<x,y> over 8 zero-padded lanes) for
node and member probabilities, and the counter-based Threefry Gumbel
stream keyed by ``(seed, chunk, source_gid, round, draw)``
(kernels/hash.py). Every op is row-independent over queries, so blocking
cannot change results: ``connectivity_impl='fused'`` is bit-identical to
``'reference'`` (tests/test_connectome.py). Like the other kernels here,
CPU containers run it with ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.connectome.traverse import phase_b_core


def _kernel(counts_ref, cents_ref, members_ref, npos_ref, vac_ref, x_ref,
            start_ref, gid_ref, valid_ref, scal_ref, tgt_ref, ok_ref,
            depth_ref, ovf_ref, *, seed, sizes, theta, sigma, frontier,
            n_levels):
    chunk = scal_ref[0]
    gid_base = scal_ref[1]
    tgt, ok, depth, overflow = phase_b_core(
        counts_ref[...], cents_ref[...], members_ref[...], npos_ref[...],
        vac_ref[...], x_ref[...], start_ref[...], gid_ref[...],
        valid_ref[...], chunk, gid_base, seed=seed, sizes=sizes, theta=theta,
        sigma=sigma, frontier=frontier, n_levels=n_levels)
    tgt_ref[...] = tgt.astype(jnp.int32)
    ok_ref[...] = ok
    depth_ref[...] = depth.astype(jnp.int32)
    ovf_ref[...] = overflow


def bh_traverse(counts, cents, members, npos, vac, x, start_cell, src_gid,
                valid, chunk, gid_base, *, seed: int, sizes, theta: float,
                sigma: float, frontier: int, n_levels: int, block_q: int = 128,
                interpret: bool = False):
    """Phase-B search for Q queries against one subtree.

    counts: (L, C) f32; cents: (L, C, 3) f32 centroids (``stack_levels``);
    members: (n_leaf, M) i32; npos: (N, 3) f32; vac: (N,) f32; x: (Q, 3);
    start_cell/src_gid: (Q,) i32; valid: (Q,) bool; chunk/gid_base: traced
    i32 scalars; sizes: static per-level cell edge lengths. Returns
    (target_gid (Q,) i32, valid (Q,), depth (Q,) i32 restart rounds — the
    telemetry frontier-depth signal — and overflow (Q,) bool, the frontier
    overflow flag the telemetry counts).

    Q that is not a multiple of the block is padded up to it (padded rows
    carry valid=False and are sliced off — same fix as ``neuron_step``)."""
    q = x.shape[0]
    bq = min(block_q, q)
    qp = -(-q // bq) * bq
    if qp != q:
        pad = qp - q
        x = jnp.pad(x, ((0, pad), (0, 0)))
        start_cell = jnp.pad(start_cell, (0, pad))
        src_gid = jnp.pad(src_gid, (0, pad), constant_values=-2)
        valid = jnp.pad(valid, (0, pad))
    scal = jnp.stack([jnp.asarray(chunk, jnp.int32),
                      jnp.asarray(gid_base, jnp.int32)])
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)  # noqa: E731
    row = pl.BlockSpec((bq,), lambda i: (i,))
    kern = functools.partial(_kernel, seed=seed, sizes=tuple(sizes),
                             theta=theta, sigma=sigma, frontier=frontier,
                             n_levels=n_levels)
    outs = pl.pallas_call(
        kern,
        grid=(qp // bq,),
        in_specs=[full(counts), full(cents), full(members), full(npos),
                  full(vac), pl.BlockSpec((bq, 3), lambda i: (i, 0)),
                  row, row, row, pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=[row, row, row, row],
        out_shape=[jax.ShapeDtypeStruct((qp,), jnp.int32),
                   jax.ShapeDtypeStruct((qp,), jnp.bool_),
                   jax.ShapeDtypeStruct((qp,), jnp.int32),
                   jax.ShapeDtypeStruct((qp,), jnp.bool_)],
        interpret=interpret,
    )(counts, cents, members, npos, vac, x, start_cell, src_gid, valid, scal)
    return tuple(o[:q] for o in outs) if qp != q else tuple(outs)


def traverse_hbm_bytes(n_levels: int, c_max: int, n_leaf: int,
                       members_cap: int, n: int, q: int) -> int:
    """Analytic HBM traffic of one fused phase-B on TPU: the tree arrays,
    membership table, and neuron data stream HBM->VMEM once (constant index
    maps keep them block-resident across the query grid), queries stream in
    once, the outputs stream out once — the per-round (Q, F) frontier
    state never leaves VMEM. Compare with the roofline-counted bytes of the
    reference lowering (benchmarks/bench_connectivity.py)."""
    tree = n_levels * c_max * 4 + n_levels * c_max * 3 * 4
    leaf = n_leaf * members_cap * 4
    neurons = n * 3 * 4 + n * 4
    queries = q * 3 * 4 + q * 4 + q * 4 + q + 8
    # target gid + valid + telemetry depth + telemetry overflow
    outs = q * 4 + q + q * 4 + q
    return tree + leaf + neurons + queries + outs
