"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile for the chip. On the CPU backend (tests and
rehearsals) they run with interpret=True: the kernel body is executed per
grid step, for correctness only. Any other backend is an error, never a
silent fallback to the interpreter.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.activity_fused import activity_window
from repro.kernels.bh_gauss import bh_gauss_probs
from repro.kernels.bh_traverse import bh_traverse as bh_traverse_kernel
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.neuron_step import neuron_step
from repro.kernels.radix_sort import morton_sort as morton_sort_kernel
from repro.kernels.radix_sort import radix_argsort as radix_argsort_kernel
from repro.kernels.synapse_apply import route_build as route_build_kernel
from repro.kernels.synapse_apply import synapse_apply as synapse_apply_kernel


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas lowering for backend {backend!r}: "
                           "kernels compile on 'tpu' and are interpreted "
                           "on 'cpu' only")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def gauss_probs(x, y, w, *, sigma: float, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return bh_gauss_probs(x, y, w, sigma=sigma, interpret=interpret)


def fused_neuron_step(v, u, ca, ax, de, inp, cfg, *, params=None,
                      interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return neuron_step(v, u, ca, ax, de, inp, cfg, params=params,
                       interpret=interpret)


def bh_traverse(counts, cents, members, npos, vac, x, start_cell, src_gid,
                valid, chunk, gid_base, *, seed, sizes, theta, sigma,
                frontier, n_levels, interpret=None):
    """Phase-B Barnes-Hut traversal kernel (see kernels/bh_traverse.py).
    Not jitted here: it runs inside the engine's jitted shard_map."""
    if interpret is None:
        interpret = _interpret_default()
    return bh_traverse_kernel(counts, cents, members, npos, vac, x,
                              start_cell, src_gid, valid, chunk, gid_base,
                              seed=seed, sizes=sizes, theta=theta,
                              sigma=sigma, frontier=frontier,
                              n_levels=n_levels, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("key_bits", "interpret"))
def radix_argsort(keys, *, key_bits: int = 30, interpret=None):
    """Stable radix argsort of non-negative int32 keys — returns
    (sorted_keys, order), bit-identical to ``jnp.argsort(stable=True)``
    (kernels/radix_sort.py). The reusable sort primitive."""
    if interpret is None:
        interpret = _interpret_default()
    return radix_argsort_kernel(keys, key_bits=key_bits, interpret=interpret)


def morton_sort(positions, leaf_base, *, leaf_level: int, n_leaf: int,
                interpret=None):
    """Fused Morton encode + radix sort feeding the on-device tree build
    (kernels/radix_sort.py). Not jitted here: it runs inside the engine's
    jitted shard_map."""
    if interpret is None:
        interpret = _interpret_default()
    return morton_sort_kernel(positions, leaf_base, leaf_level=leaf_level,
                              n_leaf=n_leaf, interpret=interpret)


def synapse_apply(edges, msg_lid, msg_gid, msg_valid, req_lid, req_src,
                  req_valid, req_prio, vacant_d, *, interpret=None):
    """Fused remove -> compact -> accept pass over one edge table
    (kernels/synapse_apply.py). Not jitted here: it runs inside the
    engine's jitted shard_map."""
    if interpret is None:
        interpret = _interpret_default()
    return synapse_apply_kernel(edges, msg_lid, msg_gid, msg_valid, req_lid,
                                req_src, req_valid, req_prio, vacant_d,
                                interpret=interpret)


def route_build(flat_other, flat_mine, *, n: int, num_ranks: int, cap: int,
                interpret=None):
    """Fused deletion-routing buffer build (kernels/synapse_apply.py). Not
    jitted here: it runs inside the engine's jitted shard_map."""
    if interpret is None:
        interpret = _interpret_default()
    return route_build_kernel(flat_other, flat_mine, n=n,
                              num_ranks=num_ranks, cap=cap,
                              interpret=interpret)


def fused_activity_window(state, in_edges, w_table, rates, bg_mean, bg_std,
                          chunk, rank, *, seed, num_steps, izh, ca_consts,
                          stim=None, lesions=None, rate_slots=None,
                          interpret=None):
    """Whole-rate-window activity megakernel (see kernels/activity_fused.py).
    ``rate_slots`` selects the sparse-exchange operand layout (compact
    subscribed-rate buffer + edge→slot remap instead of the (R, n) table).
    Not jitted here: it runs inside the engine's jitted shard_map."""
    if interpret is None:
        interpret = _interpret_default()
    return activity_window(state, in_edges, w_table, rates, bg_mean, bg_std,
                           chunk, rank, seed=seed, num_steps=num_steps,
                           izh=izh, ca_consts=ca_consts, stim=stim,
                           lesions=lesions, rate_slots=rate_slots,
                           interpret=interpret)
