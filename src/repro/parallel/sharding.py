"""Sharding rules: logical-axis mapping, best-effort constraints, param specs.

Scheme (DESIGN.md §5):
  batch             -> ('pod','data')  (pod folds into DP)
  weight "in" dim   -> 'data'   (FSDP row shard)   } only when the dim
  weight "out" dim  -> 'model'  (tensor col shard) } is large enough
  MoE expert dim    -> 'model'  (EP), fsdp dim 'data'
  optimizer m/v     -> like params, plus 'pod' on the fsdp dim (ZeRO across pods)

Small leaves (< _REPLICATE_BELOW elements) stay replicated: sharding a 64x64
matrix 256 ways buys nothing and costs collectives. Non-divisible dims are
allowed (GSPMD pads), but rules prefer divisible layouts.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


_REPLICATE_BELOW = 1 << 22          # 4M elements (~8MB bf16)

_mesh_var: contextvars.ContextVar = contextvars.ContextVar("repro_mesh",
                                                           default=None)
_layout_var: contextvars.ContextVar = contextvars.ContextVar("repro_layout",
                                                             default="tp")


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], layout: str = None):
    tok = _mesh_var.set(mesh)
    tok2 = _layout_var.set(layout) if layout else None
    try:
        yield mesh
    finally:
        _mesh_var.reset(tok)
        if tok2 is not None:
            _layout_var.reset(tok2)


def current_mesh() -> Optional[Mesh]:
    return _mesh_var.get()


def current_layout() -> str:
    return _layout_var.get()


def batch_axes(mesh: Mesh, layout: str = None):
    layout = layout or current_layout()
    names = ("pod", "data", "model") if layout == "fsdp" else ("pod", "data")
    return tuple(a for a in names if a in mesh.axis_names)


def manual_axes() -> frozenset:
    """Mesh axes currently under manual (shard_map) control at trace time."""
    am = jax.sharding.get_abstract_mesh()
    return frozenset(a for a, t in zip(am.axis_names, am.axis_types)
                     if t == AxisType.Manual)


def constrain(x, spec_axes):
    """Best-effort with_sharding_constraint. spec_axes uses logical names:
    'batch' expands to ('pod','data'); None passes through. Axes already
    manual (inside a partial shard_map, e.g. the Delta-periodic pod loop) are
    dropped — the data is already split over them."""
    mesh = current_mesh()
    if mesh is None:
        return x
    manual = manual_axes()

    def drop_manual(ax):
        if ax is None:
            return None
        axes = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                     if a not in manual)
        return axes if axes else None

    resolved = []
    used = set()
    for ax in spec_axes:
        got = drop_manual(batch_axes(mesh) if ax == "batch" else ax)
        if got is not None:  # each mesh axis may appear once (fsdp layout
            axes = got if isinstance(got, tuple) else (got,)
            axes = tuple(a for a in axes if a not in used)  # puts 'model'
            used.update(axes)                               # in 'batch')
            got = axes if axes else None
        resolved.append(got)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# ------------------------------------------------------------ param rules
_EXPERT3D = re.compile(r"(w_up|w_gate|w_down)$")
_COL = re.compile(r"(w_up|w_gate|wq|wk|wv|w_q|w_k|w_v|w_x|w_g|w_if|w)$")
_ROW = re.compile(r"(w_down|wo|w_out)$")


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def infer_param_spec(path, shape, mesh: Mesh, *, opt_state=False,
                     layout: str = None) -> P:
    """Sharding rule for one parameter leaf, keyed on its name + rank."""
    layout = layout or current_layout()
    name = _path_str(path)
    # scanned models stack per-layer params under 'layers_stacked' (leading L dim)
    stacked = 1 if "layers_stacked" in name and len(shape) >= 2 else 0
    core = shape[stacked:]
    size = 1
    for s in shape:
        size *= s
    if size < _REPLICATE_BELOW or not core:
        return P()
    if layout == "fsdp":
        fsdp = tuple(a for a in (("pod", "data", "model") if opt_state
                                 else ("data", "model"))
                     if a in mesh.axis_names)
    else:
        fsdp = ("pod", "data") if (opt_state and "pod" in mesh.axis_names) \
            else "data"
    leaf_name = name.split("/")[-1]

    def _axes_size(ax):
        if ax is None:
            return 1
        axes = ax if isinstance(ax, tuple) else (ax,)
        out = 1
        for a in axes:
            out *= mesh.shape[a]
        return out

    def pad(spec_tail):
        # drop any axis whose size does not divide the dim (jit in_shardings
        # rejects uneven shards — e.g. whisper's 51865 vocab on a 16-way axis)
        fitted = [ax if core[i] % _axes_size(ax) == 0 else None
                  for i, ax in enumerate(spec_tail)]
        return P(*([None] * stacked + fitted))

    if len(core) == 3 and _EXPERT3D.search(leaf_name):   # experts (E, d, ff)
        ep_fsdp = "data" if not opt_state or "pod" not in mesh.axis_names \
            else ("pod", "data")
        return pad(["model", ep_fsdp, None])             # EP in both layouts
    if layout == "fsdp":                                 # pure row sharding
        if len(core) >= 2:
            return pad([fsdp] + [None] * (len(core) - 1))
        return P()
    if leaf_name == "table" and len(core) == 2:          # embedding (V, d)
        return pad(["model", fsdp])
    if len(core) == 2:
        if _ROW.search(leaf_name):
            return pad(["model", fsdp])                  # (ff, d): ff->model
        if _COL.search(leaf_name) or leaf_name == "router":
            return pad([fsdp, "model"])                  # (d, ff): ff->model
        return pad([fsdp, None])
    if len(core) == 1:
        return P()
    return P()


def make_param_shardings(params_shapes, mesh: Mesh, *, opt_state=False,
                         layout: str = None):
    """params_shapes: pytree of ShapeDtypeStruct (from jax.eval_shape)."""
    def one(path, leaf):
        spec = infer_param_spec(path, leaf.shape, mesh, opt_state=opt_state,
                                layout=layout)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params_shapes)


def batch_sharding(mesh: Mesh, ndim: int, batch_dim=0, batch_size=None,
                   layout: str = None):
    """Shard dim ``batch_dim`` over the DP axes; replicate when the batch does
    not divide them (e.g. long_500k's global_batch=1)."""
    spec = [None] * ndim
    baxes = batch_axes(mesh, layout)
    import math as _math
    bsz = _math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    if batch_size is None or (batch_size % max(bsz, 1) == 0
                              and batch_size >= bsz):
        spec[batch_dim] = baxes
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
