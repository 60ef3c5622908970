import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, print memory/cost analysis, and emit the roofline JSON.

The two lines above MUST stay the first statements in this file: jax locks the
device count at first initialization, and the dry-run needs 512 placeholder
host devices to build the production mesh. Never set this flag globally —
smoke tests and benchmarks see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch msp-brain --shape brain_64k
  ... [--multi-pod] [--out experiments/dryrun] [--set moe_strategy=move_data ...]
"""
import argparse
import dataclasses
import json
import math
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, get_shape
from repro.configs.base import applicable_shapes, supports_long_context
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step, opt_config_for)
from repro.models import build_model, decode_state_specs, input_specs
from repro.models.decode import state_shardings
from repro.optim.optimizer import init_opt_state
from repro.parallel import sharding as shd


def _apply_overrides(cfg, sets):
    par_fields = {f.name for f in dataclasses.fields(cfg.parallel)}
    cfg_fields = {f.name for f in dataclasses.fields(cfg)}
    for kv in sets or []:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if k in par_fields:
            cfg = cfg.replace(parallel=cfg.parallel.replace(**{k: v}))
        elif k in cfg_fields:
            cfg = cfg.replace(**{k: v})
        else:
            raise KeyError(k)
    return cfg


def batch_shardings(cfg, batch_specs, mesh):
    out = {}
    for k, v in batch_specs.items():
        out[k] = shd.batch_sharding(mesh, len(v.shape), batch_size=v.shape[0],
                                    layout=cfg.parallel.layout)
    return out


def analytic_flops(cfg, shape):
    """MODEL_FLOPS: 6*N*D (train, dense) / 6*N_active*D (MoE); 2*N*D fwd-only."""
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        d_tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * d_tokens
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch  # decode: one token per sequence


def lower_cell(arch, shape_name, multi_pod, sets=None):
    t0 = time.time()
    if arch == "msp-brain":
        return lower_brain_cell(shape_name, multi_pod, sets)
    cfg = _apply_overrides(get_config(arch), sets)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = math.prod(mesh.shape.values())
    record = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(str(s) for s in mesh.shape.values()),
              "multi_pod": multi_pod, "kind": shape.kind,
              "overrides": sets or [], "ok": False}

    if shape.name == "long_500k" and not supports_long_context(cfg):
        record.update(ok=True, skipped=True,
                      reason="full-attention arch: quadratic over 512k "
                             "(see DESIGN.md §4)")
        return record

    api = build_model(cfg)
    specs = input_specs(cfg, shape)
    key = jax.random.key(0)
    layout = cfg.parallel.layout
    params_sds = jax.eval_shape(api.init, key)
    pshard = shd.make_param_shardings(params_sds, mesh, layout=layout)
    bshard = batch_shardings(cfg, specs, mesh)

    with shd.use_mesh(mesh, layout):
        if shape.kind == "train":
            opt_sds = jax.eval_shape(
                lambda p: init_opt_state(p, opt_config_for(cfg)), params_sds)
            oshard = {
                "m": shd.make_param_shardings(opt_sds["m"], mesh,
                                              opt_state=True, layout=layout),
                "v": shd.make_param_shardings(opt_sds["v"], mesh,
                                              opt_state=True, layout=layout),
                "step": shd.replicated(mesh)}
            step = make_train_step(api, mesh, opt_config_for(cfg))
            jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_sds, opt_sds, specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(api, mesh)
            jitted = jax.jit(step, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_sds, specs)
        else:  # decode
            state_sds = decode_state_specs(cfg, shape)
            sshard = state_shardings(cfg, state_sds, mesh, shape.global_batch)
            tshard = shd.batch_sharding(mesh, 1, batch_size=shape.global_batch)
            step = make_decode_step(api, mesh)
            jitted = jax.jit(step, in_shardings=(pshard, sshard, tshard),
                             out_shardings=(None, sshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_sds, state_sds,
                                   specs["tokens"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ----- analyses -----
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, f):
                mem[f] = getattr(ma, f)
        print("memory_analysis:", mem or ma)
    except Exception as e:  # CPU backend may not implement it
        mem = {"error": repr(e)}
        print("memory_analysis unavailable:", e)
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or "utilization" not in k)}
        print("cost_analysis flops:", cost.get("flops"),
              "bytes:", cost.get("bytes accessed"))
    except Exception as e:
        cost = {"error": repr(e)}

    hlo = compiled.as_text()
    ana = rl.analyze_hlo(hlo, ndev)

    mf = analytic_flops(cfg, shape)
    flops_dev = ana["dot_flops"]
    # memory term: analytic HBM traffic (CPU cost analysis is not fusion-aware;
    # model documented in EXPERIMENTS.md §Roofline):
    #   train   = params r/w + grads r/w + opt m,v r/w + act traffic (12x)
    #   prefill = params read + act traffic (6x)
    #   decode  = params read + decode-state read/write
    def tree_bytes(t):
        return sum(math.prod(l.shape) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(t))
    pbytes = tree_bytes(params_sds) / ndev
    tok_dev = shape.global_batch * shape.seq_len / ndev
    act = tok_dev * cfg.d_model * 2 * cfg.num_layers
    if shape.kind == "train":
        obytes = tree_bytes(opt_sds) / ndev
        mem_bytes_dev = 4 * pbytes + 2 * obytes + 12 * act
    elif shape.kind == "prefill":
        mem_bytes_dev = pbytes + 6 * act
    else:
        sbytes = tree_bytes(state_sds) / ndev
        mem_bytes_dev = pbytes + 2 * sbytes

    terms = rl.roofline_terms(flops_dev, mem_bytes_dev,
                              ana["collective_bytes_total"],
                              device_kind=rl.V5E)
    record.update(
        ok=True, lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory_analysis=mem, cost_analysis=cost,
        hlo_bytes=len(hlo),
        collectives=ana["collective_wire_bytes"],
        collective_logical=ana["collective_logical_bytes"],
        collective_bytes_per_dev=ana["collective_bytes_total"],
        hlo_dot_flops_per_dev=flops_dev,
        model_flops_global=mf,
        model_flops_per_dev=mf / ndev,
        useful_flops_ratio=(mf / ndev) / max(flops_dev, 1.0),
        mem_bytes_per_dev=mem_bytes_dev,
        param_bytes_per_dev=pbytes,
        **terms,
    )
    return record


def lower_brain_cell(shape_name, multi_pod, sets=None):
    """The paper's own workload as a dry-run row (ranks = all mesh devices)."""
    from repro.configs.msp_brain import CONFIG as BRAIN
    from repro.core import engine as brain_engine
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = math.prod(mesh.shape.values())
    n_per = int(shape_name.split("_")[-1].replace("k", "")) * 1024 \
        if "_" in shape_name else BRAIN.neurons_per_rank
    cfg = dataclasses.replace(BRAIN, neurons_per_rank=n_per)
    for kv in sets or []:
        k, v = kv.split("=", 1)
        cfg = dataclasses.replace(cfg, **{k: (int(v) if v.isdigit() else v)})
    t0 = time.time()
    lowered = brain_engine.lower_sim_step(cfg, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    hlo = compiled.as_text()
    ana = rl.analyze_hlo(hlo, ndev)
    terms = rl.roofline_terms(ana["dot_flops"], max(ana["dot_flops"], 1.0),
                              ana["collective_bytes_total"],
                              device_kind=rl.V5E)
    return {"arch": "msp-brain", "shape": shape_name, "multi_pod": multi_pod,
            "mesh": "x".join(str(s) for s in mesh.shape.values()),
            "kind": "brain", "ok": True, "overrides": sets or [],
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "collectives": ana["collective_wire_bytes"],
            "collective_bytes_per_dev": ana["collective_bytes_total"],
            "hlo_dot_flops_per_dev": ana["dot_flops"], **terms}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (model or parallel field)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    try:
        rec = lower_cell(args.arch, args.shape, args.multi_pod, args.set)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape,
               "multi_pod": args.multi_pod, "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:],
               "overrides": args.set}
    import os as _os
    _os.makedirs(args.out, exist_ok=True)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    tag = f"__{args.tag}" if args.tag else ""
    path = f"{args.out}/{args.arch}__{args.shape}__{mesh_tag}{tag}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback", "cost_analysis",
                                   "memory_analysis")},
                     indent=1, default=str))
    sys.exit(0 if rec.get("ok") else 1)


if __name__ == "__main__":
    main()
