"""Beyond-paper: the paper's principle in the LM framework — MoE dispatch
strategy (move_data vs move_compute vs auto) measured two ways: HLO collective
wire bytes (the roofline parser) and wall time on 8 host devices."""
import sys

import jax
import jax.numpy as jnp

from benchmarks._util import emit, time_fn
from repro.launch import roofline as rl
from repro.launch.mesh import make_mesh


def main():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.parallel import sharding as shd
    ndev = len(jax.devices())
    da = max(ndev // 4, 1)
    mesh = make_mesh((da, ndev // da), ("data", "model"))
    cfg0 = get_smoke_config("moonshot-v1-16b-a3b").replace(scan_layers=True)
    params = build_model(cfg0).init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 128),
                                          0, 512)}
    for strat in ("move_data", "move_compute", "auto"):
        cfg = cfg0.replace(parallel=cfg0.parallel.replace(moe_strategy=strat))
        api = build_model(cfg)

        def step(p, b):
            with shd.use_mesh(mesh):
                return api.loss(p, b, mesh)[0]

        jitted = jax.jit(step)
        compiled = jitted.lower(params, batch).compile()
        ana = rl.analyze_hlo(compiled.as_text(), ndev)
        t, _ = time_fn(jitted, params, batch, iters=3)
        emit(f"lm_moe_{strat}_d{ndev}", t * 1e6,
             f"coll_wire_MB={ana['collective_bytes_total'] / 1e6:.1f}")


if __name__ == "__main__":
    main()
