"""Beyond-paper: split-KV decode (move compute to the cache shards) vs
batch-sharded local decode — collective bytes + wall time, 8 host devices."""
import jax
import jax.numpy as jnp

from benchmarks._util import emit, time_fn
from repro.launch import roofline as rl
from repro.launch.mesh import make_mesh


def main():
    from repro.configs import get_smoke_config
    from repro.models import build_model, decode_state_specs
    from repro.parallel import sharding as shd
    ndev = len(jax.devices())
    mesh = make_mesh((1, ndev), ("data", "model"))
    for mode in ("local", "split_kv"):
        cfg = get_smoke_config("qwen2-7b").replace(num_kv_heads=4)
        cfg = cfg.replace(parallel=cfg.parallel.replace(decode_attention=mode))
        api = build_model(cfg)
        params = build_model(cfg).init(jax.random.key(0))
        state = api.init_decode_state(4, 2048)
        state["pos"] = jnp.asarray(1024, jnp.int32)
        toks = jnp.ones((4,), jnp.int32)

        def step(p, s, t):
            with shd.use_mesh(mesh):
                return api.decode_step(p, s, t, mesh)

        jitted = jax.jit(step)
        compiled = jitted.lower(params, state, toks).compile()
        ana = rl.analyze_hlo(compiled.as_text(), ndev)
        t, _ = time_fn(jitted, params, state, toks, iters=5)
        emit(f"lm_decode_{mode}_d{ndev}", t * 1e6,
             f"coll_wire_MB={ana['collective_bytes_total'] / 1e6:.2f}")


if __name__ == "__main__":
    main()
