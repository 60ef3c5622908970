"""Compile rehearsals for the TPU v5e, without a chip: the simulator's main
path at the deployment size (``CONFIG``, 65,536 neurons per chip) goes
through the TPU compiler for described devices, which refuses what the
chip would refuse (unlowerable ops, layouts, programs that do not fit).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file. The whole chunk program takes minutes to
compile and stays out of the suite (``chip_smoke.py`` runs it on the chip).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.msp_brain import CONFIG
from repro.sim import Simulator
from repro.sim import phases as sim_phases

MB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _sim(topo, k, **over):
    import dataclasses
    cfg = dataclasses.replace(CONFIG, **over) if over else CONFIG
    return Simulator(cfg, mesh=Mesh(np.array(topo.devices[:k]), ("ranks",)))


def _state_shapes(sim):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(sim.init_fn), sim.shardings())


def test_init_compiles_for_one_v5e_at_config(topo):
    sim = _sim(topo, 1)
    mem = sim.init_fn.lower().compile().memory_analysis()
    assert 0 < mem.output_size_in_bytes < 100 * MB, mem


def test_reference_activity_window_compiles_for_one_v5e(topo):
    """One Delta = 100 step window of the reference activity lowering."""
    sim = _sim(topo, 1)
    cfg = sim.cfg

    def body(st):
        ctx = sim_phases.make_context(cfg, jax.lax.axis_index("ranks"),
                                      "ranks", 1)
        return sim_phases.activity_phase(st, ctx)

    act = jax.jit(jax.shard_map(body, mesh=sim.mesh, in_specs=(sim.specs,),
                                out_specs=sim.specs, check_vma=False))
    compiled = act.lower(_state_shapes(sim)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9, mem
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("rate_exchange", ["dense", "sparse"])
def test_init_compiles_on_four_v5e_ranks(topo, rate_exchange):
    """The ``ranks`` mesh over four described chips: state sharded one
    rank block per device, under 100 MB each."""
    sim = _sim(topo, 4, rate_exchange=rate_exchange)
    mem = sim.init_fn.lower().compile().memory_analysis()
    assert 0 < mem.output_size_in_bytes < 100 * MB, mem
    assert sim.num_ranks == 4


def test_neuron_step_kernel_compiles_for_v5e_at_config(topo):
    """The elementwise Pallas kernel, compiled (not interpreted) at the
    deployment's neurons per chip."""
    from repro.kernels.neuron_step import neuron_step
    n = CONFIG.neurons_per_rank
    one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one)
    fn = jax.jit(lambda *a: neuron_step(*a, CONFIG, interpret=False))
    compiled = fn.lower(*([x] * 6)).compile()
    assert "tpu_custom_call" in compiled.as_text()
