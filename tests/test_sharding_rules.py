"""Pure-logic tests for the sharding rules and the MoE cost model (no
compiles; hypothesis sweeps)."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.moe import choose_strategy, moe_strategy_cost
from repro.parallel import sharding as shd


def _mesh(shape=(4, 2), axes=("data", "model")):
    # abstract mesh is enough for spec logic on 1 device? use real devices
    import jax
    from jax.sharding import Mesh
    devs = np.array(jax.devices() * (int(np.prod(shape))))[
        : int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


def test_divisibility_guard_drops_axes():
    mesh = _mesh((4, 2))
    # vocab 51865 doesn't divide 2 -> 'model' dropped on dim0
    spec = shd.infer_param_spec(
        (jax.tree_util.DictKey("embed"), jax.tree_util.DictKey("table")),
        (51865, 512), mesh)
    assert spec[0] is None
    # divisible case keeps the axes
    spec = shd.infer_param_spec(
        (jax.tree_util.DictKey("embed"), jax.tree_util.DictKey("table")),
        (51200, 512), mesh)
    assert spec[0] == "model"


def test_expert_rule_keeps_ep_in_both_layouts():
    mesh = _mesh((4, 2))
    path = (jax.tree_util.DictKey("layers_stacked"),
            jax.tree_util.DictKey("moe"), jax.tree_util.DictKey("w_up"))
    for layout in ("tp", "fsdp"):
        spec = shd.infer_param_spec(path, (8, 16, 2048, 1408), mesh,
                                    layout=layout)
        assert spec[1] == "model", (layout, spec)


def test_fsdp_layout_row_shards_everything():
    mesh = _mesh((4, 2))
    path = (jax.tree_util.DictKey("layers_stacked"),
            jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq"))
    spec = shd.infer_param_spec(path, (8, 4096, 4096), mesh, layout="fsdp")
    assert spec == P(None, ("data", "model"), None)
    spec_tp = shd.infer_param_spec(path, (8, 4096, 4096), mesh, layout="tp")
    assert spec_tp == P(None, "data", "model")


def test_small_leaves_replicated():
    mesh = _mesh((4, 2))
    spec = shd.infer_param_spec(
        (jax.tree_util.DictKey("final_norm"), jax.tree_util.DictKey("scale")),
        (4096,), mesh)
    assert spec == P()


@settings(max_examples=30, deadline=None)
@given(st.integers(64, 65536), st.sampled_from([4, 8, 16, 32]))
def test_moe_auto_strategy_is_min_cost(t_local, model_size):
    cfg = get_config("moonshot-v1-16b-a3b")
    c = moe_strategy_cost(cfg, t_local, model_size)
    pick = choose_strategy(cfg, t_local, model_size)
    assert c[pick] == min(c.values())


def test_moe_cost_crossover_matches_napkin_math():
    """Small per-device token counts favor move_compute (tokens are light);
    huge ones favor move_data (weights become lighter than tokens)."""
    cfg = get_config("moonshot-v1-16b-a3b")
    assert choose_strategy(cfg, 1024, 16) == "move_compute"
    assert choose_strategy(cfg, 1_000_000, 16) == "move_data"
    # arctic's experts are enormous: move_data practically never wins
    arctic = get_config("arctic-480b")
    assert choose_strategy(arctic, 65536, 16) == "move_compute"


def test_constrain_outside_mesh_is_noop():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    y = shd.constrain(x, ("batch", None))
    assert y is x
