"""Multi-device semantics, via subprocesses with 8 host devices (the XLA
device-count flag must be set before jax initializes, so these cannot run
in-process with the rest of the suite)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_py(code, devices=8, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_brain_old_new_connectivity_identical():
    """THE paper claim: the location-aware algorithm forms exactly the same
    synapses as the RMA-download baseline (we get bit-identical, the paper
    argues qualitative equivalence)."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        base = BrainConfig(neurons_per_rank=64, local_levels=3,
                           frontier_cap=32, max_synapses=16,
                           spike_alg='old', requests_cap_factor=1000)
        mesh = engine.make_brain_mesh()
        res = {}
        for alg in ['old', 'new']:
            cfg = dataclasses.replace(base, connectivity_alg=alg)
            init_fn, chunk = engine.build_sim(cfg, mesh)
            st = init_fn()
            for _ in range(3):
                st = chunk(st)
            res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                        np.sort(np.asarray(st.in_edges), 1),
                        float(st.stats['synapses_formed'].sum()),
                        float(st.stats['tree_nodes_downloaded'].sum()))
        assert np.array_equal(res['old'][0], res['new'][0]), 'out differ'
        assert np.array_equal(res['old'][1], res['new'][1]), 'in differ'
        assert res['old'][2] == res['new'][2] and res['old'][2] > 0
        assert res['old'][3] > 0 and res['new'][3] == 0  # comm asymmetry
        print('IDENTICAL', res['old'][2])
    """)
    assert "IDENTICAL" in out


def test_brain_edge_symmetry_across_ranks():
    """Every out-edge has the matching in-edge on the partner rank."""
    out = run_py("""
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        cfg = BrainConfig(neurons_per_rank=64, local_levels=3,
                          frontier_cap=32, max_synapses=16,
                          requests_cap_factor=1000)
        mesh = engine.make_brain_mesh()
        init_fn, chunk = engine.build_sim(cfg, mesh)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        out_e = np.asarray(st.out_edges); in_e = np.asarray(st.in_edges)
        n_total = out_e.shape[0]
        pairs_out = set()
        for src in range(n_total):
            for t in out_e[src]:
                if t >= 0: pairs_out.add((src, int(t)))
        pairs_in = set()
        for tgt in range(n_total):
            for s in in_e[tgt]:
                if s >= 0: pairs_in.add((int(s), tgt))
        assert pairs_out == pairs_in, (len(pairs_out), len(pairs_in),
                                       list(pairs_out ^ pairs_in)[:5])
        assert len(pairs_out) > 0
        print('SYMMETRIC', len(pairs_out))
    """)
    assert "SYMMETRIC" in out


def test_fused_activity_identical_across_ranks():
    """The fused megakernel == the reference scan bit-for-bit on a real
    multi-rank mesh (remote PRNG spikes, rates table, all-gathered
    connectivity all in play)."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000)
        res = {}
        for impl in ['reference', 'fused']:
            cfg = dataclasses.replace(base, activity_impl=impl)
            init_fn, chunk = engine.build_sim(cfg, engine.make_brain_mesh())
            st = init_fn()
            for _ in range(2):
                st = chunk(st)
            res[impl] = st
        a, b = res['reference'], res['fused']
        assert np.array_equal(np.asarray(a.neurons.v),
                              np.asarray(b.neurons.v)), 'v differs'
        assert np.array_equal(np.asarray(a.neurons.calcium),
                              np.asarray(b.neurons.calcium)), 'ca differs'
        assert np.array_equal(np.asarray(a.out_edges),
                              np.asarray(b.out_edges)), 'edges differ'
        assert np.array_equal(np.asarray(a.rates_table),
                              np.asarray(b.rates_table)), 'rates differ'
        print('FUSED==REF', float(a.neurons.calcium.mean()))
    """, devices=4)
    assert "FUSED==REF" in out


def test_sparse_rate_exchange_identical_across_ranks():
    """Sparse subscription-based rate exchange == dense (R, n) all-gather,
    bit for bit, on a 4-rank mesh for BOTH activity lowerings — the
    demand-driven push ships the exact same f32 rates the dense table
    holds, and the Bernoulli stream is keyed by the edge id, independent of
    the exchange layout (DESIGN.md §7). Also asserts the exchange-volume
    win the accounting reports."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000, subs_cap_factor=1000)
        for impl in ['reference', 'fused']:
            res = {}
            for rex in ['dense', 'sparse']:
                cfg = dataclasses.replace(base, rate_exchange=rex,
                                          activity_impl=impl)
                init_fn, chunk = engine.build_sim(cfg,
                                                  engine.make_brain_mesh())
                st = init_fn()
                for _ in range(3):
                    st = chunk(st)
                res[rex] = st
            a, b = res['dense'], res['sparse']
            for f in ('v', 'u', 'calcium', 'rate', 'spike_count'):
                assert np.array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f))), \\
                    (impl, f)
            assert np.array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges)), impl
            assert np.array_equal(np.asarray(a.out_edges),
                                  np.asarray(b.out_edges)), impl
            dense_sent = float(a.stats['rates_sent'].sum())
            sparse_sent = float(b.stats['rates_sent'].sum())
            assert float(b.stats['subscription_overflow'].sum()) == 0.0
            assert 0 < sparse_sent < dense_sent, (dense_sent, sparse_sent)
        print('SPARSE==DENSE', dense_sent / sparse_sent)
    """, devices=4)
    assert "SPARSE==DENSE" in out


def test_sparse_rate_exchange_scenarios_identical():
    """The sparse == dense contract under all 3 library scenarios
    (populations, stimulation, lesion protocols) on a 4-rank mesh: the
    registry rebuild sees lesion-retracted edge tables and dead neurons
    advertising zero rates, and must still reproduce the dense state
    exactly."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        from repro.scenarios import Lesion, Recover, Stimulate, library
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000, subs_cap_factor=1000,
                           activity_impl='fused')
        def scaled(scn, div=20):
            evs = []
            for e in scn.events:
                if isinstance(e, Stimulate):
                    evs.append(dataclasses.replace(
                        e, t0=e.t0 // div,
                        t1=max(e.t1 // div, e.t0 // div + 10)))
                elif isinstance(e, (Lesion, Recover)):
                    evs.append(dataclasses.replace(e, t=e.t // div))
            return dataclasses.replace(scn, events=tuple(evs))
        for name in sorted(library.SCENARIOS):
            scn = scaled(library.get_scenario(name))
            res = {}
            for rex in ['dense', 'sparse']:
                cfg = dataclasses.replace(base, rate_exchange=rex)
                init_fn, chunk = engine.build_sim(
                    cfg, engine.make_brain_mesh(), scenario=scn)
                st = init_fn()
                for _ in range(3):
                    st = chunk(st)
                res[rex] = st
            a, b = res['dense'], res['sparse']
            for f in ('v', 'u', 'calcium', 'rate'):
                assert np.array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f))), \\
                    (name, f)
            assert np.array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges)), name
            assert np.array_equal(np.asarray(a.out_edges),
                                  np.asarray(b.out_edges)), name
        print('SCENARIOS SPARSE==DENSE')
    """, devices=4)
    assert "SCENARIOS SPARSE==DENSE" in out


_RUN_SCAN_CODE = """
    import dataclasses
    import jax, numpy as np
    from repro.configs.msp_brain import BrainConfig
    from repro.core import engine
    from repro.scenarios import Lesion, Recover, Stimulate, library
    from repro.sim import Simulator
    base = BrainConfig(neurons_per_rank=32, local_levels=3,
                       frontier_cap=32, max_synapses=8, rate_period=10,
                       requests_cap_factor=1000, subs_cap_factor=1000,
                       rate_exchange={rex!r})
    def scaled(scn, div=50):
        evs = []
        for e in scn.events:
            if isinstance(e, Stimulate):
                evs.append(dataclasses.replace(
                    e, t0=e.t0 // div, t1=max(e.t1 // div, e.t0 // div + 5)))
            elif isinstance(e, (Lesion, Recover)):
                evs.append(dataclasses.replace(e, t=e.t // div))
        return dataclasses.replace(scn, events=tuple(evs))
    for name in sorted(library.SCENARIOS):
        scn = scaled(library.get_scenario(name))
        for impl in ['reference', 'fused']:
            cfg = dataclasses.replace(base, activity_impl=impl)
            st_scan = Simulator.from_config(cfg, scenario=scn).run(2)
            init_fn, chunk = engine.build_sim(cfg, engine.make_brain_mesh(),
                                              scenario=scn)
            st = init_fn()
            for _ in range(2):
                st = chunk(st)
            for a, b in zip(jax.tree.leaves(st_scan), jax.tree.leaves(st)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                    (name, impl)
    print('RUN==SEQ')
"""


def test_simulator_run_scan_bit_identical_dense():
    """The facade's fused multi-chunk scan (Simulator.run(k)) == k
    sequential build_sim chunk dispatches, bit for bit, on a 4-rank mesh —
    every library scenario x both activity lowerings, dense exchange."""
    out = run_py(_RUN_SCAN_CODE.format(rex="dense"), devices=4)
    assert "RUN==SEQ" in out


def test_simulator_run_scan_bit_identical_sparse():
    """Same contract under the sparse subscription-based exchange."""
    out = run_py(_RUN_SCAN_CODE.format(rex="sparse"), devices=4)
    assert "RUN==SEQ" in out


def test_fused_connectivity_identical_across_ranks():
    """The Pallas traversal kernel == the reference phase-B bit-for-bit on a
    real multi-rank mesh (42B request routing, nonzero gid_base, gathered
    global tree on the old path all in play)."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000)
        res = {}
        for impl in ['reference', 'fused']:
            cfg = dataclasses.replace(base, connectivity_impl=impl)
            init_fn, chunk = engine.build_sim(cfg, engine.make_brain_mesh())
            st = init_fn()
            for _ in range(2):
                st = chunk(st)
            res[impl] = st
        a, b = res['reference'], res['fused']
        assert np.array_equal(np.asarray(a.out_edges),
                              np.asarray(b.out_edges)), 'out differs'
        assert np.array_equal(np.asarray(a.in_edges),
                              np.asarray(b.in_edges)), 'in differs'
        formed = float(a.stats['synapses_formed'].sum())
        assert formed > 0
        # old alg + fused impl: the gathered global tree path
        cfg = dataclasses.replace(base, connectivity_impl='fused',
                                  connectivity_alg='old')
        init_fn, chunk = engine.build_sim(cfg, engine.make_brain_mesh())
        st = init_fn()
        for _ in range(2):
            st = chunk(st)
        assert np.array_equal(np.sort(np.asarray(st.out_edges), 1),
                              np.sort(np.asarray(b.out_edges), 1)), 'old!=new'
        print('KERNEL==REF', formed)
    """, devices=4)
    assert "KERNEL==REF" in out


def test_fused_tree_apply_identical_across_ranks():
    """The radix-sort tree build + fused synapse-apply kernels == the jnp
    reference bit-for-bit on a real 4-rank mesh, under a lesion scenario so
    the deletion-routing buffer (route_build kernel) actually crosses the
    all-to-all with live messages."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        from repro.scenarios import Lesion, Recover, Stimulate, library
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000)
        def scaled(scn, div=20):
            evs = []
            for e in scn.events:
                if isinstance(e, Stimulate):
                    evs.append(dataclasses.replace(
                        e, t0=e.t0 // div,
                        t1=max(e.t1 // div, e.t0 // div + 10)))
                elif isinstance(e, (Lesion, Recover)):
                    evs.append(dataclasses.replace(e, t=e.t // div))
            return dataclasses.replace(scn, events=tuple(evs))
        scn = scaled(library.get_scenario('lesion_rewiring'))
        res = {}
        for impl in ['reference', 'fused']:
            cfg = dataclasses.replace(base, tree_impl=impl, apply_impl=impl)
            init_fn, chunk = engine.build_sim(cfg, engine.make_brain_mesh(),
                                              scenario=scn)
            st = init_fn()
            for _ in range(3):
                st = chunk(st)
            res[impl] = st
        a, b = res['reference'], res['fused']
        assert np.array_equal(np.asarray(a.out_edges),
                              np.asarray(b.out_edges)), 'out differs'
        assert np.array_equal(np.asarray(a.in_edges),
                              np.asarray(b.in_edges)), 'in differs'
        for f in ('v', 'calcium', 'rate'):
            assert np.array_equal(np.asarray(getattr(a.neurons, f)),
                                  np.asarray(getattr(b.neurons, f))), f
        formed = float(a.stats['synapses_formed'].sum())
        deleted = float(a.stats['synapses_deleted'].sum())
        assert formed > 0 and deleted > 0, (formed, deleted)
        print('TREEAPPLY==REF', formed, deleted)
    """, devices=4)
    assert "TREEAPPLY==REF" in out


def test_fused_tree_apply_old_new_scenarios_across_ranks():
    """The paper's old==new invariant survives the fused tree/apply kernels
    on a 4-rank mesh for every library scenario x dense/sparse rate
    exchange — the acceptance matrix of the whole-chunk-residency PR."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        from repro.scenarios import Lesion, Recover, Stimulate, library
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=25,
                           requests_cap_factor=1000, subs_cap_factor=1000,
                           tree_impl='fused', apply_impl='fused')
        def scaled(scn, div=20):
            evs = []
            for e in scn.events:
                if isinstance(e, Stimulate):
                    evs.append(dataclasses.replace(
                        e, t0=e.t0 // div,
                        t1=max(e.t1 // div, e.t0 // div + 10)))
                elif isinstance(e, (Lesion, Recover)):
                    evs.append(dataclasses.replace(e, t=e.t // div))
            return dataclasses.replace(scn, events=tuple(evs))
        for name in sorted(library.SCENARIOS):
            scn = scaled(library.get_scenario(name))
            for rex in ['dense', 'sparse']:
                res = {}
                for alg in ['old', 'new']:
                    cfg = dataclasses.replace(base, rate_exchange=rex,
                                              connectivity_alg=alg)
                    init_fn, chunk = engine.build_sim(
                        cfg, engine.make_brain_mesh(), scenario=scn)
                    st = init_fn()
                    for _ in range(2):
                        st = chunk(st)
                    res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                                np.sort(np.asarray(st.in_edges), 1),
                                float(st.stats['synapses_formed'].sum()))
                assert res['old'][2] == res['new'][2] > 0, (name, rex)
                assert np.array_equal(res['old'][0], res['new'][0]), \\
                    (name, rex, 'out')
                assert np.array_equal(res['old'][1], res['new'][1]), \\
                    (name, rex, 'in')
        print('OLD==NEW FUSED TREEAPPLY')
    """, devices=4)
    assert "OLD==NEW FUSED TREEAPPLY" in out


def test_spike_vs_rate_statistics():
    """New spike algorithm preserves mean activity (paper Fig 8/9)."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.core import engine
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=24,
                           fraction_excitatory=1.0, requests_cap_factor=1000)
        cal = {}
        for alg in ['old', 'new']:
            cfg = dataclasses.replace(base, spike_alg=alg)
            mesh = engine.make_brain_mesh()
            init_fn, chunk = engine.build_sim(cfg, mesh)
            st = init_fn()
            for _ in range(30):
                st = chunk(st)
            cal[alg] = float(np.mean(np.asarray(st.neurons.calcium)))
        rel = abs(cal['old'] - cal['new']) / max(cal['old'], 1e-9)
        assert rel < 0.25, cal
        print('CLOSE', cal)
    """)
    assert "CLOSE" in out


def test_moe_strategies_agree_on_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.parallel import sharding as shd
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg0 = get_smoke_config('arctic-480b').replace(
            scan_layers=True, capacity_factor=4.0)
        params = build_model(cfg0).init(jax.random.key(0))
        batch = {'tokens': jax.random.randint(jax.random.key(1), (8, 32),
                                              0, 512)}
        outs = {}
        for strat in ['local', 'move_compute', 'move_data']:
            cfg = cfg0.replace(parallel=cfg0.parallel.replace(
                moe_strategy=strat))
            api = build_model(cfg)
            def step(p, b):
                with shd.use_mesh(mesh):
                    return api.loss(p, b, mesh)[0]
            outs[strat] = float(jax.jit(step)(params, batch))
        assert abs(outs['local'] - outs['move_compute']) < 3e-2, outs
        assert abs(outs['local'] - outs['move_data']) < 3e-2, outs
        print('AGREE', outs)
    """)
    assert "AGREE" in out


def test_periodic_sync_equals_direct_when_delta_1():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.optim.periodic import (init_accumulator, init_error,
                                          make_periodic_steps)
        from repro.optim.optimizer import (OptimizerConfig, adamw_update,
                                           init_opt_state)
        from repro.parallel import sharding as shd
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        cfg = get_smoke_config('qwen2-7b').replace(dtype='float32')
        api = build_model(cfg)
        params = api.init(jax.random.key(0))
        opt_cfg = OptimizerConfig(grad_clip=0.0, warmup_steps=0)
        opt = init_opt_state(params, opt_cfg)
        batch = {'tokens': jax.random.randint(jax.random.key(1), (8, 32),
                                              0, 512)}
        # direct: plain global grad + update
        def lf(p):
            with shd.use_mesh(mesh):
                return api.loss(p, batch, mesh)[0]
        g = jax.jit(jax.grad(lf))(params)
        p_ref, _, _ = adamw_update(params, g, opt, opt_cfg)
        # periodic with Delta=1: accum once then sync
        accum, sync = make_periodic_steps(api, mesh, opt_cfg)
        acc = init_accumulator(params, mesh)
        err = init_error(params, mesh)
        acc, m = accum(params, acc, batch)
        p_new, opt2, acc, err, stats = sync(params, opt, acc, err)
        d = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(p_ref),
                                jax.tree.leaves(p_new)))
        assert d < 2e-5, d
        print('EQUAL', d)
    """)
    assert "EQUAL" in out


def test_pipeline_parallel_equals_sequential():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('stage',))
        L, d = 8, 16
        ks = jax.random.split(jax.random.key(0), L)
        w = jax.vmap(lambda k: jax.random.normal(k, (d, d)) * 0.2)(ks)
        def layer_fn(lp, x):  # lp: pytree slice for one layer
            return jnp.tanh(x @ lp['w'])
        xs = jax.random.normal(jax.random.key(1), (6, 3, d))  # (M, mb, d)
        # sequential reference
        def seq(x):
            for i in range(L):
                x = layer_fn({'w': w[i]}, x)
            return x
        ref = jax.vmap(seq)(xs)
        out = pipeline_apply(layer_fn, {'w': w}, xs, mesh, axis='stage')
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print('PIPE OK')
    """)
    assert "PIPE OK" in out


def test_elastic_remesh_restore():
    """Checkpoint on 8 devices -> restore + train on 4 devices."""
    out = run_py("""
        import os, tempfile
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.checkpoint.manager import save
        from repro.runtime.elastic import make_elastic_mesh, remesh_restore
        from repro.optim.optimizer import OptimizerConfig, init_opt_state
        from repro.launch.steps import make_train_step, opt_config_for
        from repro.parallel import sharding as shd

        cfg = get_smoke_config('qwen2-7b')
        api = build_model(cfg)
        params = api.init(jax.random.key(0))
        opt = init_opt_state(params, opt_config_for(cfg))
        d = tempfile.mkdtemp()
        save(d, 5, {'params': params, 'opt': opt})
        # new, smaller mesh from 4 surviving devices
        mesh = make_elastic_mesh(jax.devices()[:4])
        assert dict(mesh.shape) == {'data': 2, 'model': 2}, mesh.shape
        step, tree, shards = remesh_restore(d, {'params': params, 'opt': opt},
                                            mesh)
        assert step == 5
        train = jax.jit(make_train_step(api, mesh, opt_config_for(cfg)))
        batch = {'tokens': jax.random.randint(jax.random.key(1), (4, 32),
                                              0, 512)}
        p2, o2, m = train(tree['params'], tree['opt'], batch)
        assert bool(jnp.isfinite(m['loss'])), m
        print('ELASTIC OK', float(m['loss']))
    """)
    assert "ELASTIC OK" in out


def test_int8_compressed_sync_close_to_exact():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.parallel.compress import allreduce_int8
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('pod',))
        x = jax.random.normal(jax.random.key(0), (8, 128))
        def body(xl):
            red, err = allreduce_int8(xl[0], jnp.zeros_like(xl[0]), 'pod')
            return red[None], err[None]
        red, err = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P('pod'),),
            out_specs=(P('pod'), P('pod')), check_vma=False))(x)
        exact = jnp.mean(x, 0)
        rel = float(jnp.abs(red[0] - exact).max() /
                    jnp.abs(exact).max())
        assert rel < 0.05, rel
        print('INT8 OK', rel)
    """)
    assert "INT8 OK" in out


def test_from_connectome_old_new_identical_4ranks():
    """ISSUE 10 acceptance: growth from a generated hemibrain-shaped
    surrogate holds the old==new connectivity bit-identity on a 4-rank
    mesh — both algorithms rewire the loaded connectome identically."""
    out = run_py("""
        import dataclasses
        import numpy as np
        from repro.configs.msp_brain import SMOKE_CONFIG
        from repro.sim.api import Simulator
        from repro.workloads import datasets as wds
        base = dataclasses.replace(SMOKE_CONFIG, spike_alg='old',
                                   requests_cap_factor=1000)
        ds = wds.generate_hemibrain_surrogate(
            4 * 64, 64, max_degree=base.max_synapses,
            fraction_excitatory=base.fraction_excitatory)
        res = {}
        for alg in ['old', 'new']:
            cfg = dataclasses.replace(base, connectivity_alg=alg)
            sim = Simulator.from_connectome(cfg, ds)
            for _ in range(3):
                st = sim.step()
            res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                        np.sort(np.asarray(st.in_edges), 1),
                        float(st.stats['synapses_formed'].sum()))
        assert np.array_equal(res['old'][0], res['new'][0]), 'out differ'
        assert np.array_equal(res['old'][1], res['new'][1]), 'in differ'
        assert res['old'][2] == res['new'][2]
        print('CONN IDENTICAL', res['old'][2])
    """, devices=4)
    assert "CONN IDENTICAL" in out


def test_from_connectome_sparse_dense_identical_4ranks():
    """ISSUE 10 acceptance: on a loaded surrogate the sparse exchange
    (subscription registry sized from the MEASURED unique-remote-source
    count) stays bit-identical to the dense all-gather on 4 ranks."""
    out = run_py("""
        import dataclasses
        import numpy as np
        from repro.configs.msp_brain import SMOKE_CONFIG
        from repro.sim.api import Simulator
        from repro.workloads import datasets as wds
        base = dataclasses.replace(SMOKE_CONFIG, requests_cap_factor=1000)
        ds = wds.generate_hemibrain_surrogate(
            4 * 64, 64, max_degree=base.max_synapses,
            fraction_excitatory=base.fraction_excitatory)
        res = {}
        for layout in ['dense', 'sparse']:
            cfg = dataclasses.replace(base, rate_exchange=layout)
            sim = Simulator.from_connectome(cfg, ds)
            for _ in range(3):
                st = sim.step()
            res[layout] = (np.asarray(st.neurons.rate),
                           np.asarray(st.neurons.calcium),
                           np.sort(np.asarray(st.out_edges), 1))
        a, b = res['dense'], res['sparse']
        assert np.array_equal(a[0], b[0]), 'rates differ'
        assert np.array_equal(a[1], b[1]), 'calcium differ'
        assert np.array_equal(a[2], b[2]), 'edges differ'
        print('SPARSE==DENSE OK', float(a[0].sum()))
    """, devices=4)
    assert "SPARSE==DENSE OK" in out
