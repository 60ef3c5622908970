"""Fused activity megakernel: counter-hash PRNG properties, kernel-vs-oracle
bit-identity (interpret mode), engine reference==fused bit-identity, the
old==new connectivity invariant under the fused path for the library
scenarios, and the HBM-byte reduction claim."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.msp_brain import BrainConfig
from repro.core import engine
from repro.kernels import hash as chash
from repro.kernels import ref
from repro.kernels.activity_fused import (activity_window, window_hbm_bytes)
from repro.scenarios import Lesion, Recover, Scenario, Stimulate, library
from repro.scenarios.populations import build_table, population


# ---------------------------------------------------------------- hash
def test_hash_deterministic_and_distinct():
    e = jnp.arange(4096, dtype=jnp.int32)
    a = chash.uniform(7, chash.NOISE_DOMAIN, 3, e)
    b = chash.uniform(7, chash.NOISE_DOMAIN, 3, e)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # different step / entity / domain / seed all decorrelate
    for other in (chash.uniform(7, chash.NOISE_DOMAIN, 4, e),
                  chash.uniform(7, chash.SPIKE_DOMAIN, 3, e),
                  chash.uniform(8, chash.NOISE_DOMAIN, 3, e)):
        assert float((np.asarray(a) == np.asarray(other)).mean()) < 0.01


def test_hash_statistics():
    e = jnp.arange(1 << 16, dtype=jnp.int32)
    u = np.asarray(chash.uniform(0, chash.SPIKE_DOMAIN, 11, e))
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 5e-3
    z = np.asarray(chash.normal(0, chash.NOISE_DOMAIN, 11, e))
    assert abs(z.mean()) < 2e-2 and abs(z.std() - 1.0) < 2e-2
    assert np.isfinite(z).all()


def test_hash_matches_known_threefry_vectors():
    """Threefry-2x32, 20 rounds: reference vectors from the Random123
    distribution (key = counter = 0, and the all-ones pattern)."""
    x0, x1 = chash.threefry2x32(0, 0, 0, 0)
    assert (int(x0), int(x1)) == (0x6B200159, 0x99BA4EFE)
    ones = 0xFFFFFFFF
    x0, x1 = chash.threefry2x32(ones, ones, ones, ones)
    assert (int(x0), int(x1)) == (0x1CB996FC, 0xBB002BE7)


# ---------------------------------------------------------------- kernel
def _rand_inputs(n, s_max, num_ranks, key=0):
    k = jax.random.key(key)
    fi = lambda i: jax.random.fold_in(k, i)   # noqa: E731
    state = (jax.random.normal(fi(1), (n,)) * 5 - 60,
             jax.random.normal(fi(2), (n,)) * 2 - 13,
             jax.random.uniform(fi(3), (n,)),
             jax.random.uniform(fi(4), (n,)) * 2,
             jax.random.uniform(fi(5), (n,)) * 2,
             jax.random.bernoulli(fi(6), 0.15, (n,)),
             jnp.zeros((n,)))
    edges = jax.random.randint(fi(7), (n, s_max), -1,
                               num_ranks * n).astype(jnp.int32)
    w = jnp.where(jnp.arange(n) < int(0.8 * n), 15.0, -15.0)
    rates = jax.random.uniform(fi(8), (num_ranks, n)) * 0.2
    return state, edges, w.astype(jnp.float32), rates


def _izh(cfg, n, hetero):
    if not hetero:
        return tuple(jnp.full((n,), x, jnp.float32) for x in
                     (cfg.izh_a, cfg.izh_b, cfg.izh_c, cfg.izh_d,
                      cfg.element_growth_rate, cfg.target_calcium))
    t = build_table(cfg, (population("rs", 0.5, "RS"),
                          population("ch", 0.25, "CH", target_calcium=0.4),
                          population("fs", 0.25, "FS",
                                     is_excitatory=False)), n)
    return (t.izh_a, t.izh_b, t.izh_c, t.izh_d, t.growth_rate,
            t.target_calcium)


@pytest.mark.parametrize("hetero", [False, True])
@pytest.mark.parametrize("protocol", ["none", "stim", "stim+lesion"])
def test_fused_bit_identical_to_oracle(hetero, protocol):
    """The pallas megakernel (interpret) == the jnp scan oracle, bit for
    bit, across populations and protocol tables."""
    cfg = BrainConfig()
    n, s_max, R, T = 96, 8, 2, 40
    state, edges, w, rates = _rand_inputs(n, s_max, R)
    stim = lesions = None
    if "stim" in protocol:
        stim = (jnp.stack([(jnp.arange(n) < n // 2).astype(jnp.float32)]),
                ((4.0, 5, 30),))
    if "lesion" in protocol:
        lesions = (jnp.stack([jnp.arange(n) >= 3 * n // 4]), ((12, 25),))
    kw = dict(seed=cfg.seed, num_steps=T, izh=_izh(cfg, n, hetero),
              ca_consts=(cfg.calcium_decay, cfg.calcium_beta),
              stim=stim, lesions=lesions)
    chunk, rank = jnp.int32(2), jnp.int32(1)
    got, got_spk = jax.jit(lambda st: activity_window(
        st, edges, w, rates, 5.0, 1.0, chunk, rank, interpret=True,
        **kw))(state)
    want, want_spk = jax.jit(lambda st: ref.activity_window_ref(
        st, edges, w, rates, 5.0, 1.0, chunk, rank, **kw))(state)
    for name, a, b in zip(("v", "u", "ca", "ax", "de", "spiked", "count"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # the telemetry per-step spike counts match too — same reduction
    np.testing.assert_array_equal(np.asarray(got_spk), np.asarray(want_spk),
                                  err_msg="spikes_per_step")
    assert got_spk.shape == (T,)
    assert float(got[6].sum()) > 0, "window produced no spikes at all"
    if lesions is not None:
        # lesion window [12, 25) closed before T=40: elements regrow after
        assert float(got[3][3 * n // 4:].min()) > 0.0


def test_fused_window_equals_per_step_calls():
    """Delta-resident state is exactly iterated one-step calls: running the
    kernel with num_steps=T equals T kernel launches of num_steps=1 with
    the counter advanced — the stage-1/stage-2 equivalence."""
    cfg = BrainConfig()
    n, s_max, R, T = 64, 8, 2, 12
    state, edges, w, rates = _rand_inputs(n, s_max, R, key=9)
    kw = dict(izh=_izh(cfg, n, False),
              ca_consts=(cfg.calcium_decay, cfg.calcium_beta))
    win, win_spk = jax.jit(lambda st: activity_window(
        st, edges, w, rates, 5.0, 1.0, jnp.int32(0), jnp.int32(0),
        seed=0, num_steps=T, interpret=True, **kw))(state)
    # per-step launches: chunk=0 is baked into gstep = 0*1 + t ... so use
    # chunk=t with num_steps=1 => gstep = t, matching the window's stream
    step1 = jax.jit(lambda st, t: activity_window(
        st, edges, w, rates, 5.0, 1.0, t, jnp.int32(0),
        seed=0, num_steps=1, interpret=True, **kw))
    st = state
    spk = []
    for t in range(T):
        st, spk_t = step1(st, jnp.int32(t))
        spk.append(np.asarray(spk_t)[0])
    for a, b in zip(win, st):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(win_spk), np.asarray(spk))


# ---------------------------------------------------------------- engine
SMALL = dataclasses.replace(library.SMOKE_SCENARIO_CONFIG,
                            neurons_per_rank=48, max_synapses=8,
                            rate_period=25)


def _scaled(scn: Scenario, div=20) -> Scenario:
    """Library scenario with event times divided so they land inside a
    short (rate_period=25, 3-chunk) test run."""
    evs = []
    for e in scn.events:
        if isinstance(e, Stimulate):
            evs.append(dataclasses.replace(e, t0=e.t0 // div,
                                           t1=max(e.t1 // div, e.t0 // div + 10)))
        elif isinstance(e, (Lesion, Recover)):
            evs.append(dataclasses.replace(e, t=e.t // div))
    return dataclasses.replace(scn, events=tuple(evs))


def test_engine_fused_equals_reference():
    """activity_impl='fused' is bit-identical to 'reference' through the
    full jitted sim (state AND the edge tables the state drives)."""
    mesh = engine.make_brain_mesh()
    res = {}
    for impl in ("reference", "fused"):
        cfg = dataclasses.replace(SMALL, activity_impl=impl)
        init_fn, chunk = engine.build_sim(cfg, mesh)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[impl] = st
    a, b = res["reference"], res["fused"]
    for f in ("v", "u", "calcium", "ax_elements", "de_elements", "rate",
              "spike_count"):
        np.testing.assert_array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.out_edges),
                                  np.asarray(b.out_edges))
    np.testing.assert_array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges))


def test_fused_requires_new_spike_alg():
    # illegal combinations now fail eagerly, at config construction
    # (BrainConfig.__post_init__ -> sim.registry), never mid-trace
    with pytest.raises(ValueError, match="spike_alg"):
        dataclasses.replace(SMALL, activity_impl="fused", spike_alg="old")


@pytest.mark.parametrize("name", sorted(library.SCENARIOS))
def test_fused_old_new_connectivity_identical(name):
    """THE paper invariant under the megakernel: with activity_impl='fused'
    both connectivity algorithms still commit bit-identical edge tables,
    for every library scenario (populations, stimulation, lesion)."""
    scn = _scaled(library.get_scenario(name))
    mesh = engine.make_brain_mesh()
    res = {}
    for alg in ("old", "new"):
        cfg = dataclasses.replace(SMALL, activity_impl="fused",
                                  connectivity_alg=alg)
        init_fn, chunk = engine.build_sim(cfg, mesh, scenario=scn)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                    np.sort(np.asarray(st.in_edges), 1),
                    float(st.stats["synapses_formed"].sum()))
    assert res["old"][2] == res["new"][2] > 0
    np.testing.assert_array_equal(res["old"][0], res["new"][0])
    np.testing.assert_array_equal(res["old"][1], res["new"][1])


# ---------------------------------------------------------------- bytes
def test_fused_hbm_bytes_drop_3x():
    """Roofline-counted HBM bytes of one activity step: the fused window's
    streaming traffic must be >= 3x below the reference lowering's
    materialized buffers (acceptance criterion; bench_activity records the
    absolute numbers)."""
    from repro.launch import roofline
    cfg = dataclasses.replace(SMALL, rate_period=100)
    mesh = engine.make_brain_mesh()
    num_ranks = mesh.shape["ranks"]
    shapes = jax.eval_shape(lambda: engine.init_state(cfg, 0, num_ranks))
    specs = engine.state_specs(shapes)

    def body(st):
        rank = jax.lax.axis_index("ranks")
        return engine.activity_phase(st, cfg, rank, "ranks", num_ranks)

    act = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                out_specs=specs, check_vma=False))
    init_fn, _ = engine.build_sim(cfg, mesh)
    hlo = act.lower(init_fn()).compile().as_text()
    ref_bytes = roofline.materialized_bytes(hlo) / cfg.rate_period
    fused_bytes = window_hbm_bytes(cfg.neurons_per_rank, cfg.max_synapses,
                                   num_ranks) / cfg.rate_period
    assert ref_bytes / fused_bytes >= 3.0, (ref_bytes, fused_bytes)
