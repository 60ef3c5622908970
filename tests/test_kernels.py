"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.msp_brain import BrainConfig
from repro.kernels import ops, ref


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 2, 2, 128, 64),    # MHA
    (2, 4, 2, 256, 64),    # GQA 2:1
    (1, 8, 1, 256, 128),   # MQA
    (1, 2, 1, 384, 32),    # seq not multiple of 256
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, hq, hkv, s, d, dtype):
    k = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, hq, s, d)).astype(dtype)
    kk = jax.random.normal(jax.random.fold_in(k, 2), (b, hkv, s, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(k, 3), (b, hkv, s, d)).astype(dtype)
    o = ops.flash_attention(q, kk, v, causal=True, interpret=True)
    o_ref = ref.attention_ref(q, kk, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_local_window(window):
    k = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(k, 1), (1, 2, 256, 64))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (1, 1, 256, 64))
    v = jax.random.normal(jax.random.fold_in(k, 3), (1, 1, 256, 64))
    o = ops.flash_attention(q, kk, v, causal=True, window=window,
                            interpret=True)
    o_ref = ref.attention_ref(q, kk, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_chunked_jax_attention():
    """The production pure-JAX chunked attention and the Pallas kernel agree."""
    from repro.models.attention import chunked_attention
    k = jax.random.key(2)
    q = jax.random.normal(jax.random.fold_in(k, 1), (2, 4, 256, 64))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (2, 2, 256, 64))
    v = jax.random.normal(jax.random.fold_in(k, 3), (2, 2, 256, 64))
    o1 = ops.flash_attention(q, kk, v, causal=True, interpret=True)
    o2 = chunked_attention(q, kk, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)


# (300, 257): above the 256 block and not a multiple — exercises the pad-up
# path (the old code shrank the block toward 1 for primes)
@pytest.mark.parametrize("n,m", [(64, 64), (128, 192), (100, 60), (300, 257)])
@pytest.mark.parametrize("sigma", [0.1, 0.25, 0.75])
def test_bh_gauss(n, m, sigma):
    k = jax.random.key(3)
    x = jax.random.uniform(jax.random.fold_in(k, 1), (n, 3))
    y = jax.random.uniform(jax.random.fold_in(k, 2), (m, 3))
    w = jax.random.uniform(jax.random.fold_in(k, 3), (m,)) * 3
    p, rs = ops.gauss_probs(x, y, w, sigma=sigma, interpret=True)
    pr, rr = ref.bh_gauss_ref(x, y, w, sigma=sigma)
    # |x|^2+|y|^2-2xy cancellation is amplified by exp(-d2/sigma^2) at small
    # sigma (documented caveat of the MXU-identity form)
    tol = 1e-5 if sigma >= 0.25 else 2e-3
    np.testing.assert_allclose(np.asarray(p), np.asarray(pr),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(rr),
                               rtol=max(tol, 1e-4), atol=max(tol, 1e-4))


@pytest.mark.parametrize("n,block", [(131, 64), (1031, 1024)])
def test_neuron_step_pads_non_divisible_n(n, block):
    """n not divisible by the block is padded up and sliced, instead of
    shrinking the block to a divisor (prime n used to degrade to block=1)."""
    from repro.kernels.neuron_step import neuron_step
    cfg = BrainConfig()
    k = jax.random.key(11)
    v = jax.random.normal(jax.random.fold_in(k, 1), (n,)) * 5 - 60
    u = jax.random.normal(jax.random.fold_in(k, 2), (n,)) * 2 - 13
    ca = jax.random.uniform(jax.random.fold_in(k, 3), (n,))
    ax = jax.random.uniform(jax.random.fold_in(k, 4), (n,)) * 2
    de = jax.random.uniform(jax.random.fold_in(k, 5), (n,)) * 2
    inp = jax.random.normal(jax.random.fold_in(k, 6), (n,)) * 5
    outs = neuron_step(v, u, ca, ax, de, inp, cfg, block=block,
                       interpret=True)
    refs = ref.neuron_step_ref(v, u, ca, ax, de, inp, cfg)
    for name, a, b in zip(["v", "u", "ca", "ax", "de"], outs, refs):
        assert a.shape == (n,)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3, err_msg=name)


@pytest.mark.parametrize("n", [64, 1000, 4096])
def test_neuron_step(n):
    cfg = BrainConfig()
    k = jax.random.key(4)
    v = jax.random.normal(jax.random.fold_in(k, 1), (n,)) * 5 - 60
    u = jax.random.normal(jax.random.fold_in(k, 2), (n,)) * 2 - 13
    ca = jax.random.uniform(jax.random.fold_in(k, 3), (n,))
    ax = jax.random.uniform(jax.random.fold_in(k, 4), (n,)) * 2
    de = jax.random.uniform(jax.random.fold_in(k, 5), (n,)) * 2
    inp = jax.random.normal(jax.random.fold_in(k, 6), (n,)) * 5
    outs = ops.fused_neuron_step(v, u, ca, ax, de, inp, cfg, interpret=True)
    refs = ref.neuron_step_ref(v, u, ca, ax, de, inp, cfg)
    # v/u can amplify 1-ulp differences near the spike threshold
    names = ["v", "u", "ca", "ax", "de", "spiked"]
    tols = {"v": 1e-3, "u": 1e-3, "ca": 1e-5, "ax": 1e-5, "de": 1e-5}
    for name, a, b in zip(names, outs, refs):
        if name == "spiked":
            assert (np.asarray(a) != np.asarray(b)).mean() < 0.01
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tols[name], atol=tols[name],
                                       err_msg=name)


def test_neuron_step_heterogeneous_populations():
    """Per-neuron parameter arrays (mixed Izhikevich types) through the fused
    kernel match the oracle — and differ from the homogeneous run."""
    from repro.core.neuron import NeuronParams
    from repro.scenarios.populations import build_table, population
    cfg = BrainConfig()
    n = 256
    t = build_table(cfg, (population("rs", 0.5, "RS"),
                          population("ch", 0.25, "CH", target_calcium=0.4),
                          population("fs", 0.25, "FS",
                                     is_excitatory=False)), n)
    params = NeuronParams(t.izh_a, t.izh_b, t.izh_c, t.izh_d,
                          t.growth_rate, t.target_calcium)
    k = jax.random.key(7)
    v = jax.random.normal(jax.random.fold_in(k, 1), (n,)) * 5 - 60
    u = jax.random.normal(jax.random.fold_in(k, 2), (n,)) * 2 - 13
    ca = jax.random.uniform(jax.random.fold_in(k, 3), (n,))
    ax = jax.random.uniform(jax.random.fold_in(k, 4), (n,)) * 2
    de = jax.random.uniform(jax.random.fold_in(k, 5), (n,)) * 2
    inp = jax.random.normal(jax.random.fold_in(k, 6), (n,)) * 5
    outs = ops.fused_neuron_step(v, u, ca, ax, de, inp, cfg, params=params,
                                 interpret=True)
    refs = ref.neuron_step_ref(v, u, ca, ax, de, inp, cfg, params=params)
    homog = ref.neuron_step_ref(v, u, ca, ax, de, inp, cfg)
    for name, a, b in zip(["v", "u", "ca", "ax", "de", "spiked"], outs, refs):
        if name == "spiked":
            assert (np.asarray(a) != np.asarray(b)).mean() < 0.01
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3, err_msg=name)
    # the FS block (a=0.1) really takes a different trajectory
    assert not np.allclose(np.asarray(outs[1])[192:], np.asarray(homog[1])[192:])


def test_kernel_engine_integration():
    """bh_gauss is the oracle for the brain sim's leaf-level probabilities."""
    from repro.core.barnes_hut import _gauss
    x = jnp.array([[0.1, 0.2, 0.3]])
    y = jnp.array([[0.15, 0.2, 0.3], [0.9, 0.9, 0.9]])
    w = jnp.array([2.0, 1.0])
    p, _ = ops.gauss_probs(x, y, w, sigma=0.25, interpret=True)
    d2 = jnp.sum((x[:, None] - y[None]) ** 2, -1)
    expected = w * _gauss(d2, 0.25)
    np.testing.assert_allclose(np.asarray(p), np.asarray(expected), rtol=1e-5)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """Kernels compile on a TPU and are interpreted on the CPU only; any
    other backend is refused rather than silently interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is interpret
