"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its two
phases pass at a tiny size on CPU devices, and the compile-cache helper
puts the cache where the environment says."""
import os
import shutil
import subprocess
import sys
import textwrap

import jax

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code_or_args, env_extra=None, cwd=ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + ROOT)
    env.update(env_extra or {})
    args = code_or_args if isinstance(code_or_args, list) else \
        ["-c", textwrap.dedent(code_or_args)]
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=cwd)


def test_chip_smoke_refuses_cpu():
    proc = _run([os.path.join(ROOT, "chip_smoke.py")],
                {"JAX_COMPILATION_CACHE_DIR": ""})
    assert proc.returncode != 0, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """A directory holding the script and nothing else of the repo."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, timeout=120,
                          cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_one_chip_phase_on_cpu(capsys):
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    from repro.configs.msp_brain import SMOKE_CONFIG
    chip_smoke.one_chip(SMOKE_CONFIG, jax.devices()[:1])
    out = capsys.readouterr().out
    assert "health_flags=0" in out
    assert "every out-edge pairs with one in-edge" in out
    assert "steady:" in out and "peak_bytes_in_use:" in out


def test_chip_smoke_four_chip_phase_on_host_devices():
    """The phase passes, and each variant's run() reuses the program the
    concurrent pre-compile built: it compiles nothing itself."""
    proc = _run("""
        import jax
        import chip_smoke
        from repro.configs.msp_brain import SMOKE_CONFIG
        chip_smoke.four_chips(SMOKE_CONFIG, jax.devices()[:4])
    """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
          "JAX_COMPILATION_CACHE_DIR": ""}, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert "sparse == dense" in proc.stdout
    assert "old == new" in proc.stdout
    assert "per-rank bh_requests" in proc.stdout
    assert proc.stdout.count(", 0 compile(s)") == 3, proc.stdout


def test_chip_smoke_edge_pairing_detects_a_dangling_edge():
    import numpy as np
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    out = np.array([[1, -1], [-1, -1], [0, -1]], np.int32)
    inn = np.array([[2, -1], [0, -1], [-1, -1]], np.int32)
    assert chip_smoke.edges_paired(out, inn)
    inn[1, 0] = -1
    assert not chip_smoke.edges_paired(out, inn)


def test_compile_cache_follows_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there; without
    it, the cache is the checkout's one fixed directory."""
    code = """
        import jax
        from chip_smoke import use_compile_cache
        path = use_compile_cache()
        print(path, jax.config.jax_compilation_cache_dir)
        if {compile}:
            jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()
    """
    cache = tmp_path / "cache"
    proc = _run(code.format(compile=True),
                {"JAX_COMPILATION_CACHE_DIR": str(cache),
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache)] * 2
    assert any(cache.iterdir())
    proc = _run(code.format(compile=False),
                {"JAX_COMPILATION_CACHE_DIR": ""})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [os.path.join(ROOT, ".jax_cache")] * 2
