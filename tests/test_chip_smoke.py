"""chip_smoke.py's phases at CPU sizes (Pallas in interpret mode), its
refusal to run off the chip, and the compile-cache helper every entry
point calls."""
import os
import subprocess
import sys

import jax
import pytest

from test_distributed import run_py

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# a phase-A world that learns well past chance on the CPU in ~30 s (near-
# iid labels: 4 non-iid workers stay at chance for hundreds of steps)
SMALL_DEFTA = dict(workers=4, hw=8, width=8, n_per_worker=64, alpha=100.0,
                   local_epochs=40, epochs=4, eval_every=2)


def test_phase_defta_runs_at_cpu_size():
    assert chip_smoke.phase_defta(**SMALL_DEFTA) == []


def test_phase_train_runs_reduced_model():
    assert chip_smoke.phase_train(["--smoke"]) == []


def test_four_chip_phases_on_host_devices():
    """--four-chips' phases on 4 forced host devices: the sharded worker
    axis against one device, the pod-mesh ppermute ring against in-jit."""
    out = run_py(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        assert chip_smoke.phase_shards(workers=64) == []
        assert chip_smoke.phase_pod_mesh(extra=["--smoke"]) == []
        print("ok")
    """, devices=4)
    assert "[S] err_params=" in out and "[M] worst_err" in out


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_refuses_without_a_tpu(args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        *args], capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "[A]" not in r.stdout, r.stdout
    assert "no TPU" in r.stderr, r.stderr


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    from repro import cache
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing of its own
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    from repro import cache
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert path == cache.compile_cache_dir()
        assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
