"""CPU rehearsal of ``chip_smoke.py`` and the compile-cache helper.

The smoke's phases run here at a tiny size with ``backend="pallas"``
(kernels in interpret mode) and make the same checks as on the chip:
self-match, pallas ≡ jnp top-k ids, distributed ≡ batched ids on four
virtual CPU devices.  The script itself must refuse to run without a
TPU, and without the rest of the repository.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_smoke_phases_pallas_interpret():
    db, dev_series = chip_smoke.phase_archive(1024, 0, backend="pallas")
    with db:
        out = chip_smoke.phase_query(db, dev_series, 0, n_requests=8,
                                     n_compare=4)
    assert 0.0 < out["precision"] <= 1.0
    chip_smoke.phase_long(256, 0, backend="pallas", length=256,
                          n_requests=2)
    chip_smoke.phase_ingest(256, 0, backend="pallas", length=256, blocks=2,
                            block_rows=32)


def test_distributed_phase_on_four_cpu_devices():
    """The ``--chips 4`` phase on virtual devices, in a child process
    that sees only the CPU backend."""
    code = ("import chip_smoke; "
            "chip_smoke.phase_distributed(1024, 0, backend='pallas', "
            "n_queries=4)")
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "devices=4" in proc.stdout
    assert "ids_equal_to_batched=4/4" in proc.stdout


def _run_script(cwd):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=300)


def test_script_refuses_cpu():
    proc = _run_script(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
