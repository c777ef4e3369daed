"""The training launcher, the compile-cache placement and the chip smoke's
phases, run on the CPU at scaled-down size."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.launch import train
from repro.launch.compile_cache import CHECKOUT, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture()
def cache_env(tmp_path, monkeypatch):
    """Entry points place the compile cache; keep it out of the checkout
    (and the process's config untouched) while tests call them."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    return str(tmp_path / "jc")


def test_compile_cache_env_is_left_to_jax(cache_env):
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == cache_env
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(ROOT / ".jax_cache") == str(CHECKOUT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_launcher_replicates_then_resumes_in_process(tmp_path, cache_env):
    argv = ["--arch", "qwen1.5-0.5b", "--batch-size", "2", "--seq-len", "32",
            "--ckpt-dir", str(tmp_path / "run"), "--ckpt-every", "3",
            "--replicate-to", "s3"]
    result, reps = train.main(argv + ["--steps", "3"])
    assert result.steps_run == 3 and result.losses[-1][0] == 3
    assert [t.status for t in reps] == [reps[0].SUCCEEDED]
    result, reps = train.main(argv + ["--steps", "5"])
    assert result.restored_from == 3 and result.steps_run == 2
    assert reps == []  # step 5 is saved, not replicated


def test_launcher_needs_a_ckpt_dir():
    with pytest.raises(SystemExit):
        train.main(["--arch", "qwen1.5-0.5b"])


def test_chip_smoke_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_one_chip_phases(tmp_path, cache_env, capsys):
    smoke = _chip_smoke()
    with smoke.PhaseTimer() as phase:
        smoke.one_chip(str(tmp_path), phase, scaled=True)
    out = capsys.readouterr().out
    assert "restored_from == 3" in out
    assert "device lanesum32 == manifest digest for 43/43 leaves" in out
    assert "s of it compiling" in out


def test_chip_smoke_four_device_restore_under_another_mesh(tmp_path):
    """(2, 2)-mesh save -> (4, 1)-mesh restore, on 4 virtual CPU devices
    (the device count is fixed before JAX starts, hence the child)."""
    script = ("import sys, chip_smoke as s\n"
              "with s.PhaseTimer() as phase:\n"
              "    s.four_chips(sys.argv[1], phase, scaled=True)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "43/43 leaves bit-equal to the one-device restore" in out.stdout
    assert "sharded leaves split across the devices" in out.stdout
    assert "on the (4, 1) mesh" in out.stdout.splitlines()[-1]
