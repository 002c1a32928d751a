"""The chip entry-point contract, as far as a CPU can check it:
``chip_smoke.py`` refuses to run off-chip, its explicit dry run passes,
the compile cache is placed from outside, and the peaks table raises
for an accelerator it does not know."""

import json
import os
import subprocess
import sys
import types

import pytest

import tpuserver
from tpuserver.ops import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args):
    # inherits the environment conftest exported: JAX_PLATFORMS=cpu
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        timeout=600)


def test_smoke_fails_off_chip_and_names_the_platform():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    # no result line: nothing on stdout parses as the smoke's JSON
    assert '"ok"' not in proc.stdout


def test_smoke_dry_run_passes_every_phase():
    proc = _run_smoke("--dry-run-cpu")
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # the result line: exactly these keys, whatever else the smoke logs
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"}
    assert result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    summary = json.loads(lines[-2].split("] summary ", 1)[1])
    assert summary["ok"] is True and summary["dry_run"] is True
    assert set(summary["phases"].values()) == {"pass"}, summary["phases"]
    # interpreted kernels: no Mosaic call anywhere on the served path
    assert set(summary["custom_calls"].values()) == {0}


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    cache setting is process-wide and must not leak into this run)."""
    import jax

    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.update(
            {name: value}))
    return updates


def test_compile_cache_dir_comes_from_the_environment(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    tpuserver.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_dir_defaults_to_the_repo(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    tpuserver.enable_compile_cache()
    assert config_updates["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache")


def test_chip_spec_raises_for_an_unknown_accelerator():
    known = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert perf.chip_spec(known).hbm_bytes == 16 << 30
    assert perf.chip_spec() is None  # the CPU test mesh
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        perf.chip_spec(unknown)
