"""Persistent XLA compilation cache: a second process start skips the
recompile.

First compiles dominate a cold start and would otherwise be re-paid by
EVERY worker process. These tests prove the wiring end to end on CPU:
`configure_compile_cache()` turns the cache on with thresholds zeroed
(at JAX_COMPILATION_CACHE_DIR when the environment places it, else at
one fixed in-checkout path), the first process populates it, and a
fresh process hits it — observed through the same jax.monitoring counters
that feed cdt_jax_cache_hits/misses on /distributed/metrics."""

import json
import os
import subprocess
import sys

import pytest

from comfyui_distributed_tpu.utils import constants

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One tiny jit program compiled under the configured cache; prints the
# monitoring tallies so the parent can assert hit/miss behavior.
_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from comfyui_distributed_tpu.workers.startup import configure_compile_cache
from comfyui_distributed_tpu.telemetry.runtime import runtime_snapshot
cache_dir = configure_compile_cache()
f = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
f(jnp.ones((16, 16))).block_until_ready()
snap = runtime_snapshot()
print(json.dumps({
    "cache_dir": cache_dir,
    "configured_dir": snap.get("compile_cache_dir"),
    "hits": snap["cache_hits"],
    "misses": snap["cache_misses"],
}))
"""


def _run_child(cache_env: str | None, cwd: str | None = None) -> dict:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.pop(constants.COMPILE_CACHE_ENV, None)
    if cache_env is not None:
        env[constants.COMPILE_CACHE_ENV] = cache_env
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd,
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_second_process_start_skips_recompile(tmp_path):
    """Two cold process starts sharing one cache dir: the first misses
    and populates, the second HITS and compiles nothing from scratch —
    the cache-dir smoke the CI job runs."""
    cache_dir = str(tmp_path / "xla-cache")
    first = _run_child(cache_dir)
    assert first["cache_dir"] == cache_dir
    assert first["configured_dir"] == cache_dir
    assert first["misses"] > 0
    assert first["hits"] == 0
    assert os.listdir(cache_dir), "first process persisted nothing"

    second = _run_child(cache_dir)
    assert second["hits"] > 0, second
    assert second["misses"] == 0, second


def test_env_places_the_cache_and_code_sets_no_directory(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set jax reads it by itself:
    configure_compile_cache must not write a directory into the
    config (an in-code path would override the operator's)."""
    import jax

    from comfyui_distributed_tpu.workers import startup

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append(name)
    )
    monkeypatch.setenv(constants.COMPILE_CACHE_ENV, str(tmp_path / "placed"))
    startup.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_persistent_cache_min_compile_time_secs" in updates

    updates.clear()
    monkeypatch.delenv(constants.COMPILE_CACHE_ENV)
    monkeypatch.setattr(
        constants, "default_compile_cache_dir", lambda: str(tmp_path / "dflt")
    )
    monkeypatch.setattr(
        startup, "default_compile_cache_dir", lambda: str(tmp_path / "dflt")
    )
    startup.configure_compile_cache()
    assert "jax_compilation_cache_dir" in updates


def test_default_cache_dir_is_fixed_under_the_checkout(tmp_path):
    """Unset, the cache is at ONE path derived from the package's
    location — the path is part of the cache key, so a directory that
    follows the working directory never hits."""
    expected = os.path.join(REPO_ROOT, ".cdt", "compile_cache")
    assert constants.default_compile_cache_dir() == expected
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    from_repo = _run_child(None, cwd=REPO_ROOT)
    from_elsewhere = _run_child(None, cwd=str(elsewhere))
    assert from_repo["cache_dir"] == expected
    assert from_elsewhere["cache_dir"] == expected
    assert not (elsewhere / ".cdt").exists()


def test_tile_scan_batch_platform_default(monkeypatch):
    """CPU default stays 1 (golden-exact); CDT_TILE_BATCH overrides."""
    monkeypatch.delenv("CDT_TILE_BATCH", raising=False)
    assert constants.tile_scan_batch() == 1  # suite runs on CPU
    monkeypatch.setenv("CDT_TILE_BATCH", "8")
    assert constants.tile_scan_batch() == 8
    monkeypatch.setenv("CDT_TILE_BATCH", "garbage")
    assert constants.tile_scan_batch() == 1


def test_tile_scan_batch_does_not_hide_a_dead_backend(monkeypatch):
    """A backend that cannot answer is an error where the batch size is
    chosen, not a quiet CPU default on an accelerator host."""
    import jax

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv("CDT_TILE_BATCH", raising=False)
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="tpu"):
        constants.tile_scan_batch()
