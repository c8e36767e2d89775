"""What `scripts/kernel_bundles.py` and `scripts/loop_body_ops.py` share: a
child process that compiles for a described TPU v5e on the CPU, with no
chip, and the parent's call of it. Nothing here runs or times anything."""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_chip():
    """In the child, before anything else touches JAX: JAX held to the
    CPU, no compilation cache, and the first chip of a described
    `v5e:2x2` as the sharding to lower for."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def run_child(script: str, *args: str, **env: str) -> subprocess.CompletedProcess:
    """`script --child <args>` in a process of its own (a process that
    loaded libtpu to compile is not one to keep), with `env` added to
    this one's. A compile that failed says why in the result's `stderr`."""
    return subprocess.run(
        [sys.executable, os.path.abspath(script), "--child", *args],
        # a test run beside this one may hold libtpu's lock
        env=dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1", **env),
        cwd=REPO, capture_output=True, text=True)
