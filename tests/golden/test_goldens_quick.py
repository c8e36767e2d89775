"""Quick golden freeze for the `-m integration` middle tier: the
txt2img + USDU + schedule-pin subset of the full golden check (same
pinned 1-device client), skipping the compile-heavy model families so
the tier fits its <10-min budget. The full check lives in
test_goldens.py (slow tier)."""

import os
import subprocess
import sys

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SCRIPT = os.path.join(_REPO, "scripts", "gen_goldens.py")
_GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "goldens.npz"
)


def test_quick_goldens_match():
    assert os.path.exists(_GOLDEN_PATH), (
        "goldens.npz missing — run scripts/gen_goldens.py and commit it"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("CDT_TILE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, _SCRIPT, "--check", "--quick"],
        capture_output=True, text=True, timeout=600, cwd=_REPO, env=env,
    )
    sys.stdout.write(proc.stdout)
    assert proc.returncode == 0, (
        f"quick golden check failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr[-2000:]}"
    )
    # the quick subset must actually cover the two headline pipelines
    assert "txt2img_64" in proc.stdout and "usdu_64_to_128" in proc.stdout
