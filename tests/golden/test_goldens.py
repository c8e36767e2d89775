"""End-to-end numeric freeze against committed goldens.

tests/golden/goldens.npz pins the outputs of every canonical pipeline
on tiny models — txt2img, USDU tiled upscale, t2v, Flux/SD3 rectified
flow, inpaint/outpaint, hi-res-fix, Kontext editing, v-prediction, and
the beta/kl_optimal schedules (see scripts/gen_goldens.py) —
generated once by scripts/gen_goldens.py and committed. Any refactor
of samplers / schedulers / VAE / tokenizer / blend that shifts
end-to-end numerics fails here loudly: the substitute for the implicit
stability the reference inherits from ComfyUI's torch stack (reference
upscale/tile_ops.py:168 delegates all numerics there; with no egress,
no published weights can pin ours).

The check runs in a SUBPROCESS with a pinned 1-device CPU client:
XLA CPU numerics measurably depend on the host-platform device count
(see scripts/gen_goldens.py docstring — ~8e-4 in one VAE encode,
~2e-2 after two diffusion steps), and pytest's conftest forces an
8-device client for the mesh tests. Pinning the client makes the
comparison bit-stable on a given wheel; atol=1e-3 absorbs benign
cross-wheel codegen drift while real defects (wrong epsilon, boundary
semantics, schedule) move outputs by orders more. CDT_GOLDEN_ATOL
overrides when a new jaxlib legitimately shifts codegen.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SCRIPT = os.path.join(_REPO, "scripts", "gen_goldens.py")
_GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "goldens.npz")


def test_pipelines_match_goldens():
    assert os.path.exists(_GOLDEN_PATH), (
        "goldens.npz missing — run scripts/gen_goldens.py and commit it"
    )
    env = dict(os.environ)
    # pin the exact client the goldens were generated under: 1-device
    # CPU, no inherited multi-device XLA_FLAGS from conftest, no
    # numerics-shifting perf knobs from the caller's shell
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("CDT_TILE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, _SCRIPT, "--check"],
        capture_output=True, text=True, timeout=1800, cwd=_REPO, env=env,
    )
    sys.stdout.write(proc.stdout)
    assert proc.returncode == 0, (
        f"golden check failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr[-2000:]}"
    )
