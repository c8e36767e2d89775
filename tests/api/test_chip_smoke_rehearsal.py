"""chip_smoke.py end to end in its rehearsal mode (integration tier):
the same legs, requests and checks as on the chip — a CLI server child,
both workflows over HTTP cold and warm, a restart that must hit the
compile cache and reproduce the bytes, the attention child, the experts
child — at toy size on the CPU, because the caller said so."""

import json
import os
import socket
import subprocess
import sys

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def test_chip_smoke_rehearsal_passes(tmp_path):
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"),
         "--rehearsal", "--out", str(tmp_path / "out"), "--port", str(port)],
        capture_output=True, text=True, timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "rehearsal": True,
    }
    assert "REHEARSAL" in lines[0]
    for leg in ("serve", "restart", "attention", "experts"):
        assert any(f"leg {leg} passed" in line for line in lines), leg
    assert any("bytes identical to the first server's" in line for line in lines)
    # the attention child ran both routes at every toy shape, a padded one too
    assert any('"route": "flash"' in line and "pad608x640" in line for line in lines)
    assert any('"route": "xla"' in line and '"flash": {"entry"' in line for line in lines)
    # and the causal calls, the kernel under its mask (interpreted) beside the XLA form
    assert any(
        '"shape": "toy causal window"' in line and "flash-causal" in line and "xla-causal" in line
        and '"ok": true' in line for line in lines)
    # and the single-query kernel (interpreted) against the einsum form
    assert any('"shape": "toy decode slot"' in line and '"ok": true' in line for line in lines)
    # and a decode step's grouped products, the kernel (interpreted) against `ragged_dot`
    assert any(
        '"shape": "toy step off the sublane tile"' in line and '"ok": true' in line
        for line in lines)
