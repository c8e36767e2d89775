"""Input/output directory resolution for media nodes.

The reference delegates to ComfyUI's folder_paths; here directories
come from config (settings.output_dir / settings.input_dir) with
sane defaults under the repo/package root, overridable by env.
"""

from __future__ import annotations

import os
import threading

from ..utils.exceptions import DistributedError


def _base_dir() -> str:
    return os.environ.get("CDT_DATA_DIR", os.path.join(os.getcwd(), "data"))


def get_output_dir(context=None) -> str:
    cfg = getattr(context, "config", None) or {}
    return (
        os.environ.get("CDT_OUTPUT_DIR")
        or cfg.get("settings", {}).get("output_dir")
        or os.path.join(_base_dir(), "output")
    )


def get_input_dir(context=None) -> str:
    cfg = getattr(context, "config", None) or {}
    return (
        os.environ.get("CDT_INPUT_DIR")
        or cfg.get("settings", {}).get("input_dir")
        or os.path.join(_base_dir(), "input")
    )


def resolve_input_path(name: str, context=None) -> str:
    """Find a media file by name: absolute paths pass through; bare
    names resolve against the input dir. Rejects path escapes."""
    if os.path.isabs(name):
        return name
    base = get_input_dir(context)
    path = os.path.normpath(os.path.join(base, name))
    if not path.startswith(os.path.normpath(base) + os.sep) and path != os.path.normpath(base):
        raise DistributedError(f"input path {name!r} escapes input dir")
    return path


def next_counter(out_dir: str, prefix: str, ext: str) -> int:
    """First free <prefix>_NNNNN.<ext> counter: max existing + 1 (the
    ComfyUI counter-scan convention — never clobbers on gaps, unlike a
    len() count). Shared by SaveImage and the animated savers."""
    suffix = f".{ext}"
    start = 0
    for f in os.listdir(out_dir):
        if not (f.startswith(f"{prefix}_") and f.endswith(suffix)):
            continue
        stem = f[len(prefix) + 1 : -len(suffix)]
        if stem.isdigit():
            start = max(start, int(stem) + 1)
    return start


# (directory, prefix, ext) -> first counter not yet handed out in this
# process. A file named by `reserve_counter` may be written later, on
# another thread, so the directory scan alone would name it twice.
_reserved: dict[tuple[str, str, str], int] = {}
_reserved_lock = threading.Lock()


def reserve_counter(out_dir: str, prefix: str, ext: str, count: int = 1) -> int:
    """The first of `count` counters that no file on disk and no
    earlier call in this process has: max(`next_counter`, last
    reserved + 1)."""
    key = (os.path.abspath(out_dir), prefix, ext)
    with _reserved_lock:
        start = max(next_counter(out_dir, prefix, ext), _reserved.get(key, 0))
        _reserved[key] = start + count
    return start
