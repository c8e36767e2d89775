"""Native data-plane bindings: compile-on-first-use C++ with numpy
fallback.

`get_lib()` returns the ctypes module or None (no toolchain); the
public wrappers (`u8_to_f32`, `f32_to_u8`, `feathered_blend_inplace`,
`content_hash`) always work — native when available, numpy otherwise —
and are drop-in equal (tests pin exact equality).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..utils.logging import log

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _source_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "blendlib.cpp")


def _build_dir() -> str:
    return os.environ.get(
        "CDT_NATIVE_BUILD_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "build"),
    )


# -ffp-contract=off: GCC's default contraction may fuse the blend lerp
# (`region*inv + tile*m`) into an FMA, which rounds once instead of
# twice — ulp-different from the numpy fallback and from eager XLA CPU.
# The device-canvas bit-identity gate (DeviceCanvas ≡
# DeterministicHostCanvas) requires all three paths to round alike.
_CXX_FLAGS = ("-O3", "-march=native", "-ffp-contract=off", "-shared", "-fPIC")


def _host_tag() -> bytes:
    """What -march=native resolves to on THIS host, in the compiler's
    own words. Part of the artefact key, so a build directory copied to
    another machine (the chip tool copies the tree as it stands) is
    rebuilt there instead of loaded. No compiler, no tag — and no build
    either: _compile reports that."""
    try:
        return subprocess.run(
            ["g++", "-march=native", "-Q", "--help=target"],
            check=True, capture_output=True, timeout=30,
        ).stdout
    except (OSError, subprocess.SubprocessError):
        return b""


def _compile() -> Optional[str]:
    src = _source_path()
    out_dir = _build_dir()
    os.makedirs(out_dir, exist_ok=True)
    # cache key: source + flags + host digest, so edits, flag changes
    # OR a different CPU rebuild
    with open(src, "rb") as fh:
        hasher = hashlib.sha256(fh.read())
    hasher.update(" ".join(_CXX_FLAGS).encode())
    hasher.update(_host_tag())
    digest = hasher.hexdigest()[:16]
    so_path = os.path.join(out_dir, f"blendlib_{digest}.so")
    if os.path.isfile(so_path):
        return so_path
    # build to a private name and rename: a co-hosted worker starting
    # at the same moment must never dlopen a half-written file
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXX_FLAGS, src, "-o", tmp_path]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, so_path)
        return so_path
    except (OSError, subprocess.SubprocessError) as exc:
        log(f"native build failed ({exc}); using the numpy data plane")
        return None


def backend() -> str:
    """Which data plane this process runs: "native" (the compiled C++
    library) or "numpy" (its drop-in twin — no toolchain, or the build
    failed; the reason is logged once by the build). The server logs
    this at start-up and reports it in /distributed/system_info."""
    return "native" if get_lib() is not None else "numpy"


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        so_path = _compile()
        if so_path is None:
            _lib_failed = True
            return None
        lib = ctypes.CDLL(so_path)
        lib.u8_to_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t
        ]
        lib.f32_to_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t
        ]
        lib.feathered_blend.argtypes = [ctypes.c_void_p] * 3 + [ctypes.c_int64] * 8
        lib.weighted_accumulate.argtypes = (
            [ctypes.c_void_p] * 4 + [ctypes.c_int64] * 8
        )
        lib.fnv1a64.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.fnv1a64.restype = ctypes.c_uint64
        _lib = lib
        return _lib


def u8_to_f32(src: np.ndarray) -> np.ndarray:
    src = np.ascontiguousarray(src, dtype=np.uint8)
    lib = get_lib()
    if lib is None:
        return src.astype(np.float32) / 255.0
    dst = np.empty(src.shape, dtype=np.float32)
    lib.u8_to_f32(src.ctypes.data, dst.ctypes.data, src.size)
    return dst


def f32_to_u8(src: np.ndarray) -> np.ndarray:
    src = np.ascontiguousarray(src, dtype=np.float32)
    lib = get_lib()
    if lib is None:
        return (np.clip(src, 0.0, 1.0) * 255.0 + 0.5).astype(np.uint8)
    dst = np.empty(src.shape, dtype=np.uint8)
    lib.f32_to_u8(src.ctypes.data, dst.ctypes.data, src.size)
    return dst


def feathered_blend_inplace(
    canvas: np.ndarray, tile: np.ndarray, mask: np.ndarray, y: int, x: int
) -> None:
    """canvas[:, y:y+th, x:x+tw, :] = lerp(canvas, tile, mask); all
    float32 contiguous, canvas modified in place."""
    assert canvas.flags["C_CONTIGUOUS"] and canvas.dtype == np.float32
    tile = np.ascontiguousarray(tile, dtype=np.float32)
    mask = np.ascontiguousarray(mask, dtype=np.float32)
    b, th, tw, c = tile.shape
    _, ch, cw, _ = canvas.shape
    lib = get_lib()
    if lib is None:
        region = canvas[:, y : y + th, x : x + tw, :]
        m = mask[None, :, :, None]
        region *= 1.0 - m
        region += tile * m
        return
    lib.feathered_blend(
        canvas.ctypes.data, tile.ctypes.data, mask.ctypes.data,
        b, th, tw, c, ch, cw, y, x,
    )


def weighted_accumulate_inplace(
    canvas: np.ndarray, weights: np.ndarray, tile: np.ndarray,
    mask: np.ndarray, y: int, x: int,
) -> None:
    """canvas[:, win] += tile*mask; weights[win] += mask (in place)."""
    assert canvas.flags["C_CONTIGUOUS"] and weights.flags["C_CONTIGUOUS"]
    tile = np.ascontiguousarray(tile, dtype=np.float32)
    mask = np.ascontiguousarray(mask, dtype=np.float32)
    b, th, tw, c = tile.shape
    _, ch, cw, _ = canvas.shape
    lib = get_lib()
    if lib is None:
        m = mask[None, :, :, None]
        canvas[:, y : y + th, x : x + tw, :] += tile * m
        weights[y : y + th, x : x + tw] += mask
        return
    lib.weighted_accumulate(
        canvas.ctypes.data, weights.ctypes.data, tile.ctypes.data,
        mask.ctypes.data, b, th, tw, c, ch, cw, y, x,
    )


def content_hash(data: bytes | np.ndarray) -> int:
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data).tobytes()
    lib = get_lib()
    if lib is None:
        h = 1469598103934665603
        for byte in data:
            h = ((h ^ byte) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        return h
    buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
    return int(lib.fnv1a64(ctypes.addressof(buf), len(data)))
