"""Step-resumable tile sampling: the checkpoint seam for step-level
preemption (graph/batch_executor.py).

The classic tile processor (graph/usdu_elastic._jit_tile_processor)
runs the whole denoise trajectory as one ``lax.scan`` — perfect for
throughput, opaque to the scheduler: a premium-lane job arriving
mid-grant waits out every remaining step of every in-flight tile. This
module re-expresses the same trajectory as three pure programs:

    init(params, tile, key)                 -> x   (encode + noise)
    step(params, x, key, pos, neg, yx, i)   -> x   (ONE denoise step)
    finish(params, x)                       -> tile output (decode)

so an executor may stop between any two steps, checkpoint ``x`` (plus
the step index and the tile's fold key, both host-side integers), and
resume later — on this worker, another worker, or never (the
recompute-from-step-0 fallback replays init + every step and is the
bit-identity reference).

Determinism contract (tests/ops/test_stepwise.py): running steps
``[0, k)`` then ``[k, n)`` — with ``x`` round-tripped through the host
checkpoint codec between them — is BIT-IDENTICAL to running ``[0, n)``
uninterrupted. That holds because each step is a pure function of
``(x, i, tile key)``: the per-step stochastic key is folded from the
tile key and the step index (never threaded through carry), sigma
pairs are looked up by ``i`` from a closed-over table, and the
float32 host round-trip is byte-exact.

Only samplers whose step carries no cross-step history qualify
(``STEPWISE_SAMPLERS``); multi-step-history samplers (lms, dpmpp_2m,
…) stay on the scan tier — ``stepwise_supported`` is the gate callers
consult before routing a job to the preemptible executor.
"""

from __future__ import annotations

import base64
from typing import Any, Callable, NamedTuple

import numpy as np

# Samplers whose per-step update is a pure function of (x, step index,
# tile key): eligible for checkpoint/resume at any step boundary.
# Second-order and history-carrying samplers (heun, dpm_2, lms,
# dpmpp_*) are deliberately absent — their carry is not just x.
STEPWISE_SAMPLERS = ("euler", "ddim", "euler_ancestral")


class StepwiseUnsupported(ValueError):
    """The job's sampler/model combination cannot run on the
    step-resumable tier. Raised by the factory BEFORE any job state is
    touched, and the ONLY exception the CDT_XJOB_BATCH delegation
    seams catch — a ValueError from deep inside a running xjob job
    must propagate, never silently re-run the whole job on the scan
    tier."""


def stepwise_supported(sampler: str, flow: bool = False) -> bool:
    """True when `sampler` can run on the step-resumable tier.
    ``euler_ancestral`` renoises with the VE rule, which is invalid for
    rectified-flow models (ops/samplers.sample rejects it there too)."""
    if sampler not in STEPWISE_SAMPLERS:
        return False
    if flow and sampler == "euler_ancestral":
        return False
    return True


class StepwiseProcessor(NamedTuple):
    """One job's step-resumable tile programs + batching signature.

    ``signature`` is the cross-job mixing key: two jobs whose
    processors carry EQUAL signatures run the same compiled ``step``
    program on the same shapes, so the executor may place their tiles
    in one device batch. Jobs with different geometry, sampler config,
    or model bundles never mix (their programs differ)."""

    init: Callable[[Any, Any, Any], Any]
    step: Callable[[Any, Any, Any, Any, Any, Any, Any], Any]
    finish: Callable[[Any, Any], Any]
    n_steps: int
    signature: tuple


def euler_step(model_fn, x, sigma, sigma_next, cond):
    """One Euler step (identical math to ops/samplers._sample_euler's
    scan body, lifted out so it can run solo)."""
    import jax.numpy as jnp

    from . import samplers as smp

    den = smp._denoised(model_fn, x, sigma, cond)
    d = (x - den) / jnp.maximum(sigma, 1e-10)
    return x + d * (sigma_next - sigma)


def euler_ancestral_step(model_fn, x, sigma, sigma_next, cond, step_key):
    """One Euler-ancestral step; ``step_key`` is already folded from
    (tile key, step index) by the caller, so the step is stateless."""
    import jax
    import jax.numpy as jnp

    from . import samplers as smp

    den = smp._denoised(model_fn, x, sigma, cond)
    sigma_down, sigma_up = smp._ancestral_split(sigma, sigma_next)
    d = (x - den) / jnp.maximum(sigma, 1e-10)
    x = x + d * (sigma_down - sigma)
    return x + jax.random.normal(step_key, x.shape) * sigma_up


# Precision lanes for the latent carry. ``bf16`` quantizes the latent
# BETWEEN steps (storage / checkpoint / transfer precision — halves
# checkpoint and d2h bytes); the per-step model math still runs in the
# model's parameter dtype via promotion, so the lane is a bounded
# quality trade (its distance from the f32 trajectory on the chip: not
# measured), not an unbounded one.
PRECISION_LANES = ("f32", "bf16")


def make_stepwise_tile_processor(
    bundle,
    grid,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    tiled_decode: bool = False,
    precision: str = "f32",
) -> StepwiseProcessor:
    """Build the production step-resumable tile processor: the same
    VAE-encode → noise → per-step denoise → VAE-decode pipeline as
    ``_jit_tile_processor``, factored at step boundaries. All three
    programs are jitted; the step program takes the step index as a
    TRACED scalar (sigma pair via ``jnp.take``) so every step of the
    trajectory shares ONE compiled program per batch shape.

    The jitted step DONATES its latent operand (``donate_argnums=(1,)``,
    the seam parallel/training.py uses for train state): XLA aliases
    the input latent buffer into the output, so the per-step loop holds
    ONE latent allocation instead of two. Callers must treat the passed
    ``x`` as consumed (the executor rebinds ``item.x`` from the output;
    checkpoints encode BEFORE the next step call).

    ``precision`` selects the latent-carry lane (``PRECISION_LANES``);
    it joins the batching signature so f32 and bf16 tiles never share a
    device batch."""
    import jax
    import jax.numpy as jnp

    from ..models import pipeline as pl
    from . import samplers as smp
    from . import upscale as upscale_ops

    param, shift = pl.model_schedule_info(bundle)
    flow = param == "flow"
    if not stepwise_supported(sampler, flow=flow):
        raise StepwiseUnsupported(
            f"sampler {sampler!r} (flow={flow}) has cross-step state and "
            "cannot run on the step-resumable tier; use the scan tier"
        )
    if precision not in PRECISION_LANES:
        raise StepwiseUnsupported(
            f"unknown precision lane {precision!r} (choose from "
            f"{PRECISION_LANES})"
        )
    bf16 = precision == "bf16"
    sigmas = smp.get_model_sigmas(
        param, scheduler, int(steps), denoise=float(denoise), flow_shift=shift
    )
    sigmas = jnp.asarray(sigmas)
    n_steps = int(sigmas.shape[0]) - 1

    @jax.jit
    def init(params, tile, key):
        z = bundle.vae.apply(params["vae"], tile, method="encode")
        noise_key, _ = jax.random.split(key)
        x = smp.noise_latents(
            param, z, jax.random.normal(noise_key, z.shape), sigmas[0]
        )
        return x.astype(jnp.bfloat16) if bf16 else x

    def _step(params, x, key, pos, neg, yx, i):
        if bf16:
            x = x.astype(jnp.float32)
        pos_t = upscale_ops.tile_cond(pos, yx[0], yx[1], grid)
        neg_t = upscale_ops.tile_cond(neg, yx[0], yx[1], grid)
        model_fn = pl.guided_model(bundle, params, float(cfg))
        cond = (pos_t, neg_t)
        sigma = jnp.take(sigmas, i)
        sigma_next = jnp.take(sigmas, i + 1)
        if sampler == "euler_ancestral":
            _, anc_key = jax.random.split(key)
            step_key = jax.random.fold_in(anc_key, i)
            out = euler_ancestral_step(
                model_fn, x, sigma, sigma_next, cond, step_key
            )
        else:
            # euler and (eta=0) ddim share the same sigma-space update
            # (see ops/samplers._sample_ddim's derivation note)
            out = euler_step(model_fn, x, sigma, sigma_next, cond)
        return out.astype(jnp.bfloat16) if bf16 else out

    step = jax.jit(_step, donate_argnums=(1,))

    @jax.jit
    def finish(params, x):
        if bf16:
            x = x.astype(jnp.float32)
        if tiled_decode:
            from .tiled_vae import decode_tiled

            return decode_tiled(pl._Static(bundle), params["vae"], x)
        return bundle.vae.apply(params["vae"], x, method="decode")

    signature = (
        "tile-stepwise",
        id(bundle),
        int(grid.padded_h),
        int(grid.padded_w),
        int(steps),
        str(sampler),
        str(scheduler),
        round(float(cfg), 6),
        round(float(denoise), 6),
        bool(tiled_decode),
        str(precision),
    )
    return StepwiseProcessor(init, step, finish, n_steps, signature)


# --------------------------------------------------------------------------
# checkpoint codec
# --------------------------------------------------------------------------
#
# Checkpoints travel master<->worker inside JSON RPC payloads
# (return_tiles attaches them on eviction; request_image hands them
# back on re-grant), so the latent state is serialized as raw bytes +
# dtype/shape — a float32 device->host->device round trip is byte-exact,
# which is what makes resume ≡ uninterrupted bit-identical. They are
# deliberately VOLATILE on the master (never journaled): recovery and
# worker crashes fall back to recompute-from-step-0, which is the
# bit-identity reference by construction.

CHECKPOINT_VERSION = 1

# One decoded checkpoint's latent may not exceed this many bytes: the
# payload arrives from the network inside a worker RPC and is buffered
# on the master until re-grant, so it must be bounded.
MAX_CHECKPOINT_BYTES = 64 * 1024 * 1024


class CheckpointError(ValueError):
    """Malformed / oversized / version-mismatched checkpoint payload —
    callers drop the checkpoint and recompute from step 0."""


def encode_checkpoint(x, step: int) -> dict[str, Any]:
    """Serialize a mid-trajectory latent + step index into a JSON-able
    dict. ``x`` may be a device array or ndarray; bytes are preserved
    exactly (C-order ``tobytes``)."""
    import time

    from ..telemetry.profiling import D2H, ledger_if_enabled

    started = time.monotonic()
    # the checkpoint spill IS the sanctioned d2h boundary (written only
    # at preemption/checkpoint time, never per step) and the ledger
    # note below brackets it
    arr = np.ascontiguousarray(np.asarray(x))  # cdt: noqa[CDT007]
    ledger = ledger_if_enabled()
    if ledger is not None:
        # np.asarray on a device array is the d2h materialization; the
        # ship cost (b64 + RPC) is charged by the submit stage span
        ledger.note_transfer(
            D2H, int(arr.nbytes), time.monotonic() - started
        )
    if arr.nbytes > MAX_CHECKPOINT_BYTES:
        raise CheckpointError(
            f"checkpoint latent is {arr.nbytes} bytes "
            f"(cap {MAX_CHECKPOINT_BYTES})"
        )
    return {
        "v": CHECKPOINT_VERSION,
        "step": int(step),
        "dtype": str(arr.dtype),
        "shape": [int(d) for d in arr.shape],
        "data": base64.b64encode(arr.tobytes()).decode("ascii"),
    }


def validate_checkpoint_meta(payload: Any) -> int:
    """Structural validation WITHOUT decoding the payload bytes —
    cheap enough to run under the store lock on the serving loop
    (full b64 + ndarray decode of a near-cap checkpoint would block
    every other coroutine for its duration). Checks version, step,
    a NUMERIC dtype, shape/byte-count consistency (b64 length is a
    pure function of the raw length), and the size cap. Returns the
    decoded byte count; raises CheckpointError otherwise. The
    consuming executor still fully decodes (``decode_checkpoint``)
    and drops on any error."""
    if not isinstance(payload, dict):
        raise CheckpointError("checkpoint payload must be a dict")
    if payload.get("v") != CHECKPOINT_VERSION:
        raise CheckpointError(f"unknown checkpoint version {payload.get('v')!r}")
    try:
        step = int(payload["step"])
        dtype = np.dtype(str(payload["dtype"]))
        shape = tuple(int(d) for d in payload["shape"])
        data = payload["data"]
    except (KeyError, TypeError, ValueError) as exc:
        raise CheckpointError(f"malformed checkpoint: {exc}") from exc
    if step < 0:
        raise CheckpointError(f"negative checkpoint step {step}")
    if dtype.kind not in "fiub" and dtype.name != "bfloat16":
        # object/str/void dtypes could smuggle arbitrary Python state
        # (and crash frombuffer); latents are numeric by construction.
        # bfloat16 (ml_dtypes) registers with kind 'V' but is a plain
        # 2-byte numeric dtype — the bf16 lane's checkpoints round-trip
        # byte-exactly through it, so it is explicitly allowlisted.
        raise CheckpointError(f"non-numeric checkpoint dtype {dtype!r}")
    if not isinstance(data, str):
        raise CheckpointError("checkpoint data must be a base64 string")
    expected = (
        int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if shape
        else dtype.itemsize
    )
    if expected < 0 or expected > MAX_CHECKPOINT_BYTES:
        raise CheckpointError(
            f"checkpoint size {expected} outside (0, {MAX_CHECKPOINT_BYTES}]"
        )
    # un-padded b64 length check: 4 chars per 3 raw bytes, padded
    if len(data) != 4 * ((expected + 2) // 3):
        raise CheckpointError(
            f"checkpoint data length {len(data)} != b64({expected} bytes)"
        )
    return expected


def decode_checkpoint(payload: Any) -> tuple[np.ndarray, int]:
    """Inverse of ``encode_checkpoint``; raises CheckpointError on any
    malformed field so callers fall back to recompute, never crash."""
    validate_checkpoint_meta(payload)
    try:
        step = int(payload["step"])
        dtype = np.dtype(str(payload["dtype"]))
        shape = tuple(int(d) for d in payload["shape"])
        raw = base64.b64decode(str(payload["data"]), validate=True)
        expected = (
            int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            if shape
            else dtype.itemsize
        )
        if len(raw) != expected:
            raise CheckpointError(
                f"checkpoint byte count {len(raw)} != expectation {expected}"
            )
        arr = np.frombuffer(raw, dtype=dtype).reshape(shape).copy()
    except CheckpointError:
        raise
    except Exception as exc:  # noqa: BLE001 - any decode failure = drop
        raise CheckpointError(f"malformed checkpoint: {exc}") from exc
    return arr, step


def checkpoint_nbytes(payload: Any) -> int:
    """Approximate retained size of an ENCODED checkpoint payload (for
    the master's per-job retention budget); 0 for malformed input."""
    try:
        data = payload.get("data", "")
    except AttributeError:
        return 0
    return int(len(data) * 3 / 4) if isinstance(data, str) else 0
