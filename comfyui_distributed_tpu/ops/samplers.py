"""Diffusion samplers and noise schedules — lax.scan step loops.

The TPU-native replacement for the k-diffusion samplers the reference
reaches through ComfyUI's `common_ksampler` (reference
upscale/tile_ops.py:239-287 passes sampler_name/scheduler/cfg/denoise
straight through). Same user-facing knobs (sampler name, scheduler,
steps, cfg, denoise), implemented as scanned, jit-compilable loops:
the whole sampling trajectory compiles to one XLA program — no host
round-trip per step.

Model contract: `model_fn(x, sigma_batch, cond) -> eps` (noise
prediction, VP parameterisation with c_in = 1/sqrt(sigma^2+1), the
SD-family convention). `denoised(x, sigma) = x - sigma * eps`.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

ModelFn = Callable[[jax.Array, jax.Array, Any], jax.Array]

SAMPLER_NAMES = (
    "euler", "euler_ancestral", "heun", "dpm_2", "dpm_2_ancestral", "lms",
    "dpmpp_2s_ancestral", "dpmpp_sde", "dpmpp_2m", "dpmpp_2m_sde", "ddim",
    "lcm",
)
SCHEDULER_NAMES = (
    "karras", "normal", "simple", "exponential", "sgm_uniform",
    "ddim_uniform", "beta", "kl_optimal",
)


# --- schedules -----------------------------------------------------------

def _vp_sigmas(n_training: int = 1000):
    """SD-style scaled-linear beta schedule → per-timestep sigmas.

    Computed in numpy so schedules are concrete at trace time — they
    are compile-time constants of the sampling program, never traced.
    """
    import numpy as np

    betas = np.linspace(0.00085**0.5, 0.012**0.5, n_training) ** 2
    alphas_cumprod = np.cumprod(1.0 - betas)
    return np.sqrt((1 - alphas_cumprod) / alphas_cumprod)


def get_sigmas(scheduler: str, steps: int, denoise: float = 1.0) -> jnp.ndarray:
    """[steps+1] descending sigma schedule ending at 0.

    `denoise < 1` truncates to the tail of the schedule (img2img /
    tile re-diffusion strength, parity with the reference's `denoise`
    input on USDU).
    """
    import numpy as np

    total_steps = steps
    if denoise < 1.0:
        total_steps = max(int(steps / max(denoise, 1e-4)), steps)
    sigmas = _spaced_from_table(_vp_sigmas(), scheduler, total_steps)
    sigmas = sigmas[-steps:] if denoise < 1.0 else sigmas
    return jnp.asarray(np.concatenate([sigmas, np.zeros((1,))]), dtype=jnp.float32)


def karras_sigmas(
    sigma_min: float, sigma_max: float, steps: int, rho: float = 7.0
):
    """Descending Karras rho-ramp grid (no terminal zero) — shared by
    the 'karras' scheduler branch and the KarrasScheduler node."""
    import numpy as np

    ramp = np.linspace(0, 1, steps)
    min_r, max_r = sigma_min ** (1 / rho), sigma_max ** (1 / rho)
    return (max_r + ramp * (min_r - max_r)) ** rho


def exponential_sigmas(sigma_min: float, sigma_max: float, steps: int):
    """Descending log-uniform grid (no terminal zero) — shared by the
    'exponential' scheduler branch and the ExponentialScheduler node."""
    import numpy as np

    return np.exp(np.linspace(np.log(sigma_max), np.log(sigma_min), steps))


def polyexponential_sigmas(
    sigma_min: float, sigma_max: float, steps: int, rho: float = 1.0
):
    """Descending poly-exponential grid (the PolyexponentialScheduler
    node): a log-space ramp warped by rho. rho=1 reduces exactly to
    exponential_sigmas; rho>1 spends more steps near sigma_min."""
    import numpy as np

    ramp = np.linspace(1.0, 0.0, steps) ** rho
    return np.exp(
        ramp * (np.log(sigma_max) - np.log(sigma_min)) + np.log(sigma_min)
    )


def _spaced_from_table(all_sigmas, scheduler: str, total_steps: int):
    """Descending [total_steps] sigma spacing over an ascending sigma
    table — the scheduler dispatch shared by the VP and flow families
    (in the reference stack the model's sampling object owns the table
    and the scheduler knob shapes spacing through it for BOTH families).
    """
    import numpy as np

    sigma_max = float(all_sigmas[-1])
    sigma_min = float(all_sigmas[0])

    if scheduler == "karras":
        sigmas = karras_sigmas(sigma_min, sigma_max, total_steps)
    elif scheduler == "exponential":
        sigmas = exponential_sigmas(sigma_min, sigma_max, total_steps)
    elif scheduler in ("normal", "simple"):
        idx = np.linspace(len(all_sigmas) - 1, 0, total_steps)
        sigmas = all_sigmas[idx.astype(np.int64)]
    elif scheduler == "sgm_uniform":
        # uniform timestep spacing with the final (smallest) timestep
        # excluded before the terminal zero — the SGM convention
        idx = np.linspace(len(all_sigmas) - 1, 0, total_steps + 1)[:-1]
        sigmas = all_sigmas[idx.astype(np.int64)]
    elif scheduler == "ddim_uniform":
        # uniform timestep stride anchored at the TOP of the schedule
        # (the DDIM convention): always starts at sigma_max
        n = len(all_sigmas)
        ss = n / max(total_steps, 1)
        idx = np.asarray(
            [n - 1 - int(i * ss) for i in range(total_steps)], dtype=np.int64
        )
        sigmas = all_sigmas[np.clip(idx, 0, n - 1)]
    elif scheduler == "beta":
        sigmas = beta_spaced_sigmas(all_sigmas, total_steps)
    elif scheduler == "kl_optimal":
        # arctan-interpolated sigma spacing ("Align Your Steps"
        # KL-optimal closed form)
        r = np.linspace(0.0, 1.0, total_steps)
        sigmas = np.tan(
            r * np.arctan(sigma_min) + (1.0 - r) * np.arctan(sigma_max)
        )
    else:
        raise ValueError(f"unknown scheduler {scheduler!r}; use {SCHEDULER_NAMES}")

    return sigmas


def beta_spaced_sigmas(
    all_sigmas, total_steps: int, alpha: float = 0.6, beta: float = 0.6
):
    """Timesteps at Beta(alpha, beta) quantiles over an ascending
    sigma table — dense at both schedule ends, sparse in the middle
    at the 0.6/0.6 default. Shared by the 'beta' scheduler branch and
    the BetaSamplingScheduler node (which exposes alpha/beta)."""
    import numpy as np

    n = len(all_sigmas)
    ts = 1.0 - np.linspace(0.0, 1.0, total_steps, endpoint=False)
    idx = np.rint(
        _beta_ppf(ts, float(alpha), float(beta)) * (n - 1)
    ).astype(np.int64)
    # strictly decreasing indices: quantile rounding can collide
    # (the reference dedupes; the fixed steps+1 scan length here
    # needs distinct sigmas instead — equal neighbors would break
    # multistep solvers). Downward nudges can cascade below 0 when
    # many low quantiles round to 0, so a bottom-up pass bumps
    # those back, preserving strictness whenever total_steps <= n.
    for i in range(1, len(idx)):
        if idx[i] >= idx[i - 1]:
            idx[i] = idx[i - 1] - 1
    floor = 0
    for i in range(len(idx) - 1, -1, -1):
        if idx[i] < floor:
            idx[i] = floor
        floor = idx[i] + 1
    return all_sigmas[np.clip(idx, 0, n - 1)]


def _betainc_np(a: float, b: float, x):
    """Regularized incomplete beta I_x(a, b) in pure numpy float64
    (Lentz continued fraction, Numerical Recipes 6.4). Schedules must
    stay concrete at trace time (module contract) and jax's betainc
    cannot be forced eager inside an outer jit on every jax version
    (its ufunc/while_loop internals leak tracers out of
    ensure_compile_time_eval on 0.4.37), so the sampler stack computes
    the CDF host-side with no jax involvement at all."""
    import math

    import numpy as np

    def betacf(aa: float, bb: float, xx: float) -> float:
        tiny, eps = 1e-30, 3e-16
        qab, qap, qam = aa + bb, aa + 1.0, aa - 1.0
        c = 1.0
        d = 1.0 - qab * xx / qap
        if abs(d) < tiny:
            d = tiny
        d = 1.0 / d
        h = d
        for m in range(1, 200):
            m2 = 2 * m
            num = m * (bb - m) * xx / ((qam + m2) * (aa + m2))
            d = 1.0 + num * d
            if abs(d) < tiny:
                d = tiny
            c = 1.0 + num / c
            if abs(c) < tiny:
                c = tiny
            d = 1.0 / d
            h *= d * c
            num = -(aa + m) * (qab + m) * xx / ((aa + m2) * (qap + m2))
            d = 1.0 + num * d
            if abs(d) < tiny:
                d = tiny
            c = 1.0 + num / c
            if abs(c) < tiny:
                c = tiny
            d = 1.0 / d
            delta = d * c
            h *= delta
            if abs(delta - 1.0) < eps:
                break
        return h

    ln_beta = math.lgamma(a) + math.lgamma(b) - math.lgamma(a + b)

    def one(xx: float) -> float:
        if xx <= 0.0:
            return 0.0
        if xx >= 1.0:
            return 1.0
        front = math.exp(
            a * math.log(xx) + b * math.log1p(-xx) - ln_beta
        )
        if xx < (a + 1.0) / (a + b + 2.0):
            return front * betacf(a, b, xx) / a
        return 1.0 - front * betacf(b, a, 1.0 - xx) / b

    return np.vectorize(one, otypes=[np.float64])(np.asarray(x, np.float64))


def _beta_ppf(q, a: float, b: float, iters: int = 60):
    """Beta(a, b) quantile function via bisection on the regularized
    incomplete beta CDF — dependency-free (the reference stack reaches
    scipy.stats.beta.ppf for this; scipy is an optional install here,
    so the sampler stack must not need it). float64 CDF + 60 halvings
    ≈ 1e-7 quantile precision, far inside the rint-to-1000-buckets
    tolerance downstream."""
    import numpy as np

    q = np.asarray(q, np.float64)
    lo = np.zeros_like(q)
    hi = np.ones_like(q)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        cdf = _betainc_np(a, b, mid)
        lo = np.where(cdf < q, mid, lo)
        hi = np.where(cdf < q, hi, mid)
    return 0.5 * (lo + hi)


def _flow_sigma_table(shift: float, n_training: int = 1000):
    """Ascending flow sigma table sigma(t) = s*t / (1 + (s-1)*t) for
    t in {1/n, ..., 1} — the flow analog of _vp_sigmas (the reference
    stack's flow model_sampling exposes the same discretized table)."""
    import numpy as np

    t = np.arange(1, n_training + 1, dtype=np.float64) / n_training
    return shift * t / (1.0 + (shift - 1.0) * t)


def get_flow_sigmas(
    steps: int,
    denoise: float = 1.0,
    shift: float = 3.0,
    scheduler: str = "simple",
) -> jnp.ndarray:
    """[steps+1] descending rectified-flow sigmas with timestep shift
    (t' = s*t / (1 + (s-1)*t)). sigma IS the flow time: x_t =
    (1-sigma)*x0 + sigma*noise, and the model's velocity prediction is
    exactly eps under the sampler contract denoised = x - sigma*eps.
    `denoise < 1` truncates to the schedule tail like get_sigmas.

    The scheduler knob shapes spacing here too (ADVICE r4): 'simple' /
    'normal' keep the exact uniform-t-through-the-shift-map grid (the
    Flux default); every other scheduler applies its spacing over the
    shifted flow sigma table, mirroring how the reference computes
    beta/sgm_uniform/karras through the model's sampling object."""
    import numpy as np

    total = steps
    if denoise < 1.0:
        total = max(int(steps / max(denoise, 1e-4)), steps)
    if scheduler in ("normal", "simple"):
        t = np.linspace(1.0, 0.0, total + 1)
        t = shift * t / (1.0 + (shift - 1.0) * t)
        return jnp.asarray(t[-(steps + 1):], dtype=jnp.float32)
    sigmas = _spaced_from_table(_flow_sigma_table(shift), scheduler, total)
    sigmas = sigmas[-steps:] if denoise < 1.0 else sigmas
    return jnp.asarray(np.concatenate([sigmas, np.zeros((1,))]), dtype=jnp.float32)


def get_model_sigmas(
    parameterization: str,
    scheduler: str,
    steps: int,
    denoise: float = 1.0,
    flow_shift: float = 3.0,
) -> jnp.ndarray:
    """Family-aware sigma schedule: flow-matching models (Flux class)
    use the shifted rectified-flow grid as their sigma table; the
    scheduler knob shapes spacing for BOTH families (parity with the
    reference stack, where spacing is computed through the model's
    sampling object — a Flux user selecting scheduler='beta' gets beta
    spacing over flow sigmas, not a silently ignored knob)."""
    if parameterization == "flow":
        return get_flow_sigmas(
            steps, denoise=denoise, shift=flow_shift, scheduler=scheduler
        )
    return get_sigmas(scheduler, steps, denoise=denoise)


def noise_latents(
    parameterization: str,
    latents: jax.Array,
    noise: jax.Array,
    sigma0: jax.Array,
) -> jax.Array:
    """img2img/tile noising to the schedule start: VP families add
    scaled noise (x = z + sigma*n); rectified flow interpolates
    (x = (1-sigma)*z + sigma*n)."""
    if parameterization == "flow":
        return (1.0 - sigma0) * latents + sigma0 * noise
    return latents + noise * sigma0


def masked_inpaint_model(
    model_fn: "ModelFn",
    parameterization: str,
    latents: jax.Array,
    noise: jax.Array,
    mask: jax.Array,
) -> "ModelFn":
    """Inpainting wrapper shared by the single-device and mesh KSampler
    paths: before every model eval the UNMASKED region (mask 0) is
    pinned to the original `latents` re-noised to the current sigma
    with the SAME noise the trajectory started from, so only the
    masked region (mask 1 = regenerate) evolves. Callers composite
    `out * mask + latents * (1 - mask)` after sampling to restore the
    unmasked region exactly. NOTE the polarity is the ComfyUI
    noise_mask convention (1 = regenerate) — the video outpainting
    helper sample_flow_masked uses the opposite (1 = known)."""

    def wrapped(x, sigma_batch, cond):
        sig = sigma_batch.reshape((-1,) + (1,) * (x.ndim - 1))
        ref = noise_latents(parameterization, latents, noise, sig)
        return model_fn(x * mask + ref * (1.0 - mask), sigma_batch, cond)

    return wrapped


def sigma_to_timestep(sigma: jax.Array) -> jax.Array:
    """Nearest training timestep for a sigma (for timestep-conditioned
    models); differentiable-free lookup."""
    import numpy as np

    log_all = jnp.asarray(np.log(_vp_sigmas()), dtype=jnp.float32)
    return jnp.argmin(
        jnp.abs(jnp.log(jnp.maximum(sigma, 1e-10))[..., None] - log_all),
        axis=-1,
    ).astype(jnp.float32)


def percent_to_sigma(
    percent: float, parameterization: str = "eps", shift: float = 3.0
) -> float:
    """Sampling-progress percent (0 = schedule start / sigma_max,
    1 = end) → sigma, per model family — the reference stack's
    model_sampling.percent_to_sigma, used to gate sigma-ranged model
    patches (skip-layer guidance)."""
    p = float(percent)
    if p <= 0.0:
        return float("inf")
    if p >= 1.0:
        return 0.0
    if parameterization == "flow":
        t = 1.0 - p
        return float(shift * t / (1.0 + (shift - 1.0) * t))
    table = _vp_sigmas()
    return float(table[round((1.0 - p) * (len(table) - 1))])


# --- multi-cond composition ----------------------------------------------

def _as_entries(cond) -> list:
    """A CONDITIONING value as a list of entries (ConditioningCombine
    produces lists; everything else is a single entry)."""
    if isinstance(cond, (list, tuple)):
        return list(cond)
    return [cond]


def _needs_composite(cond) -> bool:
    """True when a CONDITIONING value needs the per-entry composition
    path: multiple entries, or spatial/schedule restrictions on one."""
    entries = _as_entries(cond)
    if len(entries) > 1:
        return True
    e = entries[0]
    return (
        getattr(e, "area", None) is not None
        or getattr(e, "mask", None) is not None
        or getattr(e, "timestep_range", None) is not None
    )


def _default_p2s(percent: float) -> float:
    return percent_to_sigma(percent, "eps", 3.0)


def composite_eps(model_fn: ModelFn, x, sigma, cond, p2s=_default_p2s):
    """Multi-entry conditioning composition (the reference stack's
    calc_cond_batch semantics): each entry's prediction applies over
    its area (latent units = pixels//8, evaluated on the CROP — a
    static shape per entry), weighted by strength x mask x
    timestep-window gate, accumulated and normalized by total weight.
    Uncovered cells contribute zero eps (denoised = x there), matching
    the reference's division-by-count behavior. The timestep gate is
    arithmetic on sigma[0] (one scalar per step), so the trajectory
    stays one XLA program."""
    entries = _as_entries(cond)
    acc = jnp.zeros_like(x)
    count = jnp.zeros(x.shape[:-1] + (1,), x.dtype)
    for e in entries:
        weight = float(getattr(e, "strength", 1.0))
        gate = None
        rng = getattr(e, "timestep_range", None)
        if rng is not None:
            sig_hi = p2s(float(rng[0]))
            sig_lo = p2s(float(rng[1]))
            s0 = sigma[0]
            gate = ((s0 <= sig_hi) & (s0 > sig_lo)).astype(x.dtype)
        mask = getattr(e, "mask", None)
        if mask is not None:
            m = jnp.asarray(mask, x.dtype)
            if m.ndim == 4:
                m = m[..., 0]
            if m.ndim == 2:
                m = m[None]
            if m.shape[1:] != x.shape[1:3]:
                m = jax.image.resize(
                    m, (m.shape[0], x.shape[1], x.shape[2]), method="linear"
                )
            wmap = jnp.clip(m, 0.0, 1.0)[..., None] * weight
        else:
            wmap = jnp.full(x.shape[:-1] + (1,), weight, x.dtype)
        if gate is not None:
            wmap = wmap * gate
        area = getattr(e, "area", None)
        if area is not None:
            from .conditioning import resolve_area

            if area[0] == "percentage":
                # frame fractions resolve against the latent at trace
                # time (x.shape is concrete here) — the reference
                # stack's ConditioningSetAreaPercentage semantics
                ah, aw, ay, ax = resolve_area(area, x.shape[1], x.shape[2])
            else:
                ah, aw, ay, ax = (int(v) // 8 for v in area)
            # clamp origin INTO the latent too: an off-frame origin
            # would slice a zero-size crop and crash the model trace
            ay = min(max(ay, 0), x.shape[1] - 1)
            ax = min(max(ax, 0), x.shape[2] - 1)
            ah = max(1, min(ah, x.shape[1] - ay))
            aw = max(1, min(aw, x.shape[2] - ax))
            x_c = x[:, ay:ay + ah, ax:ax + aw, :]
            e_c = e
            if getattr(e, "concat_latent", None) is not None and (
                e.concat_latent.shape[1:3] == x.shape[1:3]
            ):
                # spatial payloads follow the crop — the model would
                # otherwise squash the full-image plane into the window
                e_c = e.clone()
                e_c.concat_latent = e.concat_latent[
                    :, ay:ay + ah, ax:ax + aw, :
                ]
            if getattr(e, "control_hint", None) is not None:
                # hints are pixel-space: crop the matching pixel window
                e_c = e_c.clone() if e_c is e else e_c
                k = max(1, e.control_hint.shape[1] // x.shape[1])
                e_c.control_hint = e.control_hint[
                    :, ay * k:(ay + ah) * k, ax * k:(ax + aw) * k, :
                ]
            eps_c = model_fn(x_c, sigma, e_c)
            w_c = jnp.broadcast_to(
                wmap, x.shape[:-1] + (1,)
            )[:, ay:ay + ah, ax:ax + aw, :]
            acc = acc.at[:, ay:ay + ah, ax:ax + aw, :].add(eps_c * w_c)
            count = count.at[:, ay:ay + ah, ax:ax + aw, :].add(w_c)
        else:
            eps = model_fn(x, sigma, e)
            acc = acc + eps * wmap
            count = count + jnp.broadcast_to(wmap, count.shape)
    return acc / jnp.maximum(count, 1e-9)


# --- CFG wrapper ---------------------------------------------------------

def _reject_unsupported_cond(*conds) -> None:
    """Trace-time guard: conditioning features no registered backbone
    consumes must fail loudly, not drop silently (a rendered image
    missing its image-condition looks 'plausible but wrong')."""
    for cond in conds:
        entries = cond if isinstance(cond, (list, tuple)) else [cond]
        for e in entries:
            if getattr(e, "unclip_embeds", None) is not None:
                raise ValueError(
                    "unCLIP image conditioning (unCLIPConditioning node) "
                    "reached a model without an unCLIP adm head — no "
                    "registered backbone consumes it yet; remove the "
                    "node or use an i2v-native path (WAN i2v)"
                )


def _cfg_eval(model_fn: ModelFn, cfg_scale: float, x, sigma, cond,
              p2s=_default_p2s):
    """One CFG evaluation: returns (eps_pos, guided_eps). Batches the
    cond/uncond passes into one model call (2B batch) — on TPU one big
    MXU matmul beats two small ones. Shared by cfg_model and
    slg_cfg_model (which also needs the bare eps_pos). Multi-entry or
    area/mask/timestep-restricted conditioning takes the per-entry
    composition path instead of the 2B batch."""
    pos, neg = cond
    _reject_unsupported_cond(pos, neg)
    if _needs_composite(pos) or _needs_composite(neg):
        eps_pos = composite_eps(model_fn, x, sigma, pos, p2s)
        if cfg_scale == 1.0:
            return eps_pos, eps_pos
        eps_neg = composite_eps(model_fn, x, sigma, neg, p2s)
        return eps_pos, eps_neg + cfg_scale * (eps_pos - eps_neg)
    if cfg_scale == 1.0:
        eps_pos = model_fn(x, sigma, pos)
        return eps_pos, eps_pos
    if _conds_batchable(pos, neg):
        x2 = jnp.concatenate([x, x], axis=0)
        s2 = jnp.concatenate([sigma, sigma], axis=0)
        c2 = jax.tree_util.tree_map(
            lambda p, n: jnp.concatenate([p, n], axis=0), pos, neg
        )
        eps2 = model_fn(x2, s2, c2)
        eps_pos, eps_neg = jnp.split(eps2, 2, axis=0)
    else:
        # structurally different conditioning (e.g. ControlNet hint
        # only on the positive side): two passes
        eps_pos = model_fn(x, sigma, pos)
        eps_neg = model_fn(x, sigma, neg)
    return eps_pos, eps_neg + cfg_scale * (eps_pos - eps_neg)


def cfg_model(model_fn: ModelFn, cfg_scale: float,
              p2s=_default_p2s) -> ModelFn:
    """Classifier-free guidance: cond is (positive, negative) pair.
    `p2s` converts sampling-progress percent → sigma for the
    timestep-window gates of multi-entry conditioning (pass the
    bundle-aware converter; the default assumes the VP table)."""

    def guided(x, sigma, cond):
        _eps_pos, out = _cfg_eval(model_fn, cfg_scale, x, sigma, cond, p2s)
        return out

    return guided


def dual_cfg_model(
    model_fn: ModelFn,
    cfg_conds: float,
    cfg_cond2_negative: float,
    p2s=_default_p2s,
    nested: bool = False,
) -> ModelFn:
    """Dual-conditioning CFG (the DualCFGGuider node): cond is
    ((cond1, cond2), negative). Formulas spelled out because no
    reference source is vendored here to diff against:

    regular (default):
        mid = neg + cfg_cond2_negative * (eps2 - neg)
        out = mid + cfg_conds * (eps1 - eps2)
    nested:
        inner = eps2 + cfg_conds * (eps1 - eps2)
        out   = neg + cfg_cond2_negative * (inner - neg)

    Useful invariants (pinned by tests): regular with cond2 == negative
    reduces to plain CFG over (cond1, negative) at cfg_conds; nested
    with cfg_conds == 1 reduces to plain CFG over (cond1, negative) at
    cfg_cond2_negative (and short-circuits to that 2B program).
    Otherwise the three conds run as ONE 3B-batched model call when
    structurally compatible — one big MXU matmul beats three small
    ones (same rationale as _cfg_eval's 2B batch)."""

    def guided(x, sigma, cond):
        (pos1, pos2), neg = cond
        _reject_unsupported_cond(pos1, pos2, neg)
        if nested and cfg_conds == 1.0:
            # inner == eps1: plain CFG, skip the cond2 eval entirely
            _e, out = _cfg_eval(
                model_fn, cfg_cond2_negative, x, sigma, (pos1, neg), p2s
            )
            return out
        comp = any(_needs_composite(c) for c in (pos1, pos2, neg))
        if (
            not comp
            and _conds_batchable(pos1, pos2)
            and _conds_batchable(pos2, neg)
            and _conds_batchable(pos1, neg)
        ):
            x3 = jnp.concatenate([x, x, x], axis=0)
            s3 = jnp.concatenate([sigma, sigma, sigma], axis=0)
            c3 = jax.tree_util.tree_map(
                lambda a, b, c: jnp.concatenate([a, b, c], axis=0),
                pos1, pos2, neg,
            )
            e1, e2, en = jnp.split(model_fn(x3, s3, c3), 3, axis=0)
        else:
            def _eps(c):
                if _needs_composite(c):
                    return composite_eps(model_fn, x, sigma, c, p2s)
                return model_fn(x, sigma, c)

            e1, e2, en = _eps(pos1), _eps(pos2), _eps(neg)
        if nested:
            inner = e2 + cfg_conds * (e1 - e2)
            return en + cfg_cond2_negative * (inner - en)
        mid = en + cfg_cond2_negative * (e2 - en)
        return mid + cfg_conds * (e1 - e2)

    return guided


def rescale_cfg_model(
    model_fn: ModelFn,
    cfg_scale: float,
    multiplier: float,
    p2s=_default_p2s,
) -> ModelFn:
    """CFG with std rescaling (the reference stack's RescaleCFG patch,
    Lin et al. "Common Diffusion Noise Schedules..." §3.4). The
    rescale is computed on the V-PREDICTION transform of the two
    denoised outputs — exactly the reference composition, where the
    per-sample stds are taken in v space (std(v) differs from
    std(x0) by the spatially varying x-term, so an x0-space rescale
    would diverge from reference output) — then converted back to the
    sampler's eps contract (denoised = x - sigma*eps)."""

    def guided(x, sigma, cond):
        eps_pos, eps_cfg = _cfg_eval(model_fn, cfg_scale, x, sigma, cond, p2s)
        sig = sigma.reshape((-1,) + (1,) * (x.ndim - 1))
        x0_pos = x - sig * eps_pos
        x0_cfg = x - sig * eps_cfg
        # reference transform: xs = x/(s^2+1); v = (xs - (x - x0)) *
        # sqrt(s^2+1)/s. Affine in x0 with a shared offset, so applying
        # CFG before or after the transform is equivalent.
        xs = x / (sig * sig + 1.0)
        scale = jnp.sqrt(sig * sig + 1.0) / jnp.maximum(sig, 1e-10)
        v_pos = (xs - (x - x0_pos)) * scale
        v_cfg = (xs - (x - x0_cfg)) * scale
        axes = tuple(range(1, x.ndim))
        ro_pos = jnp.std(v_pos, axis=axes, keepdims=True)
        ro_cfg = jnp.maximum(jnp.std(v_cfg, axis=axes, keepdims=True), 1e-8)
        v_rescaled = v_cfg * (ro_pos / ro_cfg)
        v_final = multiplier * v_rescaled + (1.0 - multiplier) * v_cfg
        # inverse transform back to denoised, then to eps
        x0 = x - (xs - v_final * sig / jnp.sqrt(sig * sig + 1.0))
        return (x - x0) / jnp.maximum(sig, 1e-10)

    return guided


def slg_cfg_model(
    model_fn: ModelFn,
    skip_model_fn: ModelFn,
    cfg_scale: float,
    slg_scale: float,
    sigma_start: float,
    sigma_end: float,
    p2s=_default_p2s,
) -> ModelFn:
    """CFG plus SD3.5 skip-layer guidance: the result gains
    slg_scale * (cond - cond_with_skipped_layers) while sigma is in
    [sigma_end, sigma_start] (the reference's SkipLayerGuidanceDiT
    patch, composed in eps space under this framework's sampler
    contract). The window check is a lax.cond, so the trajectory is
    still one XLA program AND off-window steps skip the extra forward
    at runtime (XLA conditionals execute only the taken branch) — with
    the default [0.01, 0.15] window that saves the ~50%-per-step skip
    pass on most steps. The gate uses sigma[0]: every sampler step
    broadcasts one scalar sigma across the batch."""

    def guided(x, sigma, cond):
        pos, _neg = cond
        eps_pos, base = _cfg_eval(model_fn, cfg_scale, x, sigma, cond, p2s)

        def correction(_):
            return _perturbed_delta(
                skip_model_fn, x, sigma, pos, eps_pos, slg_scale, p2s
            )

        active = (sigma[0] >= sigma_end) & (sigma[0] <= sigma_start)
        return base + jax.lax.cond(
            active, correction, lambda _: jnp.zeros_like(eps_pos), None
        )

    return guided


def _perturbed_delta(pert_model_fn, x, sigma, pos, eps_pos, scale, p2s):
    """scale * (eps_pos - eps_perturbed): the guidance-delta body
    shared by skip-layer guidance and PAG — one composite-aware
    perturbed forward against the positive conditioning."""
    if _needs_composite(pos):
        eps_pert = composite_eps(pert_model_fn, x, sigma, pos, p2s)
    else:
        eps_pert = pert_model_fn(x, sigma, pos)
    return scale * (eps_pos - eps_pert)


def pag_cfg_model(
    model_fn: ModelFn,
    pag_model_fn: ModelFn,
    cfg_scale: float,
    pag_scale: float,
    p2s=_default_p2s,
) -> ModelFn:
    """CFG plus perturbed-attention guidance (PAG, Ahn et al. 2024 —
    the reference stack's PerturbedAttentionGuidance patch): the
    result gains pag_scale * (cond - cond_with_identity_attn), where
    the perturbed pass replaces the middle-block self-attention matrix
    with identity (out = V; models/unet.py pag flag). One extra
    positive-cond forward per step, parameters shared."""

    def guided(x, sigma, cond):
        pos, _neg = cond
        eps_pos, base = _cfg_eval(model_fn, cfg_scale, x, sigma, cond, p2s)
        return base + _perturbed_delta(
            pag_model_fn, x, sigma, pos, eps_pos, pag_scale, p2s
        )

    return guided


def perp_neg_model(
    model_fn: ModelFn,
    cfg_scale: float,
    neg_scale: float,
    p2s=_default_p2s,
) -> ModelFn:
    """Perpendicular negative guidance (the PerpNegGuider node;
    Armandpour et al. 2023 "Re-imagine the Negative Prompt Algorithm").
    cond is ((positive, negative), empty):

        pos = eps(positive) - eps(empty)
        neg = eps(negative) - eps(empty)
        perp = neg - (<neg, pos> / |pos|^2) * pos     (per sample)
        out  = eps(empty) + cfg_scale * (pos - neg_scale * perp)

    Only the component of the negative orthogonal to the positive
    pushes away — a negative aligned with the positive no longer
    cancels it. Three conds run as ONE 3B-batched eval when
    structurally compatible. The projection is per-sample (axes 1..n);
    the reference stack computes it over the whole tensor, identical
    at batch 1."""

    def guided(x, sigma, cond):
        (pos_c, neg_c), empty_c = cond
        _reject_unsupported_cond(pos_c, neg_c, empty_c)
        comp = any(_needs_composite(c) for c in (pos_c, neg_c, empty_c))
        if (
            not comp
            and _conds_batchable(pos_c, neg_c)
            and _conds_batchable(neg_c, empty_c)
            and _conds_batchable(pos_c, empty_c)
        ):
            x3 = jnp.concatenate([x, x, x], axis=0)
            s3 = jnp.concatenate([sigma, sigma, sigma], axis=0)
            c3 = jax.tree_util.tree_map(
                lambda a, b, c: jnp.concatenate([a, b, c], axis=0),
                pos_c, neg_c, empty_c,
            )
            e_pos, e_neg, e_empty = jnp.split(model_fn(x3, s3, c3), 3, axis=0)
        else:
            def _eps(c):
                if _needs_composite(c):
                    return composite_eps(model_fn, x, sigma, c, p2s)
                return model_fn(x, sigma, c)

            e_pos, e_neg, e_empty = _eps(pos_c), _eps(neg_c), _eps(empty_c)
        pos = e_pos - e_empty
        neg = e_neg - e_empty
        axes = tuple(range(1, x.ndim))
        dot = jnp.sum(neg * pos, axis=axes, keepdims=True)
        sq = jnp.maximum(jnp.sum(pos * pos, axis=axes, keepdims=True), 1e-12)
        perp = neg - (dot / sq) * pos
        return e_empty + cfg_scale * (pos - neg_scale * perp)

    return guided


def sag_cfg_model(
    model_fn: ModelFn,
    capture_fn,
    cfg_scale: float,
    sag_scale: float,
    blur_sigma: float,
    p2s=_default_p2s,
) -> ModelFn:
    """CFG plus self-attention guidance (SAG, Hong et al. 2023 — the
    reference stack's SelfAttentionGuidance patch). Per step:

      1. capture pass (capture_fn, the sag_capture model_fn form):
         eps_uncond + the middle-block attn1 softmax probs;
      2. salience mask: attention each mid token RECEIVES (mean over
         heads, summed over queries) > 1.0 — the uniform-attention
         level — upscaled nearest to the latent grid;
      3. degraded input: gaussian-blur (radius 4, sigma blur_sigma)
         the uncond x0 estimate where salient, re-noise with the same
         noise component (x - x0);
      4. out = cfg + sag_scale * (eps_uncond - eps_degraded) — the
         paper's guide-away-from-degraded, composed in eps space
         (denoised = x - sigma*eps makes it equivalent to the x0
         form out_x0 = cfg_x0 + s * sigma * (eps_d - eps_u)).

    Four model evals per step: the capture pass is separate so the
    CFG 2B batch stays intact (the reference reuses its uncond eval
    and pays an attention-capture hook instead)."""
    from .filters import gaussian_blur

    def guided(x, sigma, cond):
        pos, neg = cond
        if _needs_composite(neg):
            raise ValueError(
                "SelfAttentionGuidance needs a single negative "
                "conditioning entry (the degraded pass re-evaluates "
                "the uncond prediction)"
            )
        eps_pos, base = _cfg_eval(model_fn, cfg_scale, x, sigma, cond, p2s)
        eps_u, probs, (mid_h, mid_w) = capture_fn(x, sigma, neg)
        sig = sigma.reshape((-1,) + (1,) * (x.ndim - 1))
        u_x0 = x - sig * eps_u
        received = probs.mean(axis=1).sum(axis=1)  # [B, mid_tokens]
        mask = (received > 1.0).astype(x.dtype)
        mask = mask.reshape(mask.shape[0], mid_h, mid_w)
        mask = jax.image.resize(
            mask, (mask.shape[0], x.shape[1], x.shape[2]), method="nearest"
        )[..., None]
        blurred = gaussian_blur(u_x0, 4, blur_sigma)
        degraded_x0 = blurred * mask + u_x0 * (1.0 - mask)
        degraded_x = degraded_x0 + (x - u_x0)
        eps_d = model_fn(degraded_x, sigma, neg)
        return base + sag_scale * (eps_u - eps_d)

    return guided


def _denoised(model_fn: ModelFn, x, sigma, cond):
    """x0 prediction from the eps model at scalar sigma."""
    sig_batch = jnp.broadcast_to(sigma, (x.shape[0],))
    eps = model_fn(x, sig_batch, cond)
    return x - sigma * eps


# --- samplers ------------------------------------------------------------

def sample(
    model_fn: ModelFn,
    x_init: jax.Array,
    sigmas: jnp.ndarray,
    cond: Any,
    sampler: str = "euler",
    noise_key: jax.Array | None = None,
    flow: bool = False,
) -> jax.Array:
    """Run a full sampling trajectory. x_init must already be scaled by
    sigmas[0] (pure noise for txt2img; noised latents for img2img).

    `flow=True` (rectified-flow models, Flux class): deterministic
    samplers apply unchanged (velocity == eps under the denoised
    contract), euler_ancestral routes to the RF-correct renoise rule,
    and the remaining stochastic samplers are rejected — their VE
    renoising (x += noise*sigma_up) puts the latent off the flow
    marginal x_t = (1-sigma)x0 + sigma*n the model was trained on."""
    deterministic = {
        "euler": _sample_euler,
        "heun": _sample_heun,
        "dpm_2": _sample_dpm_2,
        "lms": _sample_lms,
        "dpmpp_2m": _sample_dpmpp_2m,
        "ddim": _sample_ddim,
    }
    stochastic = {
        "euler_ancestral": _sample_euler_ancestral,
        "dpm_2_ancestral": _sample_dpm_2_ancestral,
        "dpmpp_2s_ancestral": _sample_dpmpp_2s_ancestral,
        "dpmpp_sde": _sample_dpmpp_sde,
        "dpmpp_2m_sde": _sample_dpmpp_2m_sde,
        "lcm": _sample_lcm,
    }
    if sampler in deterministic:
        return deterministic[sampler](model_fn, x_init, sigmas, cond)
    if sampler in stochastic:
        if noise_key is None:
            raise ValueError(f"{sampler} requires noise_key")
        if flow:
            if sampler != "euler_ancestral":
                raise ValueError(
                    f"{sampler!r} renoises with the VE rule, which is "
                    "invalid for rectified-flow models; use a "
                    "deterministic sampler (euler, ddim, dpmpp_2m, ...) "
                    "or euler_ancestral"
                )
            return _sample_euler_ancestral_rf(
                model_fn, x_init, sigmas, cond, noise_key
            )
        return stochastic[sampler](model_fn, x_init, sigmas, cond, noise_key)
    raise ValueError(f"unknown sampler {sampler!r}; use {SAMPLER_NAMES}")


# Samplers whose step does a second (correction) model eval on every
# sigma pair except the last (the lax.cond on sigma_next == 0). Keep in
# sync with the implementations above when adding a sampler.
_SECOND_ORDER = {
    "heun", "dpm_2", "dpm_2_ancestral", "dpmpp_2s_ancestral", "dpmpp_sde",
}


def model_evals_per_scan(sampler: str, n_pairs: int) -> int:
    """CFG model evaluations sample() performs over n_pairs sigma pairs
    — the `evals` attribute of the `node.KSampler` span
    (graph/nodes_core._annotate_sampling)."""
    return 2 * n_pairs - 1 if sampler in _SECOND_ORDER else n_pairs


def _sample_euler(model_fn, x, sigmas, cond):
    def step(x, sig_pair):
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        return x + d * (sigma_next - sigma), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    x, _ = jax.lax.scan(step, x, pairs)
    return x


def _ancestral_split(sigma, sigma_next, eta=1.0):
    """(sigma_down, sigma_up) for an ancestral step (k-diffusion
    get_ancestral_step)."""
    sigma_up = jnp.minimum(
        sigma_next,
        eta * jnp.sqrt(
            jnp.maximum(
                sigma_next**2
                * (sigma**2 - sigma_next**2)
                / jnp.maximum(sigma**2, 1e-10),
                0.0,
            )
        ),
    )
    sigma_down = jnp.sqrt(jnp.maximum(sigma_next**2 - sigma_up**2, 0.0))
    return sigma_down, sigma_up


def _sample_dpm_2(model_fn, x, sigmas, cond):
    """DPM-Solver-2: midpoint evaluation at the geometric mean sigma;
    the final step (sigma_next == 0) degrades to Euler."""

    def step(x, sig_pair):
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x_euler = x + d * (sigma_next - sigma)

        def second(_):
            sigma_mid = jnp.exp(
                0.5 * (jnp.log(jnp.maximum(sigma, 1e-10))
                       + jnp.log(jnp.maximum(sigma_next, 1e-10)))
            )
            x_2 = x + d * (sigma_mid - sigma)
            den_2 = _denoised(
                model_fn, x_2, jnp.maximum(sigma_mid, 1e-10), cond
            )
            d_2 = (x_2 - den_2) / jnp.maximum(sigma_mid, 1e-10)
            return x + d_2 * (sigma_next - sigma)

        # cond (not where): skips the second model eval on the
        # terminal step entirely
        return jax.lax.cond(sigma_next > 0, second, lambda _: x_euler, None), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    x, _ = jax.lax.scan(step, x, pairs)
    return x


def _sample_dpm_2_ancestral(model_fn, x, sigmas, cond, key):
    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x_euler = x + d * (sigma_down - sigma)

        def second(_):
            sigma_mid = jnp.exp(
                0.5 * (jnp.log(jnp.maximum(sigma, 1e-10))
                       + jnp.log(jnp.maximum(sigma_down, 1e-10)))
            )
            x_2 = x + d * (sigma_mid - sigma)
            den_2 = _denoised(
                model_fn, x_2, jnp.maximum(sigma_mid, 1e-10), cond
            )
            d_2 = (x_2 - den_2) / jnp.maximum(sigma_mid, 1e-10)
            return x + d_2 * (sigma_down - sigma)

        x = jax.lax.cond(sigma_down > 0, second, lambda _: x_euler, None)
        key, sub = jax.random.split(key)
        x = x + jax.random.normal(sub, x.shape, x.dtype) * sigma_up
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _lms_coefficients(sigmas_np, order: int = 4):
    """[steps, order] Adams-Bashforth-style coefficients: exact
    integrals of the Lagrange basis over each [sigma_i, sigma_{i+1}]
    (k-diffusion linear_multistep_coeff), computed in numpy at trace
    time. Column j weights the derivative from j steps ago; columns
    beyond the available history are zero."""
    import numpy as np

    steps = len(sigmas_np) - 1
    coeffs = np.zeros((steps, order), dtype=np.float64)
    for i in range(steps):
        cur_order = min(i + 1, order)
        for j in range(cur_order):
            # Lagrange basis over nodes sigmas[i-j'] for j'=0..cur_order-1
            nodes = [sigmas_np[i - k] for k in range(cur_order)]
            poly = np.poly1d([1.0])
            for k in range(cur_order):
                if k == j:
                    continue
                poly *= np.poly1d(
                    [1.0, -nodes[k]]
                ) / (nodes[j] - nodes[k])
            integral = poly.integ()
            coeffs[i, j] = integral(sigmas_np[i + 1]) - integral(sigmas_np[i])
    return coeffs


def _sample_lms(model_fn, x, sigmas, cond, order: int = 4):
    """Linear multistep (order 4) with exact per-step coefficients."""
    import numpy as np

    coeffs = jnp.asarray(
        _lms_coefficients(np.asarray(sigmas, dtype=np.float64), order),
        dtype=jnp.float32,
    )

    def step(carry, inputs):
        x, history = carry  # history: [order, ...] newest-first
        sigma, coeff_row = inputs
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        history = jnp.concatenate([d[None], history[:-1]], axis=0)
        x = x + jnp.tensordot(coeff_row, history, axes=1)
        return (x, history), None

    history = jnp.zeros((order,) + x.shape, x.dtype)
    (x, _), _ = jax.lax.scan(step, (x, history), (sigmas[:-1], coeffs))
    return x


def _sample_dpmpp_2s_ancestral(model_fn, x, sigmas, cond, key):
    """DPM-Solver++(2S) ancestral (k-diffusion formulas in
    lambda = -log sigma space)."""

    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)
        den = _denoised(model_fn, x, sigma, cond)
        # euler fallback for the terminal step
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x_euler = x + d * (sigma_down - sigma)

        def second(_):
            t = -jnp.log(jnp.maximum(sigma, 1e-10))
            t_next = -jnp.log(jnp.maximum(sigma_down, 1e-10))
            h = t_next - t
            s_mid = t + 0.5 * h
            sig_mid = jnp.exp(-s_mid)
            x_2 = (sig_mid / jnp.maximum(sigma, 1e-10)) * x - jnp.expm1(
                -0.5 * h
            ) * den
            den_2 = _denoised(
                model_fn, x_2, jnp.maximum(sig_mid, 1e-10), cond
            )
            return (
                jnp.maximum(sigma_down, 1e-10) / jnp.maximum(sigma, 1e-10)
            ) * x - jnp.expm1(-h) * den_2

        x = jax.lax.cond(sigma_down > 0, second, lambda _: x_euler, None)
        key, sub = jax.random.split(key)
        x = x + jax.random.normal(sub, x.shape, x.dtype) * sigma_up
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _sample_dpmpp_sde(model_fn, x, sigmas, cond, key, eta: float = 1.0):
    """DPM-Solver++ SDE (r=1/2): two model evaluations and two noise
    injections per step; terminal step is Euler."""
    r = 0.5

    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x_euler = x + d * (sigma_next - sigma)
        key, sub1, sub2 = jax.random.split(key, 3)

        def second(_):
            t = -jnp.log(jnp.maximum(sigma, 1e-10))
            t_next = -jnp.log(jnp.maximum(sigma_next, 1e-10))
            h = t_next - t
            s_mid = t + h * r
            sig_mid = jnp.exp(-s_mid)

            # sub-step 1 to sigma(s_mid), with its own ancestral split
            sd_1, su_1 = _ancestral_split(sigma, sig_mid, eta)
            t_d1 = -jnp.log(jnp.maximum(sd_1, 1e-10))
            x_2 = (jnp.maximum(sd_1, 1e-10) / jnp.maximum(sigma, 1e-10)) * x \
                - jnp.expm1(t - t_d1) * den
            x_2 = x_2 + jax.random.normal(sub1, x.shape, x.dtype) * su_1
            den_2 = _denoised(
                model_fn, x_2, jnp.maximum(sig_mid, 1e-10), cond
            )

            # sub-step 2 to sigma_next
            sd_2, su_2 = _ancestral_split(sigma, sigma_next, eta)
            t_d2 = -jnp.log(jnp.maximum(sd_2, 1e-10))
            fac = 1.0 / (2.0 * r)
            den_mix = (1.0 - fac) * den + fac * den_2
            x_solver = (
                jnp.maximum(sd_2, 1e-10) / jnp.maximum(sigma, 1e-10)
            ) * x - jnp.expm1(t - t_d2) * den_mix
            return x_solver + jax.random.normal(sub2, x.shape, x.dtype) * su_2

        x = jax.lax.cond(sigma_next > 0, second, lambda _: x_euler, None)
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _sample_dpmpp_2m_sde(model_fn, x, sigmas, cond, key, eta: float = 1.0):
    """DPM-Solver++(2M) SDE, midpoint variant: one model evaluation per
    step with a second-order correction from the previous denoised."""

    def step(carry, sig_pair):
        x, old_den, h_last, key = carry
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)

        t = -jnp.log(jnp.maximum(sigma, 1e-10))
        t_next = -jnp.log(jnp.maximum(sigma_next, 1e-10))
        h = t_next - t
        eta_h = eta * h
        x_solver = (
            jnp.maximum(sigma_next, 1e-10) / jnp.maximum(sigma, 1e-10)
        ) * jnp.exp(-eta_h) * x - jnp.expm1(-h - eta_h) * den
        # midpoint second-order correction (skipped on the first step
        # via h_last == 0)
        r = h_last / jnp.maximum(h, 1e-10)
        # k-diffusion midpoint term: 0.5 * -expm1(-h-eta_h) * (1/r) *
        # (den - old_den); expm1(-h-eta_h) < 0, so the negation matters
        corr = -0.5 * jnp.expm1(-h - eta_h) * (
            1.0 / jnp.maximum(r, 1e-10)
        ) * (den - old_den)
        x_solver = x_solver + jnp.where(h_last > 0, corr, 0.0)
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, x.shape, x.dtype)
        x_solver = x_solver + noise * jnp.maximum(sigma_next, 0.0) * jnp.sqrt(
            jnp.maximum(-jnp.expm1(-2.0 * eta_h), 0.0)
        )
        x = jnp.where(sigma_next > 0, x_solver, den)
        return (x, den, h, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _, _, _), _ = jax.lax.scan(
        step, (x, jnp.zeros_like(x), jnp.zeros(()), key), pairs
    )
    return x


def _sample_lcm(model_fn, x, sigmas, cond, key):
    """LCM sampling: jump to the denoised estimate, re-noise to the
    next sigma."""

    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, x.shape, x.dtype)
        x = jnp.where(sigma_next > 0, den + sigma_next * noise, den)
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _sample_ddim(model_fn, x, sigmas, cond):
    """Deterministic (eta=0) DDIM, written in its own form:
    x_{t-1} = x0_hat + sigma_next * eps_hat. In the sigma-space eps
    parameterisation this is algebraically identical to the Euler step
    (x + (x-x0)/sigma * (sigma_next-sigma)) — the name is kept as a
    first-class sampler so the equivalence is explicit, not a silent
    alias."""

    def step(x, sig_pair):
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        eps = (x - den) / jnp.maximum(sigma, 1e-10)
        return den + sigma_next * eps, None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    x, _ = jax.lax.scan(step, x, pairs)
    return x


def _sample_euler_ancestral(model_fn, x, sigmas, cond, key):
    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x = x + d * (sigma_down - sigma)
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, x.shape, x.dtype)
        x = x + noise * sigma_up
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _sample_euler_ancestral_rf(model_fn, x, sigmas, cond, key, eta=1.0):
    """Ancestral Euler for rectified flow. Under x_t = (1-s)x0 + s*n
    the VE renoise rule (x += noise*sigma_up) leaves the latent off the
    flow marginal; the RF rule downsteps to sigma_down, rescales the
    signal by alpha_next/alpha_down, and renoises with the coefficient
    that restores exactly the (1-s_next, s_next) marginal."""

    def step(carry, sig_pair):
        x, key = carry
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        down_ratio = 1.0 + (sigma_next / jnp.maximum(sigma, 1e-10) - 1.0) * eta
        sigma_down = sigma_next * down_ratio
        alpha_next = 1.0 - sigma_next
        alpha_down = jnp.maximum(1.0 - sigma_down, 1e-10)
        renoise = jnp.sqrt(
            jnp.maximum(
                sigma_next**2 - sigma_down**2 * (alpha_next / alpha_down) ** 2,
                0.0,
            )
        )
        r = sigma_down / jnp.maximum(sigma, 1e-10)
        x_det = r * x + (1.0 - r) * den
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, x.shape, x.dtype)
        x_st = (alpha_next / alpha_down) * x_det + noise * renoise
        x = jnp.where(sigma_next > 0, x_st, den)
        return (x, key), None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    (x, _), _ = jax.lax.scan(step, (x, key), pairs)
    return x


def _sample_heun(model_fn, x, sigmas, cond):
    def step(x, sig_pair):
        sigma, sigma_next = sig_pair
        den = _denoised(model_fn, x, sigma, cond)
        d = (x - den) / jnp.maximum(sigma, 1e-10)
        x_euler = x + d * (sigma_next - sigma)

        def correct(_):
            den2 = _denoised(model_fn, x_euler, sigma_next, cond)
            d2 = (x_euler - den2) / jnp.maximum(sigma_next, 1e-10)
            return x + 0.5 * (d + d2) * (sigma_next - sigma)

        x = jax.lax.cond(sigma_next > 0, correct, lambda _: x_euler, None)
        return x, None

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=-1)
    x, _ = jax.lax.scan(step, x, pairs)
    return x


def _sample_dpmpp_2m(model_fn, x, sigmas, cond):
    """DPM-Solver++(2M): second-order multistep in log-sigma time."""

    def t_of(sigma):
        return -jnp.log(jnp.maximum(sigma, 1e-10))

    def step(carry, inp):
        x, old_den, have_old = carry
        sigma, sigma_next, sigma_prev = inp
        den = _denoised(model_fn, x, sigma, cond)

        t, t_next = t_of(sigma), t_of(sigma_next)
        h = t_next - t

        def first_order(_):
            return (sigma_next / sigma) * x - jnp.expm1(-h) * den

        def second_order(_):
            # clamps guard degenerate schedules with equal adjacent
            # sigmas (h_last == 0 would make 1/(2r) inf -> NaN)
            h_last = t - t_of(sigma_prev)
            r = jnp.maximum(h_last, 1e-10) / jnp.maximum(h, 1e-10)
            den_d = (1 + 1 / (2 * r)) * den - (1 / (2 * r)) * old_den
            return (sigma_next / sigma) * x - jnp.expm1(-h) * den_d

        use_second = jnp.logical_and(have_old, sigma_next > 0)
        x_next = jax.lax.cond(use_second, second_order, first_order, None)
        # final step to sigma=0 returns the denoised sample exactly
        x_next = jnp.where(sigma_next > 0, x_next, den)
        return (x_next, den, jnp.asarray(True)), None

    sigma_prevs = jnp.concatenate([sigmas[:1], sigmas[:-1]])
    inputs = jnp.stack([sigmas[:-1], sigmas[1:], sigma_prevs[:-1]], axis=-1)
    init = (x, jnp.zeros_like(x), jnp.asarray(False))
    (x, _, _), _ = jax.lax.scan(step, init, inputs)
    return x


# --- flow matching (rectified flow, WAN/DiT video family) -----------------

def get_flow_timesteps(steps: int, shift: float = 3.0) -> jnp.ndarray:
    """[steps+1] descending t in [1, 0] with timestep shift (video
    models sample with shifted sigmas: t' = s*t / (1 + (s-1)*t))."""
    import numpy as np

    t = np.linspace(1.0, 0.0, steps + 1)
    t = shift * t / (1.0 + (shift - 1.0) * t)
    return jnp.asarray(t, dtype=jnp.float32)


def sample_flow(
    model_fn: ModelFn,
    x: jax.Array,
    timesteps: jnp.ndarray,
    cond: Any,
) -> jax.Array:
    """Euler ODE for velocity-prediction flow matching: x1 = noise at
    t=1, data at t=0; model predicts v = dx/dt; x_{t-dt} = x + v*dt
    with dt negative. `model_fn(x, t_batch*1000, cond) -> v` (the 1000x
    matches DiT timestep-embedding conventions)."""

    def step(x, t_pair):
        t, t_next = t_pair
        t_batch = jnp.broadcast_to(t * 1000.0, (x.shape[0],))
        v = model_fn(x, t_batch, cond)
        return x + v * (t_next - t), None

    pairs = jnp.stack([timesteps[:-1], timesteps[1:]], axis=-1)
    x, _ = jax.lax.scan(step, x, pairs)
    return x


def _conds_batchable(pos, neg) -> bool:
    """Whether cond/uncond can ride one 2B-batched model pass: same
    tree structure AND same leaf shapes (token-concatenated positives
    vs a plain negative differ on the token axis — those need two
    passes). Conditioning carrying ControlNet weights is never
    batchable: control_params are pytree leaves, and the 2B tree_map
    concat would concatenate the NETWORK WEIGHTS of the two sides
    (ControlNetApplyAdvanced sets identical structures on both)."""
    if (
        getattr(pos, "control_params", None) is not None
        or getattr(neg, "control_params", None) is not None
    ):
        return False
    if jax.tree_util.tree_structure(pos) != jax.tree_util.tree_structure(
        neg
    ):
        return False
    return [
        getattr(leaf, "shape", None)
        for leaf in jax.tree_util.tree_leaves(pos)
    ] == [
        getattr(leaf, "shape", None)
        for leaf in jax.tree_util.tree_leaves(neg)
    ]


def cfg_flow_model(model_fn: ModelFn, cfg_scale: float) -> ModelFn:
    """CFG for velocity models (same batched-pass trick as cfg_model)."""
    if cfg_scale == 1.0:
        def passthrough(x, t, cond):
            pos, _ = cond
            return model_fn(x, t, pos)
        return passthrough

    def guided(x, t, cond):
        pos, neg = cond
        if _conds_batchable(pos, neg):
            x2 = jnp.concatenate([x, x], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            c2 = jax.tree_util.tree_map(
                lambda p, n: jnp.concatenate([p, n], axis=0), pos, neg
            )
            v2 = model_fn(x2, t2, c2)
            v_pos, v_neg = jnp.split(v2, 2, axis=0)
        else:
            v_pos = model_fn(x, t, pos)
            v_neg = model_fn(x, t, neg)
        return v_neg + cfg_scale * (v_pos - v_neg)

    return guided


def sample_flow_masked(
    model_fn: ModelFn,
    x: jax.Array,
    timesteps: jnp.ndarray,
    cond: Any,
    known: jax.Array,
    mask: jax.Array,
    noise: jax.Array,
) -> jax.Array:
    """Flow sampling with clamped known regions (i2v / inpainting).

    `known` carries clean values where mask==1; after every step the
    masked region is reset onto the straight-line flow path
    x_t = (1-t)*known + t*noise, so generation stays consistent with
    the conditioning frames while free regions evolve normally.
    """

    def step(x, t_pair):
        t, t_next = t_pair
        t_batch = jnp.broadcast_to(t * 1000.0, (x.shape[0],))
        v = model_fn(x, t_batch, cond)
        x = x + v * (t_next - t)
        clamped = (1.0 - t_next) * known + t_next * noise
        return x * (1.0 - mask) + clamped * mask, None

    pairs = jnp.stack([timesteps[:-1], timesteps[1:]], axis=-1)
    x0 = x * (1.0 - mask) + ((1.0 - timesteps[0]) * known + timesteps[0] * noise) * mask
    x, _ = jax.lax.scan(step, x0, pairs)
    return x
