"""End-to-end tiny pipeline: prompt → latents → image, plus the
latent img2img path USDU tiles use."""

import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.models import pipeline as pl


def _bundle():
    return pl.load_pipeline("tiny-unet", seed=0)


def test_txt2img_shapes_and_determinism():
    bundle = _bundle()
    img = pl.txt2img(
        bundle, "a red square", height=32, width=32, steps=3, seed=7, batch=2
    )
    assert img.shape == (2, 32, 32, 3)
    arr = np.asarray(img)
    assert np.isfinite(arr).all()
    assert (arr >= 0).all() and (arr <= 1).all()
    again = pl.txt2img(
        bundle, "a red square", height=32, width=32, steps=3, seed=7, batch=2
    )
    np.testing.assert_array_equal(arr, np.asarray(again))


def test_txt2img_seed_changes_output():
    bundle = _bundle()
    a = pl.txt2img(bundle, "x", height=32, width=32, steps=2, seed=1)
    b = pl.txt2img(bundle, "x", height=32, width=32, steps=2, seed=2)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_img2img_latents_partial_denoise():
    bundle = _bundle()
    latents = jnp.ones((1, 8, 8, 4)) * 0.3
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    out = pl.img2img_latents(
        bundle, latents, pos, neg, steps=3, denoise=0.4, seed=0
    )
    assert out.shape == latents.shape
    assert np.isfinite(np.asarray(out)).all()
    # low denoise keeps output in the latents' neighborhood, not noise-scale
    assert float(jnp.abs(out).mean()) < 5.0


def test_dual_encoder_context_concat():
    """SDXL layout: context = concat of both encoders' penultimate
    hidden states (no zero padding), pooled from the projected second
    encoder."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import pipeline as pl

    bundle = pl.load_pipeline("tiny-unet-adm", seed=0)
    assert bundle.text_encoder_2 is not None
    cond = pl.encode_text_pooled(bundle, ["a castle on a hill"])
    # tiny-te-l width 64 + tiny-te-g width 96 = context 160
    assert cond.context.shape[-1] == 160
    assert cond.pooled.shape == (1, 96)
    # concat halves differ from zero-pad: second half must be nonzero
    assert float(jnp.abs(cond.context[..., 64:]).max()) > 0


def test_v_parameterization_exact_conversion():
    """tiny-unet-v shares weights with tiny-unet (identical module, same
    init seed); its model_fn must equal the exact v->eps transform of
    the raw network output: eps = x*s/(s^2+1) + v/sqrt(s^2+1)."""
    import jax

    eps_bundle = pl.load_pipeline("tiny-unet", seed=0)
    v_bundle = pl.load_pipeline("tiny-unet-v", seed=0)
    # same module tree + same init key => identical weights
    chex_eq = jax.tree_util.tree_all(
        jax.tree_util.tree_map(
            lambda a, b: bool((a == b).all()),
            eps_bundle.params["unet"], v_bundle.params["unet"],
        )
    )
    assert chex_eq, "tiny-unet-v must share tiny-unet's init weights"

    raw_fn = pl._make_model_fn(eps_bundle, eps_bundle.params)   # eps: raw net
    v_fn = pl._make_model_fn(v_bundle, v_bundle.params)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 4)), jnp.float32)
    sigma = jnp.asarray([3.0, 0.5], jnp.float32)
    ctx = jnp.asarray(rng.standard_normal((2, 7, 64)), jnp.float32)

    raw = np.asarray(raw_fn(x, sigma, ctx), np.float32)
    got = np.asarray(v_fn(x, sigma, ctx), np.float32)
    s = np.asarray(sigma, np.float32).reshape(-1, 1, 1, 1)
    want = np.asarray(x, np.float32) * (s / (s**2 + 1)) + raw / np.sqrt(s**2 + 1)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=0)  # bf16 net output


def test_v_parameterization_txt2img_runs():
    bundle = pl.load_pipeline("tiny-unet-v", seed=0)
    img = np.asarray(
        pl.txt2img(bundle, "v-pred", height=32, width=32, steps=2, seed=3)
    )
    assert img.shape == (1, 32, 32, 3)
    assert np.isfinite(img).all()
    assert (img >= 0).all() and (img <= 1).all()
