"""dots3-note-prev's latent attention and routing rule against published
modelling code, where it is installed. The model's own code is not in the
sandbox; two of its mechanisms have published forms that are:

- `apply_mla_qkv_lora_rescale` is LongCat-Flash's `mla_scale_q_lora` /
  `mla_scale_kv_lora`: a tiny `LongcatFlashMLA` (eager attention) with the
  same seeded weights copied across, against the repo's latent attention
  at float32, on the system's path (`dots3._queries`, `mla.latents` with
  its `scale`, `mla.expanded`) and on the reference's (`reference/dots3`):
  the query latent under its norm, both rescales, the rotation in pairs,
  one rope key for all heads left unscaled, the softmax's scale;
- the router is DeepSeek-V3's (`scoring_func` sigmoid, `topk_method`
  noaux_tc): `DeepseekV3TopkRouter` at `n_group` 1 against
  `moe.sigmoid_route` and the reference's `route`.

Outputs agree to float32 rounding, and with either rescale left out they
do not. It holds the latent attention and the routing rule, not the
window, the gate or the index (`test_dots3_model.py` holds those against
the reference).

One file, so one xdist worker pays the import of torch and transformers."""

import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

os.environ.setdefault("USE_TF", "0")  # transformers would import TensorFlow beside torch
torch = pytest.importorskip("torch")
longcat = pytest.importorskip("transformers.models.longcat_flash.modeling_longcat_flash")
deepseek_v3 = pytest.importorskip("transformers.models.deepseek_v3.modeling_deepseek_v3")

from comfyui_distributed_tpu.models import dots3, mla, moe  # noqa: E402
from comfyui_distributed_tpu.models.lm_common import apply_rope_pairs, rope_tables  # noqa: E402
from comfyui_distributed_tpu.reference import dots3 as ref  # noqa: E402

# one kind of layer at a size for the CPU: the sliding kind's ratio of widths (nope 12, rope
# 8, values 8), rescales of 2 and sqrt 2
CFG = dots3.Dots3Config(
    hidden_size=64, num_hidden_layers=5, swa_num_attention_heads=4, swa_q_lora_rank=16,
    swa_kv_lora_rank=32, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
    swa_rope_theta=5e4, n_routed_experts=16, num_experts_per_tok=4, vocab_size=512)
KIND = CFG.sliding
TOKENS = 24


@pytest.fixture(scope="module")
def weights():
    """A sliding layer's attention weights, seeded; the two norms'
    scales drawn too, so that a norm left out shows."""
    tree = dots3.init_params(CFG, jax.random.key(2))["layers"][2]["attn"]
    for index, name in enumerate(("q_norm", "kv_norm")):
        tree[name] = jax.random.uniform(
            jax.random.key(3 + index), tree[name].shape, minval=0.5, maxval=1.5)
    return tree


@pytest.fixture(scope="module")
def published(weights):
    from transformers.models.longcat_flash.configuration_longcat_flash import LongcatFlashConfig

    config = LongcatFlashConfig(
        vocab_size=512, hidden_size=CFG.hidden_size, num_layers=1, num_hidden_layers=2,
        num_attention_heads=KIND.heads, max_position_embeddings=128,
        rms_norm_eps=CFG.rms_norm_eps, rope_theta=KIND.theta, rope_scaling=None,
        attention_bias=False, q_lora_rank=KIND.q_rank, kv_lora_rank=KIND.rank,
        qk_nope_head_dim=KIND.nope, qk_rope_head_dim=KIND.rope, head_dim=KIND.rope,
        v_head_dim=KIND.value, attn_implementation="eager")
    layer = longcat.LongcatFlashMLA(config, layer_idx=0).to(torch.float32).eval()

    def t(array):  # ours are stored in by out, a torch Linear's weight out by in
        return torch.from_numpy(np.asarray(array, np.float32).T.copy())

    def v(array):
        return torch.from_numpy(np.array(array, np.float32))

    # theirs packs a head's nope key and value side by side in one matrix
    up = np.concatenate([np.asarray(weights["w_uk"]), np.asarray(weights["w_uv"])], axis=2)
    layer.load_state_dict({
        "q_a_proj.weight": t(weights["w_dq"]),
        "q_a_layernorm.weight": v(weights["q_norm"]),
        "q_b_proj.weight": t(weights["w_uq"]),
        "kv_a_proj_with_mqa.weight": t(weights["w_dkv"]),
        "kv_a_layernorm.weight": v(weights["kv_norm"]),
        "kv_b_proj.weight": t(up.reshape(KIND.rank, -1)),
        "o_proj.weight": t(weights["w_o"]),
    })
    return layer, longcat.LongcatFlashRotaryEmbedding(config)


def theirs(published, x):
    layer, rotary = published
    hidden = torch.from_numpy(np.array(x, np.float32))[None]
    positions = torch.arange(TOKENS)[None]
    mask = torch.full((TOKENS, TOKENS), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        out, _ = layer(hidden, rotary(hidden, positions), mask)
    return out[0].numpy()


def ours(cfg, weights, x):
    """The system's path: queries under the rescale, the latents with
    their scale, every key and value expanded, causal, then W_o."""
    kind = cfg.sliding
    rope = rope_tables(kind.theta, kind.rope, jnp.arange(TOKENS))
    _, q_nope, q_rope = dots3._queries(cfg, kind, weights, x, rope)
    rows = mla.latents(
        weights, x, rope, cfg.rms_norm_eps, rotate=apply_rope_pairs, scale=kind.s_kv)
    out = mla.expanded(q_nope, q_rope, rows, weights["w_uk"], weights["w_uv"], kind.width ** -0.5)
    return np.asarray(out.reshape(TOKENS, -1) @ weights["w_o"])


def the_references(cfg, weights, x, **wrong):
    sizes = dataclasses.replace(ref.Sizes.of(cfg), window=None, gate=False, **wrong)
    c_q = ref._rms_norm(x @ weights["w_dq"], weights["q_norm"], sizes.rms_norm_eps)
    rows = ref.latents(sizes, weights, x, sizes.swa_rope_theta)
    return np.asarray(ref._attention(
        sizes, weights, x, c_q, rows, ref.band(sizes, TOKENS), sizes.swa_rope_theta, None, 2, 16))


def test_latent_attention_with_the_rescale_is_the_published_codes(weights, published):
    assert (KIND.s_q, KIND.s_kv) == (2.0, 2 ** 0.5)
    layer, _ = published
    assert layer.mla_scale_q_lora == KIND.s_q and layer.mla_scale_kv_lora == KIND.s_kv
    assert layer.scaling == KIND.width ** -0.5
    x = jax.random.normal(jax.random.key(5), (TOKENS, CFG.hidden_size))
    want = theirs(published, x)
    # float32 in another order of operations: 1e-5 of the outputs' scale
    scale = np.abs(want).max()
    np.testing.assert_allclose(ours(CFG, weights, x), want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(
        the_references(CFG, weights, x), want, rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("left_out", ["rescale_q", "rescale_kv"])
def test_with_either_rescale_left_out_it_is_not(weights, published, left_out):
    x = jax.random.normal(jax.random.key(5), (TOKENS, CFG.hidden_size))
    want = theirs(published, x)
    got = the_references(CFG, weights, x, **{left_out: False})
    assert np.abs(got - want).max() > 0.05 * np.abs(want).max()
    # and the system with the switch off is the reference with both left out
    off = dataclasses.replace(CFG, apply_mla_qkv_lora_rescale=False)
    assert (off.sliding.s_q, off.sliding.s_kv) == (1.0, 1.0)
    both = the_references(CFG, weights, x, rescale_q=False, rescale_kv=False)
    np.testing.assert_allclose(ours(off, weights, x), both, rtol=1e-4, atol=1e-5)
    assert np.abs(both - want).max() > 0.05 * np.abs(want).max()


def test_the_routing_rule_is_deepseek_v3s_at_one_group():
    experts, k, hidden, tokens = 16, 4, CFG.hidden_size, 40
    config = types.SimpleNamespace(
        num_experts_per_tok=k, n_routed_experts=experts, routed_scaling_factor=1.0, n_group=1,
        topk_group=1, norm_topk_prob=True, hidden_size=hidden)
    router = deepseek_v3.DeepseekV3TopkRouter(config)
    w_g = np.array(jax.random.normal(jax.random.key(7), (hidden, experts)), np.float32)
    bias = np.array(0.1 * jax.random.normal(jax.random.key(8), (experts,)), np.float32)
    x = np.array(jax.random.normal(jax.random.key(9), (tokens, hidden)), np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.from_numpy(w_g.T.copy()))
        router.e_score_correction_bias.copy_(torch.from_numpy(bias))
        their_ids, their_weights = router(torch.from_numpy(x))
    order = np.argsort(their_ids.numpy(), axis=1)
    want_ids = np.take_along_axis(their_ids.numpy(), order, axis=1)
    want_weights = np.take_along_axis(their_weights.numpy(), order, axis=1)
    logits = jnp.asarray(x @ w_g)
    sizes = dataclasses.replace(ref.Sizes.of(CFG), num_experts_per_tok=k)
    for ids, weights in (
            moe.sigmoid_route(logits, jnp.asarray(bias), k, scale=1.0, renormalise=True),
            ref.route(sizes, bias, logits)):
        order = np.argsort(np.asarray(ids), axis=1)
        np.testing.assert_array_equal(np.take_along_axis(np.asarray(ids), order, axis=1), want_ids)
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(weights), order, axis=1), want_weights, rtol=1e-5)
    # the weights are the chosen scores without the bias, over their sum
    np.testing.assert_allclose(want_weights.sum(axis=1), 1.0, rtol=1e-5)
