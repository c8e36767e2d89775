"""The float32 reference of LongCat-Flash-Chat (`reference/longcat_flash.py`)
against the model's published modelling code, which is installed
(`transformers.models.longcat_flash`): a tiny `LongcatFlashForCausalLM`
(eager attention) with the same seeded weights copied across, every
expert held, against the reference's one pass: the double layer with its
branch on the shortcut, the two norm epsilons, latent attention under
both rescales with the rotation of interleaved pairs, the router over
experts and identities (softmax over the whole width, the selection
bias, times the factor, not renormalised), the identities' weighted
input. Logits agree to float32 rounding; with any one of the reference's
wrong mechanisms they do not. And the served programs at the same tiny
size, so the chain is published code = reference = system.

One file, so one xdist worker pays the import of torch and transformers."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

os.environ.setdefault("USE_TF", "0")  # transformers would import TensorFlow beside torch
torch = pytest.importorskip("torch")
longcat = pytest.importorskip("transformers.models.longcat_flash.modeling_longcat_flash")

from comfyui_distributed_tpu.models import longcat_flash as lf  # noqa: E402
from comfyui_distributed_tpu.models.registry import get_config  # noqa: E402
from comfyui_distributed_tpu.reference import longcat_flash as ref  # noqa: E402

# the tiny preset with every expert held: the published code has no cut
CFG = dataclasses.replace(get_config("tiny-longcat-flash"), ep_size=1)
TOKENS = 29


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the norms' scales and the selection bias drawn
    too, so that a norm, an epsilon's place or the bias left out shows."""
    tree = lf.init_params(CFG, jax.random.key(2))
    count = [0]

    def drawn(shape, low, high):
        count[0] += 1
        return jax.random.uniform(jax.random.key(100 + count[0]), shape, minval=low, maxval=high)

    for block in tree["layers"]:
        for sub in block["sub"]:
            for holder, name in ((sub, "attn_norm"), (sub, "ffn_norm"),
                                 (sub["attn"], "q_norm"), (sub["attn"], "kv_norm")):
                holder[name] = drawn(holder[name].shape, 0.5, 1.5)
        block["moe"]["bias"] = drawn(block["moe"]["bias"].shape, -0.02, 0.02)
    tree["final_norm"] = drawn(tree["final_norm"].shape, 0.5, 1.5)
    return tree


@pytest.fixture(scope="module")
def published(params):
    from transformers.models.longcat_flash.configuration_longcat_flash import LongcatFlashConfig

    config = LongcatFlashConfig(
        vocab_size=CFG.vocab_size, hidden_size=CFG.hidden_size, num_layers=CFG.num_layers,
        num_hidden_layers=2 * CFG.num_layers, num_attention_heads=CFG.num_attention_heads,
        max_position_embeddings=128, rms_norm_eps=CFG.rms_norm_eps, rope_theta=CFG.rope_theta,
        rope_scaling=None, attention_bias=False, ffn_hidden_size=CFG.ffn_hidden_size,
        q_lora_rank=CFG.q_lora_rank, kv_lora_rank=CFG.kv_lora_rank,
        qk_nope_head_dim=CFG.qk_nope_head_dim, qk_rope_head_dim=CFG.qk_rope_head_dim,
        head_dim=CFG.qk_rope_head_dim, v_head_dim=CFG.v_head_dim, moe_topk=CFG.moe_topk,
        n_routed_experts=CFG.n_routed_experts, zero_expert_num=CFG.zero_expert_num,
        expert_ffn_hidden_size=CFG.expert_ffn_hidden_size,
        routed_scaling_factor=CFG.routed_scaling_factor, attn_implementation="eager")
    model = longcat.LongcatFlashForCausalLM(config).to(torch.float32).eval()

    def t(array):  # ours are stored in by out, a torch Linear's weight out by in
        return torch.from_numpy(np.asarray(array, np.float32).T.copy())

    def v(array):
        return torch.from_numpy(np.array(array, np.float32))

    def swiglu(prefix, p, state):
        width = p["w_gate_up"].shape[-1] // 2
        state[prefix + "gate_proj.weight"] = t(p["w_gate_up"][:, :width])
        state[prefix + "up_proj.weight"] = t(p["w_gate_up"][:, width:])
        state[prefix + "down_proj.weight"] = t(p["w_down"])

    state = {"model.embed_tokens.weight": v(params["embed"]),
             "model.norm.weight": v(params["final_norm"]), "lm_head.weight": t(params["head"])}
    for index, block in enumerate(params["layers"]):
        at = f"model.layers.{index}."
        for i, sub in enumerate(block["sub"]):
            p = sub["attn"]
            # theirs packs a head's nope key and value side by side in one matrix
            up = np.concatenate([np.asarray(p["w_uk"]), np.asarray(p["w_uv"])], axis=2)
            state.update({
                f"{at}input_layernorm.{i}.weight": v(sub["attn_norm"]),
                f"{at}post_attention_layernorm.{i}.weight": v(sub["ffn_norm"]),
                f"{at}self_attn.{i}.q_a_proj.weight": t(p["w_dq"]),
                f"{at}self_attn.{i}.q_a_layernorm.weight": v(p["q_norm"]),
                f"{at}self_attn.{i}.q_b_proj.weight": t(p["w_uq"]),
                f"{at}self_attn.{i}.kv_a_proj_with_mqa.weight": t(p["w_dkv"]),
                f"{at}self_attn.{i}.kv_a_layernorm.weight": v(p["kv_norm"]),
                f"{at}self_attn.{i}.kv_b_proj.weight": t(up.reshape(CFG.kv_lora_rank, -1)),
                f"{at}self_attn.{i}.o_proj.weight": t(p["w_o"]),
            })
            swiglu(f"{at}mlps.{i}.", sub["mlp"], state)
        state[f"{at}mlp.router.classifier.weight"] = t(block["moe"]["w_g"])
        state[f"{at}mlp.router.e_score_correction_bias"] = v(block["moe"]["bias"])
        for expert in range(CFG.n_routed_experts):
            swiglu(f"{at}mlp.experts.{expert}.", jax.tree_util.tree_map(
                lambda w: w[expert], block["moe"]["experts"]), state)
    model.load_state_dict(state)
    return model


@pytest.fixture(scope="module")
def ids():
    return np.array(jax.random.randint(jax.random.key(5), (TOKENS,), 0, CFG.vocab_size))


@pytest.fixture(scope="module")
def theirs(published, ids):
    with torch.no_grad():
        return published(torch.from_numpy(ids)[None].long()).logits[0].numpy()


def test_the_published_model_holds_what_the_configuration_counts(published):
    held = sum(p.numel() for p in published.parameters()) + sum(
        b.numel() for name, b in published.named_buffers() if "e_score_correction_bias" in name)
    assert held == lf.param_count(CFG)
    layer = published.model.layers[0]
    assert len(layer.mlp.experts) == CFG.router_width == 12
    assert [type(e).__name__ for e in layer.mlp.experts[CFG.n_routed_experts:]] == ["Identity"] * 4
    assert layer.self_attn[0].mla_scale_q_lora == CFG.s_q == 2 ** 0.5
    assert layer.self_attn[1].mla_scale_kv_lora == CFG.s_kv == 2.0
    assert layer.self_attn[0].scaling == CFG.qk_head_dim ** -0.5
    assert layer.self_attn[0].q_a_layernorm.variance_epsilon == lf.MLA_NORM_EPS == ref.MLA_NORM_EPS
    assert layer.input_layernorm[0].variance_epsilon == CFG.rms_norm_eps


def test_the_references_one_pass_is_the_published_codes(params, ids, theirs):
    got, chosen, _ = ref.forward(
        ref.Sizes.of(CFG), params, ids, list(CFG.held_experts), row_block=16)
    # float32 in another order of operations: 2e-5 of the logits' scale
    np.testing.assert_allclose(
        np.asarray(got), theirs, rtol=2e-4, atol=2e-5 * np.abs(theirs).max())
    assert np.any(np.asarray(chosen) >= CFG.n_routed_experts)  # identities were drawn


WRONG = [
    dict(rescale_q=False), dict(rescale_kv=False), dict(renormalise=True),
    dict(routed_scaling_factor=1.0), dict(identities=False), dict(branch_from_x=True),
    dict(branch_after_first=True), dict(rotate_halves=True)]


@pytest.mark.parametrize("wrong", WRONG, ids=[next(iter(w)) for w in WRONG])
def test_with_one_mechanism_wrong_it_is_not(params, ids, theirs, wrong):
    got, _, _ = ref.forward(
        ref.Sizes.of(CFG, **wrong), params, ids, list(CFG.held_experts), row_block=16)
    assert np.abs(np.asarray(got) - theirs).max() > 0.02 * np.abs(theirs).max()


def test_the_served_programs_are_the_published_codes(params, ids, theirs):
    """The prefill in parts and blocks, then four steps forced onto the
    ids the published pass read: logits after each."""
    prompt = TOKENS - 4
    prefill = lf.prefill(CFG, params, jnp.asarray(ids[:prompt]), cache_len=TOKENS)
    scale = np.abs(theirs).max()
    np.testing.assert_allclose(
        np.asarray(prefill.logits), theirs[prompt - 1], rtol=2e-4, atol=2e-5 * scale)
    cache = prefill.cache
    for at in range(prompt, TOKENS):
        logits, cache, _, _ = lf.decode_step(
            CFG, params, cache, jnp.int32(ids[at]), jnp.int32(at))
        np.testing.assert_allclose(np.asarray(logits), theirs[at], rtol=2e-4, atol=2e-5 * scale)
