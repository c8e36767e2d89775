"""The repo's float32 reference of SDAR's backbone against the backbone
family's published modelling code, where it is installed: a tiny
`Qwen3MoeForCausalLM` (eager attention, `decoder_sparse_step` 1,
`norm_topk_prob` true) with the same seeded weights copied across and the
block mask handed in as a 4-D additive `attention_mask`. It holds the
backbone (QK norm before rotation, `rotate_half`, softmax then the k
largest then renormalised, no shared expert, no shift of logits against
positions), not the generation procedure, which `test_sdar_model.py`
holds against the reference's own `generate`.

One file, so one xdist worker pays the import of torch and transformers."""

import dataclasses
import os

import jax
import numpy as np
import pytest

os.environ.setdefault("USE_TF", "0")  # transformers would import TensorFlow beside torch
torch = pytest.importorskip("torch")
qwen3_moe = pytest.importorskip("transformers.models.qwen3_moe")

from comfyui_distributed_tpu.models import sdar  # noqa: E402
from comfyui_distributed_tpu.reference import sdar as ref  # noqa: E402

CFG = sdar.SdarConfig(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    vocab_size=512, mask_token_id=511)
SIZES = ref.Sizes.of(CFG)
TOKENS = 24


@pytest.fixture(scope="module")
def params():
    """Seeded weights; every norm's scale drawn too, so that a norm that
    is left out, or applied after the rotation, shows."""
    tree = sdar.init_params(CFG, jax.random.key(2))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    leaves = [
        jax.random.uniform(jax.random.fold_in(jax.random.key(3), i), leaf.shape, minval=0.5,
                           maxval=1.5) if leaf.ndim == 1 else leaf
        for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def published(params):
    config = qwen3_moe.Qwen3MoeConfig(
        vocab_size=CFG.vocab_size, hidden_size=CFG.hidden_size, intermediate_size=96,
        num_hidden_layers=CFG.num_hidden_layers, num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads, head_dim=CFG.head_dim, hidden_act="silu",
        max_position_embeddings=128, rms_norm_eps=CFG.rms_norm_eps, rope_theta=CFG.rope_theta,
        attention_bias=False, use_sliding_window=False, sliding_window=None,
        decoder_sparse_step=1, moe_intermediate_size=CFG.moe_intermediate_size,
        num_experts_per_tok=CFG.num_experts_per_tok, num_experts=CFG.num_experts,
        norm_topk_prob=True, mlp_only_layers=[], tie_word_embeddings=False,
        attn_implementation="eager")
    model = qwen3_moe.Qwen3MoeForCausalLM(config).to(torch.float32).eval()
    width = CFG.moe_intermediate_size

    def t(array):  # ours are stored in by out, a torch Linear's weight out by in
        return torch.from_numpy(np.asarray(array, np.float32).T.copy())

    def v(array):
        return torch.from_numpy(np.asarray(array, np.float32).copy())

    state = {"model.embed_tokens.weight": v(params["embed"]),
             "model.norm.weight": v(params["final_norm"]), "lm_head.weight": t(params["head"])}
    for index, block in enumerate(params["layers"]):
        at, attn, moe = f"model.layers.{index}.", block["attn"], block["moe"]
        state[at + "input_layernorm.weight"] = v(block["attn_norm"])
        state[at + "post_attention_layernorm.weight"] = v(block["moe_norm"])
        for theirs, mine in (("q_proj", "w_q"), ("k_proj", "w_k"), ("v_proj", "w_v"),
                             ("o_proj", "w_o")):
            state[at + f"self_attn.{theirs}.weight"] = t(attn[mine])
        state[at + "self_attn.q_norm.weight"] = v(attn["q_norm"])
        state[at + "self_attn.k_norm.weight"] = v(attn["k_norm"])
        state[at + "mlp.gate.weight"] = t(moe["w_g"])
        for expert in range(CFG.num_experts):
            gate_up = moe["experts"]["w_gate_up"][expert]
            mlp = at + f"mlp.experts.{expert}."
            state[mlp + "gate_proj.weight"] = t(gate_up[:, :width])
            state[mlp + "up_proj.weight"] = t(gate_up[:, width:])
            state[mlp + "down_proj.weight"] = t(moe["experts"]["w_down"][expert])
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in name for name in missing), (missing, unexpected)
    return model


def published_logits(model, ids, seen):
    """The published forward under `seen` [T, T] as a 4-D additive mask."""
    additive = torch.zeros(seen.shape, dtype=torch.float32).masked_fill(
        ~torch.from_numpy(np.array(seen)), torch.finfo(torch.float32).min)
    with torch.no_grad():
        out = model(input_ids=torch.from_numpy(np.asarray(ids, np.int64))[None],
                    attention_mask=additive[None, None], use_cache=False)
    return out.logits[0].numpy()


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def test_the_references_logits_are_the_published_backbones_under_the_block_mask(
        params, published):
    ids = np.asarray(jax.random.randint(jax.random.key(4), (TOKENS,), 0, CFG.vocab_size))
    mine, _, _ = ref.forward(SIZES, params, ids)
    theirs = published_logits(published, ids, ref.seen_mask(SIZES, TOKENS))
    # float32 on both sides: the order of the sums alone differs
    assert rel_l2(mine, theirs).max() < 2e-5
    # under the plain causal mask the published model says something else ...
    causal = published_logits(published, ids, np.tril(np.ones((TOKENS, TOKENS), bool)))
    assert rel_l2(mine, causal).max() > 1e-2
    # ... which is what the reference says with its block mask off
    plain, _, _ = ref.forward(dataclasses.replace(SIZES, block_mask=False), params, ids)
    assert rel_l2(plain, causal).max() < 2e-5


@pytest.mark.parametrize("wrong", [
    {"norm_then_rotate": False}, {"norm_topk_prob": False}, {"score_scale": 1.0}],
    ids=["rotation_before_norm", "weights_not_renormalised", "scale_one"])
def test_the_published_backbone_tells_each_wrong_mechanism_apart(params, published, wrong):
    ids = np.asarray(jax.random.randint(jax.random.key(5), (TOKENS,), 0, CFG.vocab_size))
    theirs = published_logits(published, ids, ref.seen_mask(SIZES, TOKENS))
    other, _, _ = ref.forward(dataclasses.replace(SIZES, **wrong), params, ids)
    assert rel_l2(other, theirs).max() > 1e-2
