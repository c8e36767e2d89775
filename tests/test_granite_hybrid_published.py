"""The repo's float32 reference of Granite 4.0-H against the family's
published modelling code, where that is installed
(`transformers.models.granitemoehybrid`, its torch path on the CPU): a
tiny `GraniteMoeHybridConfig` without experts, the same seeded weights
copied across, the logits of one sequence. What
`reference/granite_hybrid.py` says of the layer (the order of `in_proj`'s
columns, the gate before the norm over all channels, the scale that is
the multiplier, no rotation, where the four scalars stand) is held here
to the code the checkpoints are run with. One file: one xdist worker
pays the import of torch."""

import os

import numpy as np
import pytest

# transformers would import TensorFlow and flax where they are installed (30 s here)
os.environ.setdefault("USE_TF", "0")
os.environ.setdefault("USE_FLAX", "0")
torch = pytest.importorskip("torch")
published = pytest.importorskip("transformers.models.granitemoehybrid")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from comfyui_distributed_tpu.models import granite_hybrid as gh  # noqa: E402
from comfyui_distributed_tpu.reference import granite_hybrid as ref  # noqa: E402

LAYER_TYPES = ("mamba", "attention", "mamba", "mamba")
# hidden 64 under `mamba_expand` 2: 4 Mamba-2 heads of 32
OURS = gh.GraniteHybridConfig(
    hidden_size=64, layer_types=LAYER_TYPES, mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
    mamba_chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, vocab_size=512, prefill_part=16)
TOKENS = 37


def theirs():
    config = published.GraniteMoeHybridConfig(
        vocab_size=OURS.vocab_size, hidden_size=OURS.hidden_size, intermediate_size=96,
        num_hidden_layers=len(LAYER_TYPES), num_attention_heads=OURS.num_attention_heads,
        num_key_value_heads=OURS.num_key_value_heads, max_position_embeddings=128,
        rms_norm_eps=OURS.rms_norm_eps, tie_word_embeddings=True,
        embedding_multiplier=OURS.embedding_multiplier, logits_scaling=OURS.logits_scaling,
        residual_multiplier=OURS.residual_multiplier,
        attention_multiplier=OURS.attention_multiplier, num_local_experts=0,
        num_experts_per_tok=0, shared_intermediate_size=OURS.shared_intermediate_size,
        position_embedding_type="nope", layer_types=list(LAYER_TYPES),
        mamba_n_heads=OURS.mamba_n_heads, mamba_n_groups=OURS.mamba_n_groups,
        mamba_d_state=OURS.mamba_d_state, mamba_d_head=OURS.mamba_d_head,
        mamba_d_conv=OURS.mamba_d_conv, mamba_expand=2, mamba_chunk_size=OURS.mamba_chunk_size,
        mamba_conv_bias=True, mamba_proj_bias=False, attn_implementation="eager")
    return published.GraniteMoeHybridForCausalLM(config).eval()


def copy_across(model, params):
    """Our tree into the published module's parameters: a Linear's weight
    is [out, in]; `in_proj`'s columns are [z | xBC | dt]; `conv1d.weight`
    is [channels, 1, kernel]."""
    def tensor(a):
        return torch.from_numpy(np.array(a, np.float32))

    state = {"model.embed_tokens.weight": tensor(params["embed"]),
             "model.norm.weight": tensor(params["final_norm"])}
    for index, layer in enumerate(params["layers"]):
        at = f"model.layers.{index}."
        state[at + "input_layernorm.weight"] = tensor(layer["norm1"])
        state[at + "post_attention_layernorm.weight"] = tensor(layer["norm2"])
        state[at + "shared_mlp.input_linear.weight"] = tensor(layer["mlp"]["w_gate_up"]).T
        state[at + "shared_mlp.output_linear.weight"] = tensor(layer["mlp"]["w_down"]).T
        if "mamba" in layer:
            p = layer["mamba"]
            state[at + "mamba.in_proj.weight"] = tensor(
                jnp.concatenate([p["w_in"], p["w_dt"]], axis=1)).T
            state[at + "mamba.conv1d.weight"] = tensor(p["conv"]).T[:, None, :]
            state[at + "mamba.conv1d.bias"] = tensor(p["conv_bias"])
            state[at + "mamba.dt_bias"] = tensor(p["dt_bias"])
            state[at + "mamba.A_log"] = tensor(p["a_log"])
            state[at + "mamba.D"] = tensor(p["d"])
            state[at + "mamba.norm.weight"] = tensor(p["norm"])
            state[at + "mamba.out_proj.weight"] = tensor(p["w_out"]).T
        else:
            for ours, name in (("w_q", "q_proj"), ("w_k", "k_proj"), ("w_v", "v_proj"),
                               ("w_o", "o_proj")):
                state[at + f"self_attn.{name}.weight"] = tensor(layer["attn"][ours]).T
    state["lm_head.weight"] = state["model.embed_tokens.weight"]
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}, (missing, unexpected)


def test_the_reference_is_the_published_modelling_code_to_float32_rounding():
    params = gh.init_params(OURS, jax.random.key(11))
    # norm scales off one, so that where each stands shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)
    ids = np.array(jax.random.randint(jax.random.key(12), (TOKENS,), 0, OURS.vocab_size))
    model = theirs()
    copy_across(model, params)
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(ids)[None].long(), use_cache=False).logits[0]
    got, *_ = ref.forward(ref.Sizes.of(OURS), params, ids)
    want = want.numpy().astype(np.float64)
    error = np.linalg.norm(np.asarray(got, np.float64) - want, axis=-1) / np.linalg.norm(
        want, axis=-1)
    # float32 on both sides; the published code scans in chunks of 8, ours token by token
    assert error.max() < 2e-5, error.max()
    # and the system itself, prefilled in parts of 16
    logits = gh.prefill(OURS, params, jnp.asarray(ids), cache_len=TOKENS).logits
    assert np.linalg.norm(np.asarray(logits) - want[-1]) / np.linalg.norm(want[-1]) < 2e-5
