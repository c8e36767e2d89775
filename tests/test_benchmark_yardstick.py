"""The yardstick's own checks, collected by the tier-1 command.

`benchmark/tests/` guards the code every PR is judged by (`stats.py`,
`loadgen.py`, `xplane.py`, the span readers, the FLUX counts), but the
driver's command is `pytest tests/`, which never reached it. This file
loads those three modules by path and hands pytest their test functions,
parametrisation included, so each check still counts as one test. The
files under `benchmark/` are not edited; they put `benchmark/` first on
`sys.path` and import `client`, `stats`, `run`, `spans`, `reduce`, ... as
top-level names, which nothing else under `tests/`, `scripts/` or the
package uses.
"""

import importlib.util
import os

_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests"
)


# Two checks of PR 36 pinned the manifest and the `lm_work` directory as
# that PR left them (its twelve metrics the manifest's last, two models'
# work files), and a PR may append to both but may not edit the file that
# holds them. `benchmark/tests/test_solar_readers.py` has each in the form
# that holds afterwards (PR 38); those are adopted, these are not.
#
# PR 41 appended a cell and a fourth model in the same way, and two checks
# of `test_solar_readers.py` had pinned PR 38's state (Solar's cell the last
# of each list, three `lm_work` files; the second is the form adopted
# above, so it is set aside in its turn): `test_k_exaone_readers.py` has
# what holds afterwards.
#
# PR 45 appended a fifth model's cell to the lists that check had pinned
# as PR 41 left them (K-EXAONE's cell the last of each): its form that
# holds whoever came last is in `test_ling_flash_readers.py`.
#
# PR 48 appended a sixth model's cell and two metrics, and that form had
# in its turn pinned PR 45's state (Ling's cell and its two metrics the last
# of each list): `test_nemotron3_nano_readers.py` has the one that holds the
# order the PRs came in and no one's place at the end.
#
# PR 49 holds Nemotron-3-Nano's weights as a tree a published block, where
# `test_nemotron3_nano_readers.py` looks the attention block up as the third
# *segment* of the tree PR 48 held (a run of pairs one entry). Its form that
# finds each kind by its published index is below, in this file: a PR that
# claims a gain adds nothing under `benchmark/`.
#
# PR 52 appended a seventh model's cell to three lists that form had held
# to be what PR 48 found (the two drafting metrics' and latent attention's):
# `test_glm_dsa_readers.py` has the one that holds what each list began
# with and lets later cells follow.
#
# PR 54 appended an eighth model's cell to a list that form had held to be
# what PR 52 found (`ssm_device_pct.lm` listing Nemotron-3-Nano's cell
# alone): `test_granite_hybrid_readers.py` has the one that holds every
# list from its start and none to its end.
#
# PR 58 appended a ninth model's cell to a list that form had held to be
# what PR 54 found (`attn_device_pct.lm` listing Nemotron-3-Nano's and
# granite's cells alone): `test_sdar_readers.py` has the one that holds no
# list to its end anywhere.
#
# PR 61 appended a second model with learned sparse attention to the two
# DSA kernels' lists, which `test_dsa_attend_readers.py` and
# `test_dsa_select_readers.py` had each held to be GLM-5.2's cell alone:
# `test_dots3_readers.py` has one check for both that holds each list from
# its start.
_LISTED = ("test_the_lm_cells_are_listed_where_their_readers_find_something_no_list_held_to_"
           "its_end")
_MODULES = {}
_DSA_LISTED = "test_a_dsa_kernels_metric_keeps_its_place_and_lists_the_glm_cell_first"
_SUPERSEDED = {
    "test_the_metric_is_the_manifests_last_and_lists_the_glm_cell": _DSA_LISTED,
    "test_the_selection_metric_follows_the_attention_kernels_and_lists_the_glm_cell_alone":
        _DSA_LISTED,
    "test_the_sizes_the_nemotron3_nano_counts_read_are_the_registrys":
        "test_the_sizes_the_nemotron3_nano_counts_read_are_the_registrys_a_tree_a_block",
    "test_the_lm_cells_are_listed_where_their_readers_find_something_whoever_came_last": _LISTED,
    "test_the_lm_cells_are_listed_where_their_readers_find_something_in_the_order_they_came":
        _LISTED,
    "test_device_every_new_metric_has_its_reader_and_names_its_cells":
        "test_device_the_twelve_metrics_of_pr_36_have_their_readers_and_lie_together",
    "test_device_the_lm_readers_find_a_models_work_by_the_checkpoint_the_workflow_loads":
        "test_device_every_lm_work_file_is_found_by_its_registry_name_however_many",
    "test_device_every_configuration_with_an_lm_work_file_is_found_by_its_registry_name":
        "test_device_every_lm_work_file_is_found_by_its_registry_name_however_many",
    "test_the_solar_cell_is_listed_where_its_readers_find_something": _LISTED,
    "test_the_lm_cells_are_listed_where_their_readers_find_something": _LISTED,
    "test_the_lm_cells_are_listed_where_their_readers_find_something_each_after_those_before":
        _LISTED,
    "test_the_lm_cells_are_listed_where_their_readers_find_something_each_list_from_its_start":
        _LISTED,
}


def _adopt(filename: str) -> None:
    name = os.path.splitext(filename)[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{name}", os.path.join(_TESTS, filename)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _MODULES[name] = module
    for key, value in vars(module).items():
        if key.startswith("test_") and key not in _SUPERSEDED:
            assert key not in globals(), f"two yardstick checks are named {key}"
            globals()[key] = value


for _filename in sorted(os.listdir(_TESTS)):
    if _filename.startswith("test_") and _filename.endswith(".py"):
        _adopt(_filename)


def test_the_sizes_the_nemotron3_nano_counts_read_are_the_registrys_a_tree_a_block():
    """What the set-aside check asserts, the blocks found by published
    index (0 a Mamba block, 5 the first attention block, 51 the trailing
    sparse block)."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, nemotron_h
    from comfyui_distributed_tpu.models.registry import create_model

    readers = _MODULES["test_nemotron3_nano_readers"]
    config, counts = readers.CONFIG, readers.counts
    model = get_config(config["registry_name"])
    assert nemotron_h.param_count(model) == counts.total_params(config)
    assert (len(model.blocks_of("M")), len(model.blocks_of("E")), len(model.blocks_of("*"))) == (
        counts.blocks(config))
    assert model.hybrid_override_pattern == config["hybrid_override_pattern"]
    assert (model.mamba_inner, model.conv_channels, model.chunk_size) == (
        counts.mamba_inner(config), counts.conv_channels(config), config["chunk_size"])
    blocks = nemotron_h.param_shapes(model)["blocks"]
    assert len(blocks) == 52
    assert nemotron_h.count_params(blocks[0]["mamba"]) == counts.mamba_params(config)
    assert nemotron_h.count_params(blocks[5]["attn"]) == counts.attention_params(config)
    last = blocks[51]["moe"]
    assert nemotron_h.count_params(last["experts"]) == 8 * counts.expert_params(config)
    assert nemotron_h.count_params({"r": last["w_g"], "s": last["shared"]}) == (
        counts.always_params(config))
    lm = create_model(config["registry_name"])
    lm.dtype = jnp.dtype(config["as_run"]["weights_dtype"])
    described = lm.describe(8704)
    assert described["cache_bytes"] == counts.cache_bytes(config, 8704)
    assert described["state_bytes"] == counts.state_bytes(config)
    assert (described["mamba_layers"], described["sparse_layers"], described["attention_layers"],
            described["layers"]) == (23, 23, 6, 52)


for _old, _new in _SUPERSEDED.items():
    assert _new in globals(), f"{_old} was set aside and {_new} is not there"
