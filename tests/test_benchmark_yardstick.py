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
_LISTED = ("test_the_lm_cells_are_listed_where_their_readers_find_something_in_the_order_"
           "they_came")
_SUPERSEDED = {
    "test_the_lm_cells_are_listed_where_their_readers_find_something_whoever_came_last": _LISTED,
    "test_device_every_new_metric_has_its_reader_and_names_its_cells":
        "test_device_the_twelve_metrics_of_pr_36_have_their_readers_and_lie_together",
    "test_device_the_lm_readers_find_a_models_work_by_the_checkpoint_the_workflow_loads":
        "test_device_every_lm_work_file_is_found_by_its_registry_name_however_many",
    "test_device_every_configuration_with_an_lm_work_file_is_found_by_its_registry_name":
        "test_device_every_lm_work_file_is_found_by_its_registry_name_however_many",
    "test_the_solar_cell_is_listed_where_its_readers_find_something": _LISTED,
    "test_the_lm_cells_are_listed_where_their_readers_find_something": _LISTED,
}


def _adopt(filename: str) -> None:
    name = os.path.splitext(filename)[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{name}", os.path.join(_TESTS, filename)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for key, value in vars(module).items():
        if key.startswith("test_") and key not in _SUPERSEDED:
            assert key not in globals(), f"two yardstick checks are named {key}"
            globals()[key] = value


for _filename in sorted(os.listdir(_TESTS)):
    if _filename.startswith("test_") and _filename.endswith(".py"):
        _adopt(_filename)

for _old, _new in _SUPERSEDED.items():
    assert _new in globals(), f"{_old} was set aside and {_new} is not there"
