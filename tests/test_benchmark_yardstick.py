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


def _adopt(filename: str) -> None:
    name = os.path.splitext(filename)[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{name}", os.path.join(_TESTS, filename)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for key, value in vars(module).items():
        if key.startswith("test_"):
            assert key not in globals(), f"two yardstick checks are named {key}"
            globals()[key] = value


for _filename in sorted(os.listdir(_TESTS)):
    if _filename.startswith("test_") and _filename.endswith(".py"):
        _adopt(_filename)
