"""``benchmark/tests/test_manifest.py``'s cases, collected by the tier-1
command.

That file holds ``BENCHMARK.json`` to the files it names (a reader for
every entry, cells for every ``workloads`` list, the model's own reader
behind every shared metric) and imports no JAX, but the tier-1 command
collects ``tests/`` and not ``benchmark/tests/``: an entry a PR adds
was guarded only when someone ran the benchmark's own tests. The cases
are the module's own functions, parametrised where it parametrises
them, under this module's name; nothing is copied, so the two cannot
drift.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.lib import common  # noqa: E402

_manifest = common.load_module(
    ROOT / "benchmark" / "tests" / "test_manifest.py",
    "benchmark_tests_manifest")
globals().update({name: case for name, case in vars(_manifest).items()
                  if name.startswith("test_")})
