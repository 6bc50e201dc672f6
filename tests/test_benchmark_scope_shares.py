"""``benchmark/tests/test_scope_shares.py``'s cases, collected by the
tier-1 command, as ``tests/test_benchmark_manifest.py`` collects the
manifest's: the tier-1 command collects ``tests/`` and not
``benchmark/tests/``, and the readers of ``device_*_share`` (PR 52) are
guarded where every PR is. Nothing is copied, so the two cannot drift.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.lib import common  # noqa: E402

_cases = common.load_module(
    ROOT / "benchmark" / "tests" / "test_scope_shares.py",
    "benchmark_tests_scope_shares")
globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
