"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are rehearsals 1 and 2 of the on-chip-measurement guide, and they
hold the two checks the contract asks to keep: the control must come out
as not correct, and a broken timed path must too.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
