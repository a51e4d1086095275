"""The benchmark's own tests run on the CPU at reduced sizes:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
