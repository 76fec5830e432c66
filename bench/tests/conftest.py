"""The benchmark's own tests run on the CPU: they check the harness, the
trace reduction and the comparison that decides ``correct``, never a
speed."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
