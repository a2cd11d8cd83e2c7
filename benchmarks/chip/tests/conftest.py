"""The harness's tests run on the CPU, at tiny sizes, in seconds to
minutes; nothing here needs a chip."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE), str(HERE / "metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)
