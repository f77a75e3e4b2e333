"""The benchmark's own tests run on the CPU: `python -m pytest benchmark/tests`.
They are not part of the program's tier-1 suite (`tests/`)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RAY_TPU_CHIPS", "none")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
