"""The benchmark's own tests: run with ``python -m pytest bench/tests``
from the root of a checkout (on the CPU; nothing here needs a chip)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
