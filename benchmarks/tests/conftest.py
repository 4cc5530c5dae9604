"""The benchmark's self-check: run by hand with ``pytest benchmarks/tests``.

It is outside the repo's tier-1 tests on purpose (``pytest.ini`` collects
``tests/`` only): it checks the yardstick, not the program. Everything here
runs on the CPU.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
