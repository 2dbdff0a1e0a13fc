"""Puts the benchmark's own modules and the program's ``src`` on the path.
Imported first by every test file here (not a ``conftest.py``: the repo's
``tests/`` import names from their own ``conftest`` module)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
