"""Imported first by every test file here: puts the repo root on the path
so that ``benchmarks`` imports.  (Not a ``conftest.py``: the suite's own
tests import names from THE conftest by module name.)"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
