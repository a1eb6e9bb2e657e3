"""chipbench's own tests: ``python -m pytest chipbench/tests -q`` from
the repo's root. CPU only, outside tier-1."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
