"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
(not part of the repository's tier-1 suite, which collects ``tests/``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
