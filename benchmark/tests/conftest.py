"""Run by hand (`python -m pytest benchmark/tests -q`), on the CPU: the
driver's tier-1 command collects `tests/` only."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)
