"""The benchmark's own checks: ``python -m pytest benchmark/checks -q`` from
the root of the checkout, on the CPU. Not part of the repository's tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
