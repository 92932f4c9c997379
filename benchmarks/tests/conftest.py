"""The benchmark's own tests: run by hand (``python -m pytest benchmarks/tests -q``),
on the CPU, at sizes a test run can hold.  Not part of the repo's tier-1 run."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
