"""The benchmark's own tests, run apart from the repo's suite:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
