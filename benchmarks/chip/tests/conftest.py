"""The benchmark's self-tests run on the CPU, at sizes a test run can hold:
``python -m pytest benchmarks/chip/tests`` from the checkout's root."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[2] / "src"), str(HERE.parent), str(HERE)]
