"""The benchmark's own tests run on the CPU at tiny sizes:
``python -m pytest bench/tests -q`` from the root of the checkout."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
