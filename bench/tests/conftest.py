"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
checkout's root.  They run on the CPU at small sizes; the cells' real sizes
run only through ``bench/run.py`` on the chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
