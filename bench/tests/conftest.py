"""The benchmark's own tests: the yardstick's arithmetic, the trace
reduction on a trace recorded on a TPU v5e, the per-layer readers, the
reference against the program, and the comparison that decides
``correct`` against its control and against a broken timed path.  They
run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
