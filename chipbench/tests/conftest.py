"""CPU tests of the harness's pure parts and of a whole run at a small
size.  Run from the root of the checkout:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They are not among the repository's tier-1 tests (``testpaths``).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
