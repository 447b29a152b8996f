"""CPU rehearsal of the benchmark. Run by hand, never by the driver:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider

Nothing here is a measurement: the sizes are toys and the device is a
CPU that the tests admit by patching the harness's device check from
inside the test (the command line has no switch that does).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# No persistent compile cache: a rehearsal must not load another run's
# programs, nor leave any behind.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
