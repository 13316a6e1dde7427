"""CPU tests of the chip benchmark: four emulated host devices, Pallas in
interpret mode, tiny sizes.  Run them with

    python -m pytest chipbench/tests

from the checkout's root.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
