"""The benchmark's own tests; run by hand from the checkout's root:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import os
import sys

# the CPU, with as many devices as the largest cell asks for, before JAX
# is imported
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
