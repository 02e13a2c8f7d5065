"""CPU rehearsals of the benchmark, run by hand (`python -m pytest
benchmark/tests -q`, about two minutes): they are not part of the repo's
tier-1 tests.  JAX is held to the CPU with four virtual devices, so that the
four-chip cell's mesh can be rehearsed."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
