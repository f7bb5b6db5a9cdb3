"""Tests of the benchmark itself, on the CPU at test sizes:

    python -m pytest chipbench/tests

Four virtual CPU devices stand in for a four-chip host."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import env  # noqa: E402

env.prepare()
