"""Tests of the benchmark itself, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The CPU gets four virtual devices, so that the node-sharded cell runs its
mesh and halo exchange here. They skip the harness's look for a chip and
drive the rest of a run."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
