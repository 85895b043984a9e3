"""The benchmark's own tests run on the CPU, with four virtual devices
for the four-worker cell: ``python -m pytest bench/tests`` from the
root of the repository."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
