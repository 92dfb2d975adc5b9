"""The benchmark's own tests run on the CPU, with four virtual devices for
the mesh rehearsal. They live with the benchmark, not under tests/:
`python -m pytest benchmarks/tests -q`."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
