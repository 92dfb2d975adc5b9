"""Driver kind `open_loop`: one `ServingEngine`, arrivals on the
generator's schedule at the cell's fixed rate, whatever the engine does
(`harness/serve_loop.py`, which reads the kind from `cell["driver"]`)."""
from benchmarks.harness.serve_loop import run  # noqa: F401
