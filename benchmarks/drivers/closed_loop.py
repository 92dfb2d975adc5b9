"""Driver kind `closed_loop`: one `ServingEngine`, `clients` callers each
sending its next request when the last one finished
(`harness/serve_loop.py`, which reads the kind from `cell["driver"]`)."""
from benchmarks.harness.serve_loop import run  # noqa: F401
