"""Driver kind `train_loop`: `paddle.jit.to_static(step, state_objects=
[model, opt])` over the family's model, a fresh Zipf batch every step
(`harness/train_loop.py`)."""
from benchmarks.harness.train_loop import run  # noqa: F401
