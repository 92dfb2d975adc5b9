"""A bounded `while ...: eng.step()` for the serving tests.

A scheduler change can make the state a test waits for unreachable; an
unbounded loop then spins in `ServingEngine.step` until the whole
suite's time limit cuts the run. Every such wait goes through here (or
carries its own cap) so it fails its own test instead.
"""

STEP_CAP = 500


def step_until(eng, done, cap=STEP_CAP):
    """Step `eng` until `done()` holds; returns the steps taken."""
    steps = 0
    while not done():
        assert steps < cap, f"engine state not reached in {cap} steps"
        eng.step()
        steps += 1
    return steps


def drain(eng, cap=STEP_CAP):
    """Step `eng` until it has no work left."""
    return step_until(eng, lambda: not eng.has_work(), cap)
