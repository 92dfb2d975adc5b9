"""The three lookups by name. Nothing in the harness names a model: what
belongs to one model family, one driver kind or one work count is a file
of its own, found here by the name a data file gives, so that a later PR
adds a NEW file and edits none that is there.

  family(cfg)          `families/<cfg["family"]>.py`: what the drivers need
                       of a model (README: the list); a configuration file
                       without the key is of DEFAULT_FAMILY
  driver(cell)         `drivers/<cell["driver"]>.py`, exposing `run(cfg,
                       cell, seed=, seconds=, cache=, phases=, tracer=,
                       spans=)`
  work(cfg, name)      a work count, the module-level function
                       `name(cfg, cell, values)`: in the
                       configuration's family first, in the shared
                       `harness/work.py` (what no model shapes) second,
                       else None, and the metric is left out of the line.
                       Never another family's count.
"""
from __future__ import annotations

import importlib
import os
import re

from .common import BENCH_DIR

DEFAULT_FAMILY = "llama"       # the one place the name is written
_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_]*$")


def _module(kind: str, name):
    """The module `benchmarks/<kind>/<name>.py`, or a LookupError that
    names the directory and what it holds."""
    directory = os.path.join(BENCH_DIR, kind)
    if isinstance(name, str) and _NAME.match(name) \
            and os.path.isfile(os.path.join(directory, f"{name}.py")):
        return importlib.import_module(f"benchmarks.{kind}.{name}")
    have = sorted(f[:-3] for f in os.listdir(directory)
                  if f.endswith(".py") and not f.startswith("_"))
    raise LookupError(f"no {name!r} under benchmarks/{kind}/ (it holds "
                      f"{have}): add {kind}/{name}.py, see "
                      f"benchmarks/README.md")


def family(cfg: dict):
    return _module("families", cfg.get("family", DEFAULT_FAMILY))


def driver(cell: dict):
    return _module("drivers", cell["driver"])


def work(cfg: dict, name: str):
    from . import work as shared
    for home in (family(cfg), shared):
        fn = getattr(home, name, None)
        if callable(fn):
            return fn
    return None
