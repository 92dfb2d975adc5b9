"""What the algorithm REQUIRES of the chip, from shapes alone: operations
and bytes. Kept with the benchmark so that no PR that claims a gain can
change the count. This file holds what no model shapes: the rules of
counting, the byte widths and the table of peaks. A model's own counts
are work functions of its family's file (`families/<family>.py`), which
`lookup.work` finds by the name a metric file gives: each takes the
configuration (the dict of a `configs/*.json`), the cell (the dict of a
`workloads/*.json`) and the run's `values`, and returns a number or a
dict of numbers. A work function that no model shapes would live here.

Counts: a matmul of an (m, k) by a (k, n) is 2mkn operations. Training
does forward + backward = 3 x the forward's matmul operations (6 per
parameter per token). CAUSAL attention needs half of the s x s score
matrix: forward QK^T and PV are 2 x 2 x (s^2/2) x h = 2 s^2 h a sequence a
layer, so 6 s^2 h with the backward, i.e. 6 s h a token (`bench.py`'s
`llama_step_flops` counts the unmasked 12). The embedding lookup,
norms, RoPE, softmax and any recomputation count nothing.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def chip_peaks(device_kind: str) -> dict:
    """{'flops': bf16 FLOP/s, 'bytes': HBM bytes/s} of one chip; raises
    for a device the table does not hold."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = str(device_kind).lower()
    for row in table:
        if row["match"] in kind:
            return {"flops": row["bf16_flops_per_s"],
                    "bytes": row["hbm_bytes_per_s"]}
    raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                     f"add it to peaks.json with its source")
