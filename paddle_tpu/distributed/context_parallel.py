"""Context parallelism — long-sequence attention over the `sep` mesh axis.

Capability-parity-plus (SURVEY.md §5): the reference's long-context story is
Megatron-SP (`fleet/utils/sequence_parallel_utils.py`) plus the `sep`
topology axis (`fleet/base/topology.py:70-90`); ring attention lives outside
its core. Here both ring (ppermute K/V rotation) and Ulysses (all_to_all
head/seq swap) are first-class, built on the Pallas flash kernel.

Two entry levels:
  * `ring_attention` / `ulysses_attention` (re-exported from
    paddle_tpu.kernels.ring_attention) — call INSIDE shard_map on local
    shards;
  * `context_parallel_attention` — takes global jax.Arrays sequence-sharded
    over `sep` on an ambient mesh and wraps the shard_map for you.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels.ring_attention import ring_flash_attention, ulysses_attention

__all__ = ["ring_attention", "ulysses_attention",
           "context_parallel_attention"]

ring_attention = ring_flash_attention


def context_parallel_attention(q, k, v, mesh=None, axis_name="sep",
                               causal=True, mode="ring", sm_scale=None):
    """Attention over (B, S, H, D) arrays whose sequence dim is sharded on
    `axis_name`. mode: "ring" (ppermute ring flash) or "ulysses"
    (all_to_all head swap). Returns an array with the same sharding.
    """
    if mesh is None:
        sh = getattr(q, "sharding", None)
        mesh = getattr(sh, "mesh", None)
    if mesh is None:
        # under jit tracing: the aval carries the AbstractMesh
        aval = getattr(q, "aval", None)
        sh = getattr(aval, "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is not None and getattr(mesh, "empty", False):
            mesh = None
    if mesh is None:
        raise ValueError("inputs carry no mesh; pass mesh= explicitly")
    if mode == "ring":
        inner = lambda a, b, c: ring_flash_attention(
            a, b, c, axis_name, causal=causal, sm_scale=sm_scale)
    elif mode == "ulysses":
        inner = lambda a, b, c: ulysses_attention(
            a, b, c, axis_name, causal=causal, sm_scale=sm_scale)
    else:
        raise ValueError(f"unknown context-parallel mode {mode!r}")
    spec = P(None, axis_name)
    return shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
