"""The fleet facade.

Parity: reference `python/paddle/distributed/fleet/fleet.py:218,674`
(fleet.init -> hybrid env; distributed_model; distributed_optimizer) and
`fleet/model.py:32,134-153` (wrapper selection by degrees).
"""
from __future__ import annotations

from typing import Optional

import jax

from ...core.tensor import Tensor
from ..env import get_rank, get_world_size, init_parallel_env
from .distributed_strategy import DistributedStrategy
from .topology import CommunicateTopology, HybridCommunicateGroup

__all__ = ["init", "is_initialized", "distributed_model",
           "distributed_optimizer", "get_hybrid_communicate_group",
           "fleet"]

_strategy: Optional[DistributedStrategy] = None
_hcg: Optional[HybridCommunicateGroup] = None


def init(role_maker=None, is_collective=True, strategy=None, log_level="INFO"):
    """Parity: fleet.init. Builds the hybrid topology over jax devices."""
    global _strategy, _hcg
    init_parallel_env()
    _strategy = strategy or DistributedStrategy()
    h = _strategy.hybrid_configs
    topo = CommunicateTopology(
        ["data", "pipe", "sharding", "sep", "model"],
        [h["dp_degree"], h["pp_degree"], h["sharding_degree"],
         h["sep_degree"], h["mp_degree"]])
    _hcg = HybridCommunicateGroup(topo, rank=get_rank())
    return fleet


def is_initialized():
    return _hcg is not None


def get_hybrid_communicate_group() -> HybridCommunicateGroup:
    if _hcg is None:
        init()
    return _hcg


def _ensure_init():
    if _hcg is None:
        init()


def distributed_model(model):
    """Parity: fleet.distributed_model (fleet/model.py:32): wrap by degrees."""
    _ensure_init()
    from ..parallel import DataParallel
    from .meta_parallel import (PipelineParallel, ShardingParallel,
                                TensorParallel)
    from .pp_layers import PipelineLayer
    if _hcg.get_pipe_parallel_world_size() > 1:
        if not isinstance(model, PipelineLayer):
            raise TypeError("pipeline parallel requires a PipelineLayer model "
                            "(parity: reference fleet/model.py:118)")
        return PipelineParallel(model, _hcg, _strategy)
    if _hcg.get_model_parallel_world_size() > 1:
        return TensorParallel(model, _hcg, _strategy)
    if _hcg.get_sharding_parallel_world_size() > 1:
        return ShardingParallel(model, _hcg, _strategy)
    return DataParallel(model)


def distributed_optimizer(optimizer, strategy=None):
    """Parity: fleet.distributed_optimizer -> HybridParallelOptimizer.
    An explicit strategy argument overrides the fleet.init one (the
    reference accepts either call pattern)."""
    _ensure_init()
    from .hybrid_parallel_optimizer import HybridParallelOptimizer
    if strategy is not None and _strategy is not None:
        a = getattr(strategy, "hybrid_configs", None)
        b = getattr(_strategy, "hybrid_configs", None)
        if a and b and dict(a) != dict(b):
            raise ValueError(
                "distributed_optimizer strategy.hybrid_configs "
                f"{a} differ from the fleet.init topology {b}; the comm "
                "groups were built at init — re-run fleet.init with the "
                "new topology instead")
    return HybridParallelOptimizer(optimizer, _hcg,
                                   strategy if strategy is not None
                                   else _strategy)


def collective_perf(comm_type="allreduce", round=5, size_and_time=None):
    """Parity: fleet.collective_perf (fleet.py:632) — micro-bench of a
    collective over the live mesh (or a no-op report on one device)."""
    import time
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _hcg.mesh if _hcg else None
    results = {}
    sizes = list((size_and_time or {1 << 20: None}).keys())
    for size in sizes:
        n = size // 4
        x = jnp.ones((max(n, 8),), jnp.float32)
        if mesh is not None and mesh.devices.size > 1:
            from jax import shard_map
            f = jax.jit(shard_map(lambda a: jax.lax.psum(a, "data"),
                                  mesh=mesh,
                                  in_specs=P("data"), out_specs=P()))
            xs = jax.device_put(
                jnp.ones((mesh.shape["data"] * max(n // 8, 8),), jnp.float32),
                NamedSharding(mesh, P("data")))
            f(xs).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(round):
                f(xs).block_until_ready()
            dt = (time.perf_counter() - t0) / round
        else:
            t0 = time.perf_counter()
            for _ in range(round):
                (x + 1).block_until_ready()
            dt = (time.perf_counter() - t0) / round
        results[size] = dt
        print(f"[collective_perf] {comm_type} size={size}B "
              f"avg={dt*1e6:.1f}us")
    return results


class UtilBase:
    """Parity: fleet.UtilBase (base/util_factory.py) — cross-worker
    utility helpers riding the collective layer + local FS."""

    def all_reduce(self, input, mode="sum", comm_world="worker"):
        import numpy as np
        import jax.numpy as jnp
        from ..collective import ReduceOp, all_reduce as _ar
        from ...core.tensor import Tensor
        t = input if isinstance(input, Tensor) else Tensor(jnp.asarray(
            np.asarray(input)))
        op = {"sum": ReduceOp.SUM, "max": ReduceOp.MAX,
              "min": ReduceOp.MIN}[mode]
        _ar(t, op=op)
        return np.asarray(t._data)

    def barrier(self, comm_world="worker"):
        from ..collective import barrier as _b
        _b()

    def all_gather(self, input, comm_world="worker"):
        out = []
        import numpy as np
        import jax.numpy as jnp
        from ..collective import all_gather as _ag
        from ...core.tensor import Tensor
        _ag(out, Tensor(jnp.asarray(np.asarray(input))))
        return [np.asarray(t._data) for t in out]

    def get_file_shard(self, files):
        """Split a file list evenly over workers (reference contract:
        earlier workers take the remainder)."""
        from ..env import get_rank, get_world_size
        n, rank = max(get_world_size(), 1), get_rank()
        per, rem = divmod(len(files), n)
        start = rank * per + min(rank, rem)
        return files[start:start + per + (1 if rank < rem else 0)]

    def print_on_rank(self, message, rank_id=0):
        from ..env import get_rank
        if get_rank() == rank_id:
            print(message)


util = UtilBase()


class _FleetNamespace:
    """`fleet` object surface (so `from paddle_tpu.distributed import fleet`
    followed by fleet.init(...) works like the reference)."""
    init = staticmethod(init)
    is_initialized = staticmethod(is_initialized)
    distributed_model = staticmethod(distributed_model)
    distributed_optimizer = staticmethod(distributed_optimizer)
    get_hybrid_communicate_group = staticmethod(get_hybrid_communicate_group)
    collective_perf = staticmethod(collective_perf)
    DistributedStrategy = DistributedStrategy

    @property
    def worker_num(self):
        return get_world_size()

    @property
    def worker_index(self):
        return get_rank()

    @property
    def util(self):
        return util


# reference exports the class as fleet.Fleet (fleet.py:218)
Fleet = _FleetNamespace

fleet = _FleetNamespace()
