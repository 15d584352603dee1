"""Device-agnostic collectives façade over XLA collectives.

TPU-native analog of ``deepspeed/comm/comm.py`` (module-level collectives
:222-520, ``timed_op`` profiling decorator :101, ``init_distributed`` :619)
and ``comm/torch.py``'s ``TorchBackend``.  There is no NCCL/process-group
layer: every collective is a ``jax.lax`` op inside a ``shard_map`` over a
named mesh axis; XLA routes it over ICI/DCN.

Two usage modes:

* **Inside a jitted step function** (the hot path): use the ``lax_*``
  re-exports directly (``lax_psum`` etc.) — these are zero-overhead aliases
  with named-scope annotations for profile readability.
* **Eager, engine/host level** (microbenchmarks, broadcast at init, barrier,
  metric reduction): the :class:`Collectives` object bound to a
  :class:`MeshTopology`, whose ops are profiled via ``comms_logger``
  exactly like the reference's ``timed_op``.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .comms_logging import comms_logger
from .mesh import MeshTopology
from ..utils.logging import logger


# --------------------------------------------------------------------------
# In-jit aliases (hot path)
# --------------------------------------------------------------------------

def lax_psum(x, axis_name):
    with jax.named_scope(f"all_reduce_{axis_name}"):
        return lax.psum(x, axis_name)


def lax_pmean(x, axis_name):
    with jax.named_scope(f"all_reduce_mean_{axis_name}"):
        return lax.pmean(x, axis_name)


def lax_all_gather(x, axis_name, axis: int = 0, tiled: bool = True):
    with jax.named_scope(f"all_gather_{axis_name}"):
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def lax_reduce_scatter(x, axis_name, scatter_dimension: int = 0):
    with jax.named_scope(f"reduce_scatter_{axis_name}"):
        return lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension, tiled=True)


def lax_all_to_all(x, axis_name, split_axis: int, concat_axis: int, tiled: bool = True):
    with jax.named_scope(f"all_to_all_{axis_name}"):
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def lax_ppermute(x, axis_name, perm):
    with jax.named_scope(f"ppermute_{axis_name}"):
        return lax.ppermute(x, axis_name, perm)


# --------------------------------------------------------------------------
# init_distributed
# --------------------------------------------------------------------------

_initialized = False


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host initialization (reference: comm/comm.py:619).

    On TPU pods this wraps ``jax.distributed.initialize``; single-process
    (one host, or CPU emulation) is a no-op.  Safe to call repeatedly.
    """
    global _initialized
    if _initialized:
        return
    import os

    explicit = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if explicit or os.environ.get("JAX_NUM_PROCESSES"):
        jax.distributed.initialize(
            coordinator_address=explicit,
            num_processes=num_processes or int(os.environ.get("JAX_NUM_PROCESSES", 0)) or None,
            process_id=process_id if process_id is not None
            else (int(os.environ["JAX_PROCESS_ID"]) if "JAX_PROCESS_ID" in os.environ else None),
        )
        logger.info("jax.distributed initialized: process %d/%d",
                    jax.process_index(), jax.process_count())
    _initialized = True


def get_world_size() -> int:
    return jax.device_count()


def get_rank() -> int:
    return jax.process_index()


# --------------------------------------------------------------------------
# Eager collectives over a mesh axis
# --------------------------------------------------------------------------

def _timed(op_name: str):
    """Profiling wrapper — the reference's ``timed_op`` (comm/comm.py:101)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self: "Collectives", x, *args, **kwargs):
            profile = comms_logger.should_profile(op_name)
            if profile:
                jax.block_until_ready(x)
                t0 = time.perf_counter()
            out = fn(self, x, *args, **kwargs)
            if profile:
                out = jax.block_until_ready(out)
                dt = time.perf_counter() - t0
                axis = kwargs.get("axis_name") or (args[0] if args else "data")
                n = self.topology.axis_sizes.get(axis, 1)
                size = x.size * x.dtype.itemsize
                comms_logger.append(op_name, kwargs.get("log_name", op_name),
                                    dt, size, n)
            return out

        return wrapper

    return deco


class Collectives:
    """Eager collectives bound to a mesh, for host-level orchestration and
    comm microbenchmarks.  Arrays are treated as sharded along dim 0 over
    ``axis_name`` (all_gather/reduce_scatter) or replicated (all_reduce).

    The jitted-executable cache is keyed by (op, axis, **shape/dtype**)
    and LRU-bounded (the serving ``_pstep_fns`` discipline): each key
    sees exactly one specialization, so evicting an entry really frees
    its executable — the unkeyed cache used to retain every shape ever
    reduced.  Fills and runtime retraces count through the PR-9
    compile-observatory counters (``training_comm_collective_*``) on
    ``metrics`` (an optional shared
    :class:`~deepspeed_tpu.telemetry.metrics.MetricsRegistry`; a
    private one is created when none is passed)."""

    _CACHE_CAP = 16

    def __init__(self, topology: MeshTopology, metrics=None):
        self.topology = topology
        self._cache = {}
        self._compiled_ever = set()
        if metrics is None:
            from ..telemetry.metrics import MetricsRegistry
            metrics = MetricsRegistry()
        self.metrics = metrics
        self._c_compiles = metrics.counter(
            "training_comm_collective_compiles_total",
            "eager-collective executables built (cache fills)",
            int_valued=True)
        self._c_retraces = metrics.counter(
            "training_comm_collective_retraces_total",
            "re-builds of an eager-collective key already compiled "
            "(LRU thrash across shapes/dtypes — each warns loudly)",
            int_valued=True)

    @property
    def mesh(self) -> Mesh:
        return self.topology.mesh

    def _sig(self, x) -> tuple:
        # key on dtype WITHOUT materializing x on device — jnp.asarray
        # here would pay a full H2D transfer per call just to read a
        # field, and the real transfer happens inside the jitted op
        dt = getattr(x, "dtype", None)
        return (tuple(np.shape(x)),
                str(dt if dt is not None else jnp.result_type(x)))

    def _jit(self, key, build):
        fn = self._cache.pop(key, None)
        if fn is None:
            if len(self._cache) >= self._CACHE_CAP:
                self._cache.pop(next(iter(self._cache)))
            fn = build()
            self._c_compiles.inc()
            if key in self._compiled_ever:
                self._c_retraces.inc()
                logger.warning(
                    "eager collective %r re-built at runtime (retrace "
                    "#%d) — the executable cache is thrashing across "
                    "shapes/dtypes", key, int(self._c_retraces.value()))
            else:
                self._compiled_ever.add(key)
        self._cache[key] = fn            # reinsert: LRU, not FIFO
        return fn

    # -- ops ---------------------------------------------------------------
    @_timed("all_reduce")
    def all_reduce(self, x, axis_name: str = "data", op: str = "sum", **_):
        mesh = self.mesh

        def build():
            def f(v):
                with jax.named_scope(f"all_reduce_{axis_name}"):
                    r = lax.psum(v, axis_name)
                return r / self.topology.size(axis_name) if op == "mean" else r

            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False))

        fn = self._jit(("ar", axis_name, op) + self._sig(x), build)
        return fn(x)

    @_timed("all_gather")
    def all_gather(self, x, axis_name: str = "data", **_):
        """x sharded on dim 0 over axis_name -> fully replicated concat."""
        mesh = self.mesh

        def build():
            def f(v):
                with jax.named_scope(f"all_gather_{axis_name}"):
                    return lax.all_gather(v, axis_name, axis=0, tiled=True)

            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P(axis_name), out_specs=P(),
                check_vma=False))

        fn = self._jit(("ag", axis_name) + self._sig(x), build)
        return fn(x)

    @_timed("reduce_scatter")
    def reduce_scatter(self, x, axis_name: str = "data", **_):
        """x replicated -> dim-0 shards of the sum across axis_name."""
        mesh = self.mesh

        def build():
            def f(v):
                with jax.named_scope(f"reduce_scatter_{axis_name}"):
                    return lax.psum_scatter(v, axis_name,
                                            scatter_dimension=0, tiled=True)

            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(axis_name),
                check_vma=False))

        fn = self._jit(("rs", axis_name) + self._sig(x), build)
        return fn(x)

    @_timed("all_to_all")
    def all_to_all(self, x, axis_name: str = "data", split_dim: int = 0,
                   concat_dim: int = 0, **_):
        mesh = self.mesh

        def build():
            def f(v):
                with jax.named_scope(f"all_to_all_{axis_name}"):
                    return lax.all_to_all(v, axis_name, split_axis=split_dim,
                                          concat_axis=concat_dim, tiled=True)

            spec = [None] * x.ndim
            spec[concat_dim] = axis_name
            in_spec = P(*spec)
            out_spec_l = [None] * x.ndim
            out_spec_l[split_dim] = axis_name
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=in_spec, out_specs=P(*out_spec_l),
                check_vma=False))

        fn = self._jit(("a2a", axis_name, split_dim, concat_dim)
                       + self._sig(x), build)
        return fn(x)

    @_timed("broadcast")
    def broadcast(self, x, axis_name: str = "data", src: int = 0, **_):
        """Replicate rank ``src``'s shard to all ranks along axis."""
        mesh = self.mesh

        def build():
            def f(v):
                with jax.named_scope(f"broadcast_{axis_name}"):
                    idx = lax.axis_index(axis_name)
                    v = jnp.where(idx == src, v, jnp.zeros_like(v))
                    return lax.psum(v, axis_name)

            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False))

        fn = self._jit(("bc", axis_name, src) + self._sig(x), build)
        return fn(x)

    def barrier(self) -> None:
        """Block until all devices reach this point (reference: comm barrier)."""
        x = jnp.zeros((), dtype=jnp.int32)
        out = self.all_reduce(x, axis_name=DATA_DEFAULT_AXIS(self.topology))
        jax.block_until_ready(out)


def DATA_DEFAULT_AXIS(topology: MeshTopology) -> str:
    for a in ("data", "fsdp", "tensor"):
        if topology.axis_sizes.get(a, 1) >= 1:
            return a
    return "data"


def log_summary(show_straggler: bool = False):
    """Print the accumulated comm table (reference: comm/comm.py:422)."""
    return comms_logger.log_all(print_log=True, show_straggler=show_straggler)
