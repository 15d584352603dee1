"""ZeRO stages as declarative sharding policy.

TPU-native re-design of the reference's ZeRO optimizers
(``runtime/zero/stage_1_and_2.py:96`` — flat-buffer partitioning, grad-hook
IPG bucketing, ``stage3.py:109`` — hook-driven param gather/release).  Under
XLA SPMD none of that machinery exists: each ZeRO stage is simply a choice of
PartitionSpecs for (params, grads, optimizer state) over the ``fsdp`` mesh
axis, and the partitioner inserts exactly the collectives the reference
hand-codes:

* stage 0 — everything replicated; grads psum over data+fsdp.
* stage 1 — master/opt state sharded over fsdp; compute params replicated.
            XLA emits grad all-reduce + sharded update + param all-gather —
            the same comm pattern as stage_1_and_2.py step (:1823).
* stage 2 — grads also sharded over fsdp: XLA emits reduce-scatter instead
            of all-reduce at the GAS boundary (reduce_ipg_grads :1364).
* stage 3 — compute params sharded too, and the train step SAYS what that
            means (``ZeroPolicy.placement``): every use of a sharded
            parameter gathers the parameter over ``fsdp`` in the compute
            dtype (one layer's slice at a time inside the layer scan, so
            forward, recomputation and backward each fetch and release
            it — partitioned_param_coordinator.py:262), its gradient is
            reduce-scattered back into the sharded layout, and every
            activation stays split over the batch.  Specs on the
            parameters alone do not say this: left to itself the
            partitioner reads "weight sharded on a feature dim" as tensor
            parallelism over ``fsdp`` and moves the whole batch through
            every projection (PERF.md section 6, PR 29).  What XLA is
            left to schedule is when each gather runs relative to the
            compute around it; nothing prefetches layer i+1 yet.

ZeRO++-style variants:
* hpZ (secondary partition, ``zero_hpz_partition_size``) — params shard over
  an *intra-slice* subaxis so the backward all-gather never crosses DCN.
* qwZ/qgZ (quantized collectives) — see deepspeed_tpu/ops/quant.py; applied
  inside manual shard_map collectives when enabled.

Small parameters stay replicated below ``param_persistence_threshold``
(reference: stage3 persistence threshold, compared with one module's
parameter: a leaf stacked over ``layers`` is compared by one layer's share).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.mesh import BATCH_AXES, FSDP_AXIS, MeshTopology
from ..config.config import ZeroConfig
from . import sharding as shd


@dataclass
class ZeroPolicy:
    """Resolved sharding policy for one training run."""

    stage: int
    topology: MeshTopology
    rules: Optional[Dict[str, Sequence[str]]] = None
    param_persistence_threshold: int = 10_000
    # ZeRO-Offload shards masters over the *full* DP world (data x fsdp),
    # like the reference partitions optimizer state across all DP ranks
    # (stage_1_and_2.py:646): minimises host DRAM per rank and keeps every
    # leaf partitioned, which XLA host-memory placement requires.
    offload: bool = False
    # hpZ (ZeRO++ secondary partition, zero_hpz_partition_size): compute
    # params shard over the small intra-slice fsdp axis (cheap ICI
    # gathers) while master/opt/grads shard over the full data x fsdp
    # world — the engine shrinks the fsdp axis to the hpz size and folds
    # the rest into data (reference: ds_secondary_tensor, groups.py:529).
    hpz: bool = False

    @classmethod
    def from_config(cls, zcfg: ZeroConfig, topology: MeshTopology,
                    rules: Optional[Dict[str, Sequence[str]]] = None) -> "ZeroPolicy":
        return cls(stage=zcfg.stage, topology=topology, rules=rules,
                   param_persistence_threshold=zcfg.param_persistence_threshold,
                   # cpu: host-DRAM minimization; nvme: per-rank swap
                   # fragments (each process stores/updates only its own
                   # data x fsdp shard — stage3.py:614 per-rank swap)
                   offload=zcfg.offload_optimizer.device in ("cpu", "nvme"),
                   hpz=zcfg.zero_hpz_partition_size > 1)

    # ---- spec builders ---------------------------------------------------
    def _tp_spec(self, axes, shape) -> P:
        return shd.spec_for_axes(axes, self.rules, self.topology, shape)

    def param_spec(self, axes, shape) -> P:
        """Compute-parameter sharding (what the step holds between uses)."""
        spec = self._tp_spec(axes, shape)
        if self.stage >= 3:
            # the threshold is one module's parameter: a stacked leaf
            # holds shape[0] of them
            layers = shape[0] if axes and axes[0] == "layers" else 1
            spec = shd.add_fsdp_to_spec(
                spec, shape, self.topology,
                min_size=self.param_persistence_threshold * layers)
        return spec

    def master_spec(self, axes, shape) -> P:
        """fp32 master params + optimizer moments: sharded from stage 1 on."""
        spec = self._tp_spec(axes, shape)
        if self.stage >= 1:
            spec = shd.add_fsdp_to_spec(spec, shape, self.topology, min_size=0)
        if self.offload or self.hpz:
            spec = shd.add_fsdp_to_spec(spec, shape, self.topology, min_size=0,
                                        axis=shd.DATA_AXIS)
        return spec

    def grad_spec(self, axes, shape) -> P:
        """Gradient sharding at the reduction boundary: stage >=2 shards
        (reduce-scatter); below that grads follow the compute params."""
        if self.stage >= 2:
            return self.master_spec(axes, shape)
        return self.param_spec(axes, shape)

    # ---- stage 3: what a use of a parameter reads -------------------------
    @property
    def gathers_per_use(self) -> bool:
        """Stage 3 over an ``fsdp`` axis that exists: the one condition
        under which the step states gathers and batch-sharded activations."""
        return self.stage >= 3 and shard_count(self.topology) > 1

    def use_spec(self, axes, shape) -> P:
        """How a use of the parameter reads it: the compute layout without
        the ``fsdp`` axis (a ``tensor`` or ``expert`` split stays)."""
        return self._tp_spec(axes, shape)

    def gather_bytes(self, held, used, shapes, itemsize: int):
        """(leaves a stage-3 step gathers per use, bytes ONE gather of each
        brings to one chip at ``itemsize``) — from the specs alone."""
        n = shard_count(self.topology)
        is_spec = lambda x: isinstance(x, P)
        sizes = [int(np.prod(sh)) for h, u, sh in zip(
            jax.tree.leaves(held, is_leaf=is_spec),
            jax.tree.leaves(used, is_leaf=is_spec),
            jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)))
            if h != u]
        return len(sizes), sum(sizes) * itemsize * (n - 1) // n

    def placement(self, held, used):
        """The two statements a stage-3 forward makes, as callables for a
        model that knows no mesh (``models.transformer.Placement``).
        ``held`` / ``used``: ``tree_param_specs`` / ``tree_use_specs``."""
        from ..models.transformer import Placement

        mesh = self.topology.mesh
        sizes = self.topology.axis_sizes
        batch_axes = tuple(a for a in BATCH_AXES if sizes.get(a, 1) > 1)

        def keep(x):
            # inside a shard_map that took some batch axes (the manual
            # gradient reductions take `data`), state only the rest
            manual = jax.sharding.get_abstract_mesh().manual_axes
            axes = tuple(a for a in batch_axes if a not in manual)
            if not axes or x.shape[0] % int(np.prod([sizes[a] for a in axes])):
                return x
            return jax.lax.with_sharding_constraint(x, NamedSharding(
                mesh, P(axes, *[P.UNCONSTRAINED] * (x.ndim - 1))))

        def use(name, sub, layer_slice=False):
            cut = (lambda s: P(*s[1:])) if layer_slice else (lambda s: s)
            return jax.tree.map(
                lambda h, u, x: x if h == u else _gathered(
                    x, NamedSharding(mesh, cut(h)),
                    NamedSharding(mesh, cut(u))),
                held[name], used[name], sub,
                is_leaf=lambda s: isinstance(s, P))

        return Placement(use=use, keep=keep)

    # ---- tree level ------------------------------------------------------
    def tree_param_specs(self, axes_tree, params) -> Any:
        return _tree_zip_specs(self.param_spec, axes_tree, params)

    def tree_use_specs(self, axes_tree, params) -> Any:
        return _tree_zip_specs(self.use_spec, axes_tree, params)

    def tree_master_specs(self, axes_tree, params) -> Any:
        return _tree_zip_specs(self.master_spec, axes_tree, params)

    def tree_grad_specs(self, axes_tree, params) -> Any:
        return _tree_zip_specs(self.grad_spec, axes_tree, params)

    def tree_named(self, spec_tree) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.topology.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gathered(x, held: NamedSharding, used: NamedSharding):
    """``x`` as its uses read it.  The cotangent goes straight back to the
    layout the parameter is held in: each chip's partial weight gradient
    (its batch shard's) is reduce-scattered, never summed whole.  Both
    constraints carry a named scope, so the collectives the partitioner
    writes for them have a name in whichever pass they run."""
    with jax.named_scope("zero_gather"):
        return jax.lax.with_sharding_constraint(x, used)


def _gathered_fwd(x, held, used):
    return _gathered(x, held, used), None


def _gathered_bwd(held, used, _, ct):
    with jax.named_scope("zero_scatter"):
        return (jax.lax.with_sharding_constraint(ct, held),)


_gathered.defvjp(_gathered_fwd, _gathered_bwd)


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def _tree_zip_specs(fn, axes_tree, params):
    return jax.tree.map(
        lambda ax, p: fn(ax, tuple(np.shape(p))),
        axes_tree, params, is_leaf=lambda x: _is_axes(x))


def shard_count(topology: MeshTopology) -> int:
    return topology.axis_sizes.get(FSDP_AXIS, 1)
