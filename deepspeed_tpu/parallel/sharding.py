"""Logical-axis sharding rules.

TPU-native replacement for the reference's parameter-partitioning machinery
(``runtime/zero/partition_parameters.py``, ``module_inject/auto_tp.py:30``
``ReplaceWithTensorSlicing``, and the v2 declarative sharding helpers
``inference/v2/model_implementations/sharding/``).  Instead of slicing
tensors imperatively, every parameter carries a tuple of *logical axis
names* (``('embed', 'mlp')`` …), and a table of rules maps logical axes to
mesh axes.  ``jax.jit`` + XLA SPMD then insert all gathers/reduce-scatters.

This is the idiomatic TPU formulation (T5X/MaxText-style); combined with the
ZeRO stage policy in :mod:`deepspeed_tpu.parallel.zero` it reproduces the
reference's DP/TP/ZeRO behaviors declaratively.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..comm.mesh import (AXIS_ORDER, DATA_AXIS, EXPERT_AXIS, FSDP_AXIS,
                         MeshTopology, PIPE_AXIS, SEQ_AXIS, TENSOR_AXIS)

# A logical axis annotation: tuple of names, one per tensor dim (None = never shard)
LogicalAxes = Tuple[Optional[str], ...]

# Default logical->mesh rules (tensor parallelism).  Multiple candidates are
# tried in order; first mesh axis with size>1 that still divides wins.
DEFAULT_RULES: Dict[str, Sequence[str]] = {
    # activations / batch-like
    "batch": (DATA_AXIS, FSDP_AXIS),
    "seq": (SEQ_AXIS,),
    # stacked layer dim: pipeline stages own contiguous layer slices
    "layers": (PIPE_AXIS,),
    # parameter axes
    "vocab": (TENSOR_AXIS,),
    "embed": (),                      # residual stream: replicated under TP
    "mlp": (TENSOR_AXIS,),            # MLP hidden (column-parallel in, row-parallel out)
    "heads": (TENSOR_AXIS,),          # attention heads (Megatron-style head split)
    "kv_heads": (TENSOR_AXIS,),
    "head_dim": (),
    "expert": (EXPERT_AXIS,),         # MoE expert dimension
    "norm": (),
    "conv_in": (), "conv_out": (TENSOR_AXIS,), "conv_k": (),
}


def spec_for_axes(axes: LogicalAxes, rules: Optional[Dict[str, Sequence[str]]],
                  topology: MeshTopology, shape: Optional[Tuple[int, ...]] = None) -> P:
    """Map one parameter's logical axes to a PartitionSpec under `rules`.

    A mesh axis is only assigned once per spec and only if it has size > 1
    (size-1 axes would be no-ops but pollute the spec) and, when `shape` is
    given, only if it divides the dim size.
    """
    rules = {**DEFAULT_RULES, **(rules or {})}
    used = set()
    entries = []
    for i, name in enumerate(axes):
        assigned = None
        for mesh_axis in rules.get(name, ()) if name else ():
            size = topology.axis_sizes.get(mesh_axis, 1)
            if mesh_axis in used or size <= 1:
                continue
            if shape is not None and shape[i] % size != 0:
                continue
            assigned = mesh_axis
            used.add(mesh_axis)
            break
        entries.append(assigned)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def add_fsdp_to_spec(spec: P, shape: Tuple[int, ...], topology: MeshTopology,
                     min_size: int = 0, axis: str = FSDP_AXIS) -> P:
    """Layer ZeRO/FSDP sharding on top of a TP spec: shard the largest
    still-unsharded dim that the fsdp axis size divides (reference analog:
    flat 1-D partitioning in stage_1_and_2.py:646 / stage3 — but on TPU we
    shard a real tensor dim, so a gather is one all-gather along it).
    The spec says where the leaf lives, not how it is used: the stage-3
    step states the per-use gather itself (zero.py ``placement``)."""
    n = topology.axis_sizes.get(axis, 1)
    if n <= 1 or int(np.prod(shape)) < max(min_size, 1):
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    # candidate dims: not already sharded; divisible by n after existing shards
    best, best_size = None, 0
    for i, dim in enumerate(shape):
        cur = entries[i]
        cur_axes = (cur,) if isinstance(cur, str) else tuple(cur or ())
        if axis in cur_axes:
            return spec
        denom = 1
        for a in cur_axes:
            denom *= topology.axis_sizes.get(a, 1)
        local = dim // denom
        if local % n == 0 and local > best_size:
            best, best_size = i, local
    if best is None:
        return spec
    cur = entries[best]
    if cur is None:
        entries[best] = axis
    elif isinstance(cur, str):
        entries[best] = (cur, axis)
    else:
        entries[best] = tuple(cur) + (axis,)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_specs(axes_tree: Any, topology: MeshTopology,
               rules: Optional[Dict[str, Sequence[str]]] = None,
               shapes: Any = None) -> Any:
    """Map a pytree of LogicalAxes (+ optional matching shapes tree) to specs."""
    if shapes is None:
        return jax.tree.map(
            lambda ax: spec_for_axes(ax, rules, topology),
            axes_tree, is_leaf=lambda x: isinstance(x, tuple) and
            all(e is None or isinstance(e, str) for e in x))
    return jax.tree.map(
        lambda ax, sh: spec_for_axes(ax, rules, topology, tuple(sh)),
        axes_tree, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and
        all(e is None or isinstance(e, str) for e in x))


def named(topology: MeshTopology, spec: P) -> NamedSharding:
    return NamedSharding(topology.mesh, spec)


def tree_named(topology: MeshTopology, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(topology.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def infer_logical_axes(params: Any) -> Any:
    """Fallback when a model provides no logical axes: mark every dim None
    (replicated under TP; fsdp layering still applies by shape)."""
    return jax.tree.map(lambda p: tuple([None] * np.ndim(p)), params)
