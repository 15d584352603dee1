"""Mixture-of-Experts: top-k gating + expert-parallel dispatch.

TPU-native re-design of the reference MoE stack
(``deepspeed/moe/layer.py:17`` MoE, ``moe/sharded_moe.py`` — ``TopKGate``
:374, top-1/2/k gating with capacity/jitter/RSample :183-449, ``MOELayer``
einsum dispatch → all_to_all → local experts → all_to_all → combine :533,
``_AllToAll`` autograd :96, ``Experts`` moe/experts.py:13).

Here the dispatch is the GShard dense-einsum formulation: build
``dispatch [T,E,C]`` / ``combine [T,E,C]`` masks from the gate top-k with
per-expert capacity, then

    expert_in  = einsum('tec,td->ecd', dispatch, x)     # XLA: all_to_all
    expert_out = ff_e(expert_in)                        # E sharded on mesh
    y          = einsum('tec,ecd->td', combine, expert_out)

With expert weights sharded over the ``expert`` mesh axis and tokens over
the batch axes, the SPMD partitioner inserts exactly the reference's
all_to_all pair.  Capacity keeps every shape static (XLA requirement —
and the reference drops tokens the same way, sharded_moe.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    dispatch: jnp.ndarray   # [T, E, C] float (0/1)
    combine: jnp.ndarray    # [T, E, C] float (gate weights)
    aux_loss: jnp.ndarray   # scalar load-balancing loss
    dropped: jnp.ndarray    # scalar fraction of tokens dropped


def top_k_gating(logits: jnp.ndarray, top_k: int, capacity: int,
                 rng: Optional[jax.Array] = None,
                 noise_policy: Optional[str] = None,
                 norm_topk: bool = True) -> GateOutput:
    """logits: [T, E].  (reference: top1gating/top2gating/topkgating
    sharded_moe.py:183,290,449)."""
    T, E = logits.shape
    if noise_policy == "RSample" and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) / E

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T, E]

    # iterative top-k: mask out previous choices
    dispatch_parts = []
    combine_parts = []
    remaining = gates
    sel_masks = []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                      # [T]
        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)           # [T, E]
        sel_masks.append(sel)
        remaining = remaining * (1.0 - sel)

    # aux loss from the top-1 assignment (Switch/GShard style,
    # reference sharded_moe.py l_aux)
    me = gates.mean(axis=0)                                       # [E]
    ce = sel_masks[0].mean(axis=0)                                # [E]
    aux_loss = (me * ce).sum() * E

    # capacity assignment: position of each token within its expert,
    # counting across all k choices in priority order
    prev_counts = jnp.zeros((E,), jnp.float32)
    kept_any = jnp.zeros((T,), jnp.float32)
    for sel in sel_masks:
        pos = jnp.cumsum(sel, axis=0) - 1.0 + prev_counts[None, :]  # [T, E]
        keep = sel * (pos < capacity)
        pos_idx = (pos * keep).astype(jnp.int32)
        disp = keep[:, :, None] * jax.nn.one_hot(
            pos_idx, capacity, dtype=jnp.float32)
        gate_val = (gates * keep).sum(axis=-1, keepdims=True)     # [T, 1]
        dispatch_parts.append(disp)
        combine_parts.append(disp * gate_val[:, :, None])
        prev_counts = prev_counts + sel.sum(axis=0)
        kept_any = jnp.maximum(kept_any, keep.sum(axis=-1))

    dispatch = sum(dispatch_parts)
    combine = sum(combine_parts)
    if top_k > 1 and norm_topk:
        # renormalize kept gate weights to sum 1 per token (reference: top2
        # normalization sharded_moe.py:290; top-1 keeps the raw probability
        # as in Switch / reference top1gating; qwen2-moe's
        # norm_topk_prob=False keeps the raw softmax probabilities)
        denom = combine.sum(axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    dropped = 1.0 - kept_any.mean()
    return GateOutput(dispatch=dispatch, combine=combine,
                      aux_loss=aux_loss, dropped=dropped)


class SparseGateOutput(NamedTuple):
    """Index-form gating (the megablox-style dispatch): one (expert,
    slot, weight) triple per (token, choice) instead of [T, E, C]
    one-hot masks."""
    ids: jnp.ndarray        # [T, K] i32 expert per choice
    pos: jnp.ndarray        # [T, K] i32 slot within the expert (== C when
                            #            dropped — scatter mode="drop")
    vals: jnp.ndarray       # [T, K] f32 gate weights (0 when dropped)
    aux_loss: jnp.ndarray
    dropped: jnp.ndarray


def top_k_gating_sparse(logits: jnp.ndarray, top_k: int, capacity: int,
                        rng: Optional[jax.Array] = None,
                        noise_policy: Optional[str] = None,
                        norm_topk: bool = True) -> SparseGateOutput:
    """Same selection/capacity/renormalization math as
    :func:`top_k_gating`, returning indices instead of one-hot masks —
    dispatch/combine become gather/scatter (O(T·K·d)) instead of
    mask einsums (O(T·E·C·d)), the dense-mask cost the reference pays in
    sharded_moe.py:533 and solves with the cutlass moe_gemm
    (inference/v2/kernels/cutlass_ops) — here the index form IS the
    XLA-friendly kernel."""
    T, E = logits.shape
    if noise_policy == "RSample" and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) / E

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T, E]

    remaining = gates
    sel_masks = []
    ids = []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                      # [T]
        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        sel_masks.append(sel)
        ids.append(idx.astype(jnp.int32))
        remaining = remaining * (1.0 - sel)

    me = gates.mean(axis=0)
    ce = sel_masks[0].mean(axis=0)
    aux_loss = (me * ce).sum() * E

    prev_counts = jnp.zeros((E,), jnp.float32)
    kept_any = jnp.zeros((T,), jnp.float32)
    pos_list, val_list = [], []
    for k, sel in enumerate(sel_masks):
        pos = jnp.cumsum(sel, axis=0) - 1.0 + prev_counts[None, :]
        keep = sel * (pos < capacity)
        pos_t = (pos * sel).sum(axis=-1)                          # [T]
        kept_t = keep.sum(axis=-1)                                # [T]
        gate_val = (gates * keep).sum(axis=-1)                    # [T]
        # dropped choices point at slot C — scatters with mode="drop"
        # discard them, gathers never see them (vals = 0)
        pos_list.append(jnp.where(kept_t > 0, pos_t,
                                  float(capacity)).astype(jnp.int32))
        val_list.append(gate_val)
        prev_counts = prev_counts + sel.sum(axis=0)
        kept_any = jnp.maximum(kept_any, kept_t)

    vals = jnp.stack(val_list, axis=1)                            # [T, K]
    if top_k > 1 and norm_topk:
        vals = vals / jnp.maximum(vals.sum(axis=1, keepdims=True), 1e-9)
    return SparseGateOutput(
        ids=jnp.stack(ids, axis=1), pos=jnp.stack(pos_list, axis=1),
        vals=vals, aux_loss=aux_loss, dropped=1.0 - kept_any.mean())


def capacity_for(tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float, min_capacity: int = 4) -> int:
    """(reference: _capacity sharded_moe.py)."""
    cap = int(math.ceil(tokens * top_k * capacity_factor / num_experts))
    return max(cap, min_capacity)


# --------------------------------------------------------------------------
# Expert FFN params (stacked on a leading expert dim)
# --------------------------------------------------------------------------

def experts_init(key, num_experts: int, d_model: int, d_ff: int,
                 gated: bool = False, out_scale: float = None):
    """Params [E, ...] with logical axes led by 'expert'
    (reference: Experts moe/experts.py:13 — a python list of FFNs; here one
    stacked tensor so a single grouped matmul serves all local experts)."""
    out_scale = out_scale or 1.0 / math.sqrt(d_ff)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wi": jax.random.normal(k1, (num_experts, d_model, d_ff))
         / math.sqrt(d_model),
         "wo": jax.random.normal(k2, (num_experts, d_ff, d_model)) * out_scale}
    a = {"wi": ("expert", "embed", "mlp"), "wo": ("expert", "mlp", "embed")}
    if gated:
        p["wg"] = jax.random.normal(k3, (num_experts, d_model, d_ff)) \
            / math.sqrt(d_model)
        a["wg"] = ("expert", "embed", "mlp")
    return p, a


def experts_apply(p, x, activation, gated: bool = False):
    """x: [E, C, d_model] -> [E, C, d_model]; one grouped matmul per
    projection (megablox-style grouped GEMM is the Pallas upgrade path,
    reference cutlass moe_gemm)."""
    dt = x.dtype
    u = jnp.einsum("ecd,edf->ecf", x, p["wi"].astype(dt))
    if gated:
        u = activation(jnp.einsum("ecd,edf->ecf", x, p["wg"].astype(dt))) * u
    else:
        u = activation(u)
    return jnp.einsum("ecf,efd->ecd", u, p["wo"].astype(dt))


def gate_init(key, d_model: int, num_experts: int):
    return ({"kernel": jax.random.normal(key, (d_model, num_experts)) * 0.01},
            {"kernel": ("embed", None)})


def route(logits, bias=None, *, top_k: int, score: str = "softmax",
          norm_topk: bool = True, route_scale: float = 1.0,
          groups: Optional[Tuple[int, int]] = None,
          group_score: str = "top2"):
    """The router's choice without capacity: float32 ``logits [T, E]`` →
    ``(weights [T, K] f32, experts [T, K] i32, open [T, n] bool)``, the
    last the groups a row may choose among (None without ``groups``).

    ``score``: ``softmax`` over the experts, or the ``sigmoid`` of each
    logit.  ``bias [E]``: added to the scores for the CHOICE of the
    top-k only (a router balanced without an auxiliary loss); the
    weights are the unbiased scores of the chosen.  ``norm_topk``: the
    chosen weights are renormalised to sum 1; ``route_scale`` multiplies
    them after that.  ``groups``: ``(n, kept)``, a limit on expert
    groups: the experts in order form ``n`` groups and the top-k is
    taken among the experts of the ``kept`` best groups.  What a group
    scores is ``group_score``, and the two rules choose different groups
    on the same scores: ``"top2"``, the sum of its two largest (biased)
    scores (DeepSeek-V3's ``noaux_tc``; Ling's); ``"max"``, its largest
    score (DeepSeek-V2's ``group_limited_greedy``, where a group is the
    experts of one device and the limit bounds the devices a token
    reaches)."""
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    choice = scores if bias is None else scores + bias.astype(scores.dtype)
    open_ = None
    if groups is not None and groups[0] > 1:
        n, kept = groups
        T, E = choice.shape
        grouped = choice.reshape(T, n, E // n)
        if group_score == "max":
            best = grouped.max(-1)
        else:
            best2, _ = jax.lax.top_k(grouped, 2)
            best = best2.sum(-1)
        _, keep = jax.lax.top_k(best, kept)                       # [T, kept]
        open_ = jnp.zeros((T, n), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(open_, E // n, axis=1), choice,
                           -jnp.inf)
    if choice is scores:            # the choice is by the scores alone
        vals, ids = jax.lax.top_k(scores, top_k)
    else:
        _, ids = jax.lax.top_k(choice, top_k)
        vals = jnp.take_along_axis(scores, ids, axis=1)
    if top_k > 1 and norm_topk:
        vals = vals / jnp.maximum(vals.sum(axis=1, keepdims=True), 1e-9)
    if route_scale != 1.0:
        vals = vals * route_scale
    return vals, ids, open_


def _ragged_moe(gate_p, expert_p, x, logits, *, top_k: int, activation,
                gated: bool, score: str = "softmax",
                route_scale: float = 1.0,
                norm_topk: bool = True,
                noise_policy: Optional[str], rng: Optional[jax.Array],
                dt, groups=None, held=None, group_score: str = "top2"
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """DROPLESS grouped-GEMM MoE (``dispatch_mode="ragged"``): tokens
    sort by assigned expert and each projection is ONE
    ``jax.lax.ragged_dot`` over per-expert row groups — the megablox
    formulation, and the TPU answer to the reference's cutlass grouped
    GEMMs (inference/v2/kernels/cutlass_ops/mixed_gemm + moe_gemm): no
    capacity padding, no dropped tokens, MXU-shaped contiguous groups.

    Expert weights must be locally addressable (replicated or
    fsdp-memory-sharded); expert-parallel meshes keep the
    scatter/einsum dispatch whose all-to-all GSPMD understands."""
    B, S, dm = x.shape
    T = B * S
    E = logits.shape[-1]
    lf = logits.reshape(T, E)
    if noise_policy == "RSample" and rng is not None:
        lf = lf + jax.random.normal(rng, lf.shape) / E
    lf = lf.astype(jnp.float32)
    gates = jax.nn.softmax(lf, axis=-1)                           # [T, E]
    # renormalised to sum 1 per token under norm_topk — same convention
    # as top_k_gating (reference top2 normalization sharded_moe.py:290)
    vals, ids, _ = route(lf, gate_p.get("bias"), top_k=top_k, score=score,
                         norm_topk=norm_topk, route_scale=route_scale,
                         groups=groups, group_score=group_score)
    me = gates.mean(axis=0)
    ce = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32).mean(axis=0)
    aux_loss = (me * ce).sum() * E
    if held is not None:
        # the experts held here are [first, first + count): the others'
        # assignments go nowhere (see ``moe_serve``: id ``count``, which
        # the scatter of group sizes drops) and weigh nothing
        ids, E = _held_ids(ids, held)
        vals = jnp.where(ids < E, vals, 0.0)

    flat_ids = ids.reshape(-1)                                    # [T*K]
    order = jnp.argsort(flat_ids, stable=True)
    tok = order // top_k                                          # [T*K]
    xs = x.reshape(T, dm)[tok].astype(dt)
    group_sizes = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)

    u = jax.lax.ragged_dot(xs, expert_p["wi"].astype(dt), group_sizes)
    if gated:
        g = jax.lax.ragged_dot(xs, expert_p["wg"].astype(dt), group_sizes)
        u = activation(g) * u
    else:
        u = activation(u)
    out = jax.lax.ragged_dot(u, expert_p["wo"].astype(dt), group_sizes)

    w = vals.reshape(-1)[order].astype(dt)
    if held is not None:
        out = jnp.where((flat_ids[order] < E)[:, None], out, 0)
    y = jnp.zeros((T, dm), dt).at[tok].add(out * w[:, None])
    return y.reshape(B, S, dm), {
        "moe_aux_loss": aux_loss,
        "moe_dropped": jnp.float32(0.0)}


def _held_ids(ids, held):
    """Expert ids ``[T, K]`` as the holder of experts ``[first, first +
    count)`` numbers them: 0..count-1 its own, ``count`` (nowhere) every
    other.  → (ids, count)."""
    first, count = held
    local = ids - first
    return jnp.where((local >= 0) & (local < count), local, count), count


def moe_serve(gate_p, expert_p, h, valid=None, *, top_k: int, activation,
              gated: bool, norm_topk: bool, kernel: bool = False,
              layer=None, score: str = "softmax",
              route_scale: float = 1.0, with_ids: bool = False,
              groups: Optional[Tuple[int, int]] = None,
              held: Optional[Tuple[int, int]] = None, zero: int = 0,
              group_score: str = "top2",
              held_groups: Optional[Tuple[int, int]] = None):
    """The serving expert layer: DROPLESS by construction.  h: [T, d]
    rows of one serving step (any mix of sequences); ``valid``: [T] bool,
    False for the rows that pad the step's bucket (None: all real).
    Returns ``(y [T, d], stats [3] i32)``, and with ``with_ids`` a third:
    ``[T, top_k] i32``, the experts each row took (a padding row: E).

    Every real row's ``top_k`` assignments are computed whatever else is
    in the step: there is no capacity, so a row's output does not depend
    on its neighbours.  Padding rows are routed nowhere: they sort behind
    the last expert, are counted in no group, and come back zero.  The
    router's scores and its top-k are float32 (:func:`route`, one
    ``jax.lax.top_k``; ``score``, ``route_scale`` and a ``bias`` in
    ``gate_p`` are its form); the scores of the chosen are used as they
    are unless ``norm_topk``.

    The three projections are grouped matrix multiplications over the
    rows sorted by expert: ``ops/grouped_matmul.py`` when ``kernel`` (a
    TPU, weights on one device), else ``jax.lax.ragged_dot``.  The
    assignments are listed choice by choice, so a row's ``top_k`` expert
    rows come back as ``[top_k, T, d]`` and their weighted sum is one
    pass over them: float32 products, a float32 sum, one cast.

    ``layer``: with it ``expert_p`` holds the experts of ALL layers,
    ``[L, E, ...]`` as the model stacks them, and this call is layer
    ``layer``'s (a traced scalar in a layer scan).  The kernel indexes
    the stack where it lies; a layer sliced out of it first would be
    copied whole on its way into the custom call.

    ``groups``, ``group_score``: the router's limit on expert groups
    (:func:`route`).
    ``held``: ``(first, count)``, the experts whose weights ``expert_p``
    holds (``[.., count, ...]``), a chip's share of the layer.  The
    router keeps all its outputs and its ``top_k`` a row, the weights
    are normalised over all the chosen, and an assignment to an expert
    that is not held goes nowhere, as a padding row's does: it is
    counted in no group and adds nothing.  Nothing stands in for the
    chips that hold the others.  ``with_ids`` gives the router's own
    numbering.

    ``zero``: experts that compute nothing, the router's LAST ``zero``
    outputs (``held`` and ``expert_p`` number the ones before them, which
    have weights).  An assignment to one is counted in no group and
    reaches no grouped product: it gives the row's input back, times the
    weight (scope ``moe_zero``).  That part needs no weights and no
    exchange: every holder of a share computes it for its own rows.

    ``stats``: (assignments computed, 1000 x the fullest expert's rows
    over the mean, experts that took a row), for the engine's counters
    (with ``held``: of the experts held; the router made ``top_k`` a
    real row); with ``zero`` a fourth, the assignments to experts that
    compute nothing; with ``held_groups``, ``(first, count)`` of the
    router's groups where a group is a device's experts and ``held`` is
    whole groups (``TransformerConfig.held_groups``), a last: the real
    rows that opened at least one of them, which a deployment's exchange
    would send to this chip."""
    T, dm = h.shape
    E = expert_p["wi"].shape[-3]
    dt = h.dtype
    outputs = gate_p["kernel"].shape[-1]
    if zero and held is None:
        held = (0, outputs - zero)      # the experts that have weights
    if kernel:
        from ..ops.grouped_matmul import grouped_matmul

        def mm(x, w, sizes):
            return grouped_matmul(
                x, w.reshape((-1,) + w.shape[-2:]), sizes,
                first_group=0 if layer is None else layer * E)
    else:
        mm = jax.lax.ragged_dot
        if layer is not None:
            expert_p = jax.tree.map(lambda w: w[layer], expert_p)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(h, gate_p["kernel"].astype(dt),
                         preferred_element_type=jnp.float32)
        vals, ids, open_ = route(
            logits, gate_p.get("bias"), top_k=top_k, score=score,
            norm_topk=norm_topk, route_scale=route_scale, groups=groups,
            group_score=group_score)
        if valid is not None:
            ids = jnp.where(valid[:, None], ids, outputs)
        taken = ids                         # expert E: nowhere
        if held is not None:
            ids, _ = _held_ids(ids, held)
        # the assignments choice-major, ``k * T + t``: the expert rows
        # gathered back are then ``[K, T, d]`` as they lie
        flat = ids.T.reshape(-1)                                  # [K*T]
        order = jnp.argsort(flat, stable=True)
        # counted by comparison (a scatter-add of K*T ones into E
        # counters serialises on the chip); id E, nowhere, fits none
        group_sizes = (flat[:, None] == jnp.arange(E, dtype=flat.dtype)
                       ).sum(axis=0, dtype=jnp.int32)
        xs = h[order % T]                                     # [K*T, d]
    with jax.named_scope("moe_experts"):
        u = mm(xs, expert_p["wi"].astype(dt), group_sizes)
        if gated:
            u = activation(mm(xs, expert_p["wg"].astype(dt),
                              group_sizes)) * u
        else:
            u = activation(u)
        out = mm(u, expert_p["wo"].astype(dt), group_sizes)
    with jax.named_scope("moe_route"):
        back = jnp.argsort(order)       # the permutation's inverse
        picked = out[back].reshape(top_k, T, dm)
        # one pass over the rows as the experts wrote them: float32
        # products, a float32 sum over the K slabs ([T, K, d] in float32
        # is never an array).  An assignment that went nowhere (not held,
        # a padding row's) weighs nothing; its row of ``out`` is zero
        w = jnp.where(ids < E, vals, 0.0)
        y = sum(picked[k].astype(jnp.float32) * w[:, k, None]
                for k in range(top_k))
        if zero:
            with jax.named_scope("moe_zero"):
                nothing = (taken >= outputs - zero) & (taken < outputs)
                y = y + jnp.where(nothing, vals, 0.0).sum(
                    axis=1, keepdims=True) * h.astype(jnp.float32)
        y = y.astype(dt)
        if valid is not None:
            y = jnp.where(valid[:, None], y, 0)
        n = group_sizes.sum()
        stats = [n, (group_sizes.max() * (1000 * E)) // jnp.maximum(n, 1),
                 (group_sizes > 0).sum()]
        if zero:
            stats.append(nothing.sum())
        if held_groups is not None:
            first, count = held_groups
            here = open_[:, first:first + count].any(1)
            stats.append((here if valid is None else here & valid).sum())
        stats = jnp.stack(stats)
    return (y, stats, taken) if with_ids else (y, stats)


def moe_ffn(gate_p, expert_p, x, *, top_k: int, capacity_factor: float,
            min_capacity: int = 4, activation=jax.nn.gelu,
            gated: bool = False, rng: Optional[jax.Array] = None,
            noise_policy: Optional[str] = None,
            dispatch_mode: str = "scatter",
            norm_topk: bool = True, score: str = "softmax",
            route_scale: float = 1.0, groups=None, held=None,
            zero: int = 0, group_score: str = "top2"
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full MoE FFN over x [B, S, d_model] (reference: MOELayer.forward
    sharded_moe.py:533).  Returns (y, metrics) with metrics carrying the
    aux load-balancing loss.

    Tokens are gated **per group** (one group per sequence, the GShard
    grouping) so dispatch state is linear in total tokens (Cg is the
    per-group capacity).

    ``dispatch_mode="scatter"`` (default) is the megablox-style index
    form: dispatch is a scatter of token ids into [E, Cg] slots and a
    gather, combine a K-way weighted gather — O(T·K·d) data movement.
    ``"einsum"`` is the GShard dense-mask formulation (one-hot
    [Tg, E, Cg] masks contracted against activations — O(T·E·Cg·d), the
    cost the reference's cutlass moe_gemm kernels exist to avoid); kept
    as the executable specification the scatter path is tested against.
    ``"ragged"`` is the DROPLESS megablox-style grouped GEMM
    (``jax.lax.ragged_dot`` over expert-sorted tokens — no capacity, no
    drops; see :func:`_ragged_moe`).

    Why scatter is the default: at mixtral-ish shapes (E8 d1024 ff3584
    T16k) it compiled to 2.4x less temp memory than the einsum form (420
    vs 1007 MB, a CPU-mesh compile; no chip run of either is on record).

    ``score``, ``route_scale`` and a ``bias`` in ``gate_p``: the
    router's form, see :func:`route`; the capacity dispatches know only
    the softmax router without a bias.

    Training only.  Serving does not come here: ``moe_serve`` below is
    dropless whatever ``dispatch_mode`` a config names.
    """
    if zero:
        raise ValueError(
            "experts that compute nothing (moe_zero_experts) are served "
            "(moe_serve); no training dispatch knows the form")
    B, S, dm = x.shape
    E = gate_p["kernel"].shape[-1]
    cap = capacity_for(S, E, top_k, capacity_factor, min_capacity)
    if noise_policy == "Jitter" and rng is not None:
        # jitter gets its own stream: reusing ``rng`` here would
        # correlate the input jitter with the gating noise drawn below
        jitter_rng, rng = jax.random.split(rng)
        xg = x * jax.random.uniform(jitter_rng, x.shape,
                                    minval=0.98, maxval=1.02)
    else:
        xg = x
    logits = jnp.einsum("gtd,de->gte", xg, gate_p["kernel"].astype(x.dtype))
    dt = x.dtype
    if dispatch_mode == "ragged":
        return _ragged_moe(gate_p, expert_p, x, logits, top_k=top_k,
                           activation=activation, gated=gated,
                           noise_policy=noise_policy, rng=rng, dt=dt,
                           norm_topk=norm_topk, score=score,
                           route_scale=route_scale, groups=groups,
                           held=held, group_score=group_score)
    if score != "softmax" or "bias" in gate_p or route_scale != 1.0 \
            or groups is not None or held is not None:
        raise ValueError(
            f"moe_dispatch={dispatch_mode!r} routes by softmax without a "
            "selection bias; this router needs moe_dispatch='ragged'")
    rngs = jax.random.split(rng, B) if rng is not None else None

    gate_fn = functools.partial(
        top_k_gating_sparse if dispatch_mode == "scatter" else top_k_gating,
        top_k=top_k, capacity=cap, noise_policy=noise_policy,
        norm_topk=norm_topk)
    if rngs is None:
        gate = jax.vmap(lambda l: gate_fn(l, rng=None))(logits)
    else:
        gate = jax.vmap(lambda l, r: gate_fn(l, rng=r))(logits, rngs)

    if dispatch_mode == "scatter":
        def dispatch_group(ids, pos, x_g):
            # token index per (expert, slot); empty slots point at token
            # 0 with zero validity
            tok = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[:, None], ids.shape)
            slot_tok = jnp.zeros((E, cap), jnp.int32).at[
                ids, pos].set(tok, mode="drop")
            valid = jnp.zeros((E, cap), dt).at[
                ids, pos].set(jnp.ones_like(tok, dt), mode="drop")
            return x_g[slot_tok] * valid[..., None]

        expert_in = jax.vmap(dispatch_group, in_axes=(0, 0, 0),
                             out_axes=1)(gate.ids, gate.pos, x)
        expert_in = expert_in.reshape(E, B * cap, dm)
        expert_out = experts_apply(expert_p, expert_in, activation, gated)
        expert_out = expert_out.reshape(E, B, cap, dm)

        def combine_group(ids, pos, vals, eo_g):
            # eo_g: [E, Cg, d]; K-way weighted gather per token
            safe_pos = jnp.minimum(pos, cap - 1)
            picked = eo_g[ids, safe_pos]                  # [Tg, K, d]
            return (picked * vals[..., None].astype(dt)).sum(axis=1)

        y = jax.vmap(combine_group, in_axes=(0, 0, 0, 1))(
            gate.ids, gate.pos, gate.vals, expert_out)
    else:
        # [G,Tg,E,Cg] x [G,Tg,d] -> [E, G*Cg, d]; SPMD: the all_to_all
        expert_in = jnp.einsum("gtec,gtd->egcd", gate.dispatch.astype(dt), x)
        expert_in = expert_in.reshape(E, B * cap, dm)
        expert_out = experts_apply(expert_p, expert_in, activation, gated)
        expert_out = expert_out.reshape(E, B, cap, dm)
        y = jnp.einsum("gtec,egcd->gtd", gate.combine.astype(dt), expert_out)
    return y, {"moe_aux_loss": gate.aux_loss.mean(),
               "moe_dropped": gate.dropped.mean()}
