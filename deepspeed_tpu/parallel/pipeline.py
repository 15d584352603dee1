"""Pipeline parallelism over the ``pipe`` mesh axis.

TPU-native re-design of the reference pipeline stack
(``runtime/pipe/module.py:86`` PipelineModule layer partitioning,
``schedule.py:189`` TrainSchedule/1F1B instruction generator,
``pipe/engine.py:61`` PipelineEngine instruction interpreter with p2p
send/recv ``pipe/p2p.py:46``).

The reference interprets instruction lists per rank with explicit
send/recv.  Under SPMD there is no per-rank program: a pipeline schedule
is a single ``lax.scan`` over ticks inside one ``shard_map`` over the
``pipe`` axis.  Each tick every stage applies its layer slice and hands
its activation to the next stage via ``lax.ppermute`` — the instruction
schedule *is* the scan and the p2p layer *is* ppermute riding ICI
neighbor links.

Two schedules:

* **gpipe** — forward scan over ``M + S - 1`` ticks, backward by
  autodiff through the scan.  Simple, but reverse-mode saves every
  tick's boundary activation: live activation memory grows with M.
* **1f1b** — the reference TrainSchedule's memory behaviour
  (schedule.py:189: ``num_pipe_buffers = min(S - stage, M)`` :313),
  implemented as an *eager-gradient* custom VJP: the forward runs the
  interleaved fwd/bwd schedule itself (fwd of microbatch m at stage s on
  tick ``m + s``; its backward on tick ``m + 2(S-1) - s + 1``, i.e.
  immediately after the forward on the last stage), stashing only a ring
  of ``min(M, 2S - 1)`` boundary activations per stage and accumulating
  parameter gradients tick by tick.  ``jax.grad`` then merely scales the
  precomputed gradients — activation memory is O(S), independent of M.

Sequence parallelism composes: with ``seq > 1`` the sequence dim is
sharded across the same shard_map and attention runs the per-shard
Ulysses all-to-all (``parallel/sequence.make_ulysses_local``).

Layer placement: the model's stacked ``blocks`` (leading ``layers`` dim)
are sharded over ``pipe`` — contiguous equal slices, the 'uniform'
partition method of module.py:391.  Embedding/unembedding stay replicated
across stages (the reference's tied-layer broadcast, module.py:77; the
tied-weight gradient allreduce is the explicit PIPE psum of the shared
grads below / XLA's psum transpose under gpipe).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..comm.mesh import BATCH_AXES, MeshTopology, PIPE_AXIS, SEQ_AXIS
from ..models import layers as L
from ..models.transformer import (TransformerConfig, block_apply,
                                  rolled_lm_targets, _norm)


def make_pipelined_loss_fn(cfg: TransformerConfig, topology: MeshTopology,
                           num_microbatches: int,
                           attention_fn: Callable = L.causal_attention,
                           schedule: str = "gpipe"):
    """Build ``loss_fn(params, batch, rng)`` running a pipeline schedule.

    Requirements: ``num_layers % pipe == 0``; the global micro-batch (the
    engine's per-step batch) divisible by ``num_microbatches``; with
    seq > 1, heads divisible by the seq axis (Ulysses constraint).
    """
    mesh = topology.mesh
    S = topology.pp_size
    M = num_microbatches
    sp = topology.sp_size
    if cfg.num_layers % S:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                         f"pipe stages {S}")
    if not cfg.plain_stack:
        raise NotImplementedError(
            "the pipeline's stages hold layers of one block type "
            "(TransformerConfig.plain_stack)")
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(gpipe | 1f1b)")
    if cfg.position == "alibi":
        if sp > 1:
            # replace the model's plain ALiBi wrapper: under the
            # pipeline's manual seq axis the bias must slice the GLOBAL
            # slope series at this shard's head offset (the sp>1 branch
            # below then wraps it with the per-shard Ulysses a2a)
            from .sequence import make_ulysses_alibi_base
            attention_fn = make_ulysses_alibi_base(
                cfg.num_heads, sp, attn_scale=cfg.attn_scale)
        elif attention_fn is L.causal_attention:
            # direct callers that never resolved the model's attention:
            # the ALiBi bias (and any custom attn_scale) must not
            # silently vanish under PP — mirror _resolve_attention
            base = L.causal_attention
            if cfg.attn_scale is not None:
                s = cfg.attn_scale

                def base(q, k, v, mask=None, **kw):
                    return L.causal_attention(q, k, v, mask=mask,
                                              scale=s, **kw)
            attention_fn = L.make_alibi_attention(base)

    if sp > 1:
        if cfg.num_heads % sp or cfg.num_kv_heads % sp:
            raise ValueError(
                f"pipeline x seq needs heads divisible by seq axis: "
                f"H={cfg.num_heads}, Hkv={cfg.num_kv_heads}, seq={sp}")
        from .sequence import make_ulysses_local
        attention_fn = make_ulysses_local(attention_fn)

    norm = _norm(cfg)
    dp = topology.dp_world_size
    reduce_axes = (PIPE_AXIS,) + tuple(BATCH_AXES) + \
        ((SEQ_AXIS,) if sp > 1 else ())
    batch_reduce_axes = tuple(BATCH_AXES) + ((SEQ_AXIS,) if sp > 1 else ())
    data_spec = P(BATCH_AXES, SEQ_AXIS) if sp > 1 else P(BATCH_AXES)

    # ---------------------------------------------------------------- util
    def stage_fwd(blocks_local, x, attn_mask, cos, sin):
        """Apply this stage's layer slice.  Returns (x, aux) where aux is
        the mean MoE load-balancing loss over the local layers (0.0 for
        dense models)."""
        def body(h, lp):
            h, metrics = block_apply(cfg, lp, h, cos, sin, mask=attn_mask,
                                     attention_fn=attention_fn)
            aux = metrics.get("moe_aux_loss", jnp.float32(0.0)) \
                if metrics else jnp.float32(0.0)
            return h, aux
        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, aux = lax.scan(body_fn, x, blocks_local)
        return x, jnp.mean(aux)

    def head_nll(shared, y, labels, msk):
        """Unembed + lse - target_logit loss sum (no fp32 [mb,S,V]
        buffer — same rationale as cross_entropy_loss)."""
        dt = shared["embed"]["table"].dtype
        h = norm(shared["ln_f"], y)
        if cfg.tie_embeddings:
            logits = h @ shared["embed"]["table"].astype(dt).T
        else:
            logits = h @ shared["lm_head"]["kernel"].astype(dt)
            if cfg.head_bias:
                logits = logits + shared["lm_head"]["bias"].astype(dt)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = lse - tgt.astype(jnp.float32)
        return (nll * msk).sum()

    def embed_in(shared, ids, pos0, seq_local):
        dt = shared["embed"]["table"].dtype
        x0 = L.embed(shared["embed"], ids).astype(dt)
        if cfg.embed_norm:          # bloom word_embeddings_layernorm
            x0 = norm(shared["ln_embed"], x0)
        if cfg.position == "learned":
            tab = lax.dynamic_slice_in_dim(shared["pos_embed"]["table"],
                                           pos0, seq_local)
            x0 = x0 + tab.astype(dt)
        return x0

    def rope_tables(pos0, seq_local):
        if cfg.position != "rope":
            return None, None
        cos, sin = L.rope_freqs(cfg.rotary_dim, cfg.max_seq_len,
                                cfg.rope_theta)
        return (lax.dynamic_slice_in_dim(cos, pos0, seq_local),
                lax.dynamic_slice_in_dim(sin, pos0, seq_local))

    def pos_offset(seq_local):
        if sp > 1:
            return lax.axis_index(SEQ_AXIS) * seq_local
        return 0

    def stage_ext(blocks_local, shared, x_in, ids, labels, msk, amask,
                  cos, sin, pos0, seq_local):
        """One stage's whole per-microbatch compute: (embed |
        passthrough) -> layer slice -> (loss head on the last stage).
        Differentiable in (blocks_local, shared, x_in)."""
        stage = lax.axis_index(PIPE_AXIS)
        first, last = stage == 0, stage == S - 1
        x0 = embed_in(shared, ids, pos0, seq_local)
        x = jnp.where(first, x0, x_in)
        y, aux = stage_fwd(blocks_local, x, amask, cos, sin)
        contrib = jnp.where(last, head_nll(shared, y, labels, msk), 0.0)
        return y, contrib, aux

    # ------------------------------------------------------------- shared
    def split_params(params):
        blocks = params["blocks"]
        shared = {k: v for k, v in params.items() if k != "blocks"}
        return blocks, shared

    def batch_views(ids, labels, tgt_mask, amask):
        B, seq_local = ids.shape
        mb = B // M
        return (ids.reshape(M, mb, seq_local),
                labels.reshape(M, mb, seq_local),
                tgt_mask.reshape(M, mb, seq_local),
                amask.reshape(M, mb, seq_local), mb, seq_local)

    def mb_slice(arrs, m):
        return tuple(lax.dynamic_index_in_dim(a, m, 0, keepdims=False)
                     for a in arrs)

    perm_down = [(i, i + 1) for i in range(S - 1)]
    perm_up = [(i + 1, i) for i in range(S - 1)]

    # ===================================================== gpipe schedule
    def gpipe_loss(params, batch, rng):
        ids = batch["input_ids"]
        B, seq = ids.shape
        if (B // dp) % M:
            raise ValueError(
                f"per-dp-shard batch {B}//{dp} not divisible by "
                f"num_microbatches {M}")
        amask = batch.get("attention_mask")
        labels, tgt_mask = rolled_lm_targets(ids, amask)
        if amask is None:
            amask = jnp.ones_like(ids, jnp.float32)

        def local(blocks, shared, ids, labels, tgt_mask, amask):
            stage = lax.axis_index(PIPE_AXIS)
            last = stage == S - 1
            dt = shared["embed"]["table"].dtype
            views = batch_views(ids, labels, tgt_mask, amask)
            ids_mb, labels_mb, mask_mb, amask_mb, mb, seq_local = views
            pos0 = pos_offset(seq_local)
            cos, sin = rope_tables(pos0, seq_local)

            T = M + S - 1

            def tick(carry, t):
                buf, loss_sum, tok_sum, aux_sum, aux_n = carry
                t_here = jnp.clip(t - stage, 0, M - 1)
                i, lbl, msk, am = mb_slice(
                    (ids_mb, labels_mb, mask_mb, amask_mb), t_here)
                y, contrib, aux = stage_ext(blocks, shared, buf, i, lbl,
                                            msk, am, cos, sin, pos0,
                                            seq_local)
                # the last stage processes microbatch t-(S-1) at tick t
                valid = last & (t >= S - 1)
                contrib = jnp.where(valid, contrib, 0.0)
                toks = jnp.where(valid, msk.sum(), 0.0)
                # every stage contributes its layers' MoE aux loss for
                # the microbatch it actually processed this tick
                a_valid = (t >= stage) & (t - stage < M)
                aux_sum = aux_sum + jnp.where(a_valid, aux, 0.0)
                aux_n = aux_n + a_valid.astype(jnp.float32)
                buf_next = lax.ppermute(y, PIPE_AXIS, perm_down) \
                    if S > 1 else y
                return (buf_next, loss_sum + contrib, tok_sum + toks,
                        aux_sum, aux_n), None

            buf0 = jnp.zeros((mb, seq_local, cfg.d_model), dt)
            (_, loss_sum, tok_sum, aux_sum, aux_n), _ = lax.scan(
                tick, (buf0, jnp.float32(0.0), jnp.float32(0.0),
                       jnp.float32(0.0), jnp.float32(0.0)),
                jnp.arange(T))
            loss_sum = lax.psum(loss_sum, reduce_axes)
            tok_sum = lax.psum(tok_sum, reduce_axes)
            loss = loss_sum / jnp.maximum(tok_sum, 1.0)
            if cfg.num_experts > 1:
                # mean over (stages x microbatches x data shards) of the
                # per-stage layer-mean aux loss (reference: l_aux summed
                # into the LM loss, sharded_moe.py)
                aux_sum = lax.psum(aux_sum, reduce_axes)
                aux_n = lax.psum(aux_n, reduce_axes)
                loss = loss + cfg.aux_loss_coef * (
                    aux_sum / jnp.maximum(aux_n, 1.0))
            return loss

        blocks, shared = split_params(params)
        blocks_specs = jax.tree.map(lambda _: P(PIPE_AXIS), blocks)
        shared_specs = jax.tree.map(lambda _: P(), shared)
        return shard_map(
            local, mesh=mesh,
            in_specs=(blocks_specs, shared_specs, data_spec, data_spec,
                      data_spec, data_spec),
            out_specs=P(),
            check_vma=False)(blocks, shared, ids, labels, tgt_mask, amask)

    if schedule == "gpipe":
        return gpipe_loss

    # ====================================================== 1f1b schedule
    # fwd of mb m at stage s on tick m+s; bwd on tick m + 2(S-1) - s + 1.
    # Ring of R = min(M, 2S-1) stashed boundary activations per stage.
    R = min(M, 2 * S - 1)
    T2 = M + 2 * S - 1

    def sched_local(blocks, shared, ids, labels, tgt_mask, amask):
        """Runs the full interleaved schedule; returns per-shard
        (loss_sum, tok_sum, grad_blocks, grad_shared), all psum'd."""
        stage = lax.axis_index(PIPE_AXIS)
        last = stage == S - 1
        dt = shared["embed"]["table"].dtype
        views = batch_views(ids, labels, tgt_mask, amask)
        ids_mb, labels_mb, mask_mb, amask_mb, mb, seq_local = views
        pos0 = pos_offset(seq_local)
        cos, sin = rope_tables(pos0, seq_local)

        def run_ext(x_in, m):
            i, lbl, msk, am = mb_slice(
                (ids_mb, labels_mb, mask_mb, amask_mb), m)
            return lambda b, sh, x: stage_ext(
                b, sh, x, i, lbl, msk, am, cos, sin, pos0, seq_local)

        # tokens and aux-slot counts are needed BEFORE the schedule so
        # the eager VJP can seed ALREADY-NORMALIZED cotangents — a single
        # cotangent chain then carries both the LM and the MoE aux terms
        # masks are REPLICATED across pipe — count them once per batch
        # (and seq) shard only
        tok_global = lax.psum(tgt_mask.sum().astype(jnp.float32),
                              batch_reduce_axes)
        inv_tok = 1.0 / jnp.maximum(tok_global, 1.0)
        # every (stage, microbatch, batch shard) contributes one aux value
        n_aux = float(M * S * dp * sp)
        aux_seed = (cfg.aux_loss_coef / n_aux) \
            if cfg.num_experts > 1 else 0.0

        def tick(carry, t):
            buf_f, buf_b, stash, gb, gsh, loss_sum, aux_acc = carry

            # ---- backward slot (reads stash BEFORE this tick's fwd write)
            m_b = t - 2 * (S - 1) + stage - 1
            b_active = (m_b >= 0) & (m_b < M)
            m_b_c = jnp.clip(m_b, 0, M - 1)
            x_st = lax.dynamic_index_in_dim(stash, m_b_c % R, 0,
                                            keepdims=False)
            fn = run_ext(x_st, m_b_c)
            _, pull = jax.vjp(fn, blocks, shared, x_st)
            seed_y = jnp.where(b_active, buf_b, jnp.zeros_like(buf_b))
            seed_c = jnp.where(b_active & last, inv_tok, 0.0)
            seed_a = jnp.where(b_active, jnp.float32(aux_seed), 0.0)
            gb_m, gsh_m, x_bar = pull((seed_y.astype(dt), seed_c, seed_a))
            act = b_active.astype(jnp.float32)
            gb = jax.tree.map(lambda a, g: a + act * g.astype(jnp.float32),
                              gb, gb_m)
            gsh = jax.tree.map(lambda a, g: a + act * g.astype(jnp.float32),
                               gsh, gsh_m)
            x_bar = jnp.where(b_active, x_bar, jnp.zeros_like(x_bar))

            # ---- forward slot
            m_f = t - stage
            f_active = (m_f >= 0) & (m_f < M)
            m_f_c = jnp.clip(m_f, 0, M - 1)
            fn_f = run_ext(buf_f, m_f_c)
            y, contrib, aux = fn_f(blocks, shared, buf_f)
            valid = last & f_active
            loss_sum = loss_sum + jnp.where(valid, contrib, 0.0)
            aux_acc = aux_acc + jnp.where(f_active, aux, 0.0)
            stash = stash.at[m_f_c % R].set(
                jnp.where(f_active, buf_f, stash[m_f_c % R]))

            # ---- hand off: activation down, cotangent up.  NOTE: these
            # and the slots' collectives are mutually independent; on the
            # virtual CPU mesh this requires the sequential thunk
            # scheduler (--xla_cpu_enable_concurrency_optimized_scheduler
            # =false, see tests/conftest.py) or the in-process rendezvous
            # can deadlock.  Real TPUs are unaffected.
            buf_f_next = lax.ppermute(y, PIPE_AXIS, perm_down) \
                if S > 1 else y
            buf_b_next = lax.ppermute(x_bar, PIPE_AXIS, perm_up) \
                if S > 1 else jnp.zeros_like(x_bar)
            return (buf_f_next, buf_b_next, stash, gb, gsh,
                    loss_sum, aux_acc), None

        zeros_f32 = lambda tree: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), tree)
        buf0 = jnp.zeros((mb, seq_local, cfg.d_model), dt)
        stash0 = jnp.zeros((R, mb, seq_local, cfg.d_model), dt)
        carry0 = (buf0, jnp.zeros_like(buf0), stash0,
                  zeros_f32(blocks), zeros_f32(shared),
                  jnp.float32(0.0), jnp.float32(0.0))
        (_, _, _, gb, gsh, loss_sum, aux_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T2))

        # blocks grads: each stage owns its slice — reduce over data axes
        # only; shared grads: reduce over everything incl. pipe (the tied
        # embed/head gradient allreduce of module.py:77)
        loss = lax.psum(loss_sum, reduce_axes) * inv_tok
        if cfg.num_experts > 1:
            loss = loss + cfg.aux_loss_coef * \
                lax.psum(aux_acc, reduce_axes) / n_aux
        gb = jax.tree.map(lambda g: lax.psum(g, batch_reduce_axes), gb)
        gsh = jax.tree.map(lambda g: lax.psum(g, reduce_axes), gsh)
        return loss, gb, gsh

    def run_sched(params, batch):
        ids = batch["input_ids"]
        B, seq = ids.shape
        if (B // dp) % M:
            raise ValueError(
                f"per-dp-shard batch {B}//{dp} not divisible by "
                f"num_microbatches {M}")
        amask = batch.get("attention_mask")
        labels, tgt_mask = rolled_lm_targets(ids, amask)
        if amask is None:
            amask = jnp.ones_like(ids, jnp.float32)
        blocks, shared = split_params(params)
        blocks_specs = jax.tree.map(lambda _: P(PIPE_AXIS), blocks)
        shared_specs = jax.tree.map(lambda _: P(), shared)
        loss, gb, gsh = shard_map(
            sched_local, mesh=mesh,
            in_specs=(blocks_specs, shared_specs, data_spec, data_spec,
                      data_spec, data_spec),
            out_specs=(P(), blocks_specs, shared_specs),
            check_vma=False)(blocks, shared, ids, labels, tgt_mask, amask)
        grads = dict(gsh)
        grads["blocks"] = gb
        # cotangents were seeded pre-normalized (1/tokens for the LM
        # term, coef/n_aux for MoE) — grads are d(loss)/dp directly
        return loss, grads

    @jax.custom_vjp
    def loss_1f1b(params, batch):
        loss, _ = run_sched(params, batch)
        return loss

    def loss_1f1b_fwd(params, batch):
        loss, grads = run_sched(params, batch)
        aval = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        return loss, (grads, jax.tree.map(aval, params),
                      jax.tree.map(aval, batch))

    def loss_1f1b_bwd(res, g):
        grads, pavals, bavals = res
        pbar = jax.tree.map(lambda gr, a: (g * gr).astype(a.dtype),
                            grads, pavals)
        # batch cotangents are never consumed (grad is taken w.r.t.
        # params only): float0 for integer leaves, zeros for float ones
        bbar = jax.tree.map(
            lambda a: np.zeros(a.shape, jax.dtypes.float0)
            if jnp.issubdtype(a.dtype, jnp.integer)
            or jnp.issubdtype(a.dtype, jnp.bool_)
            else jnp.zeros(a.shape, a.dtype), bavals)
        return pbar, bbar

    loss_1f1b.defvjp(loss_1f1b_fwd, loss_1f1b_bwd)

    def loss_fn(params, batch, rng):
        return loss_1f1b(params, batch)

    # forward-only evaluation path: loss_1f1b's primal runs the FULL
    # interleaved schedule (per-tick vjp pullbacks + param-grad
    # accumulation) even when nobody wants gradients; eval_batch uses
    # the gpipe forward instead (same loss, ~half the FLOPs, O(1)
    # activation memory)
    loss_fn.eval_fn = gpipe_loss
    return loss_fn
