"""Decoder-only transformer, scan-over-layers, TPU-first.

The model-family core behind ``deepspeed_tpu.models.gpt2 / llama``:
a single configurable implementation covering the reference's training
model zoo (megatron-style GPT, llama/llama2/llama3, mistral-ish GQA — the
containers of ``module_inject/containers/`` and
``inference/v2/model_implementations/``) as *config presets* rather than
per-model classes.

TPU-first choices:
* layer params are **stacked** on a leading ``layers`` dim and the block is
  applied with ``lax.scan`` — one compiled layer body regardless of depth
  (fast compiles, natural ``jax.checkpoint`` remat point, and the natural
  unit for pipeline staging later);
* logical axes on every param (see parallel/sharding.py) give Megatron-style
  TP (column-parallel qkv/up, row-parallel out/down) with zero model code;
* attention is pluggable: XLA softmax attention today, Pallas flash /
  Ulysses all-to-all / ring attention slot in via ``attention_fn``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None        # None => MHA
    d_ff: Optional[int] = None                # None => 4*d_model (or 8/3 gated)
    max_seq_len: int = 1024
    activation: str = "gelu"
    gated_mlp: bool = False                   # SwiGLU-style (llama)
    norm: str = "layernorm"                   # layernorm | rmsnorm
    position: str = "learned"                 # learned | rope | alibi
    rope_theta: float = 10000.0
    rope_pct: float = 1.0                     # partial rotary (phi: 0.4)
    # olmoe / olmo-2: RMSNorm with a learned scale over the WHOLE q and
    # the whole k projection (all heads together), before rotary
    qk_norm: bool = False
    # bloom: layernorm applied to the word embeddings before the stack
    embed_norm: bool = False
    # parallel residual: x + attn(ln(x)) + mlp(ln(x)), one shared norm
    # (falcon, phi, gpt-j)
    parallel_block: bool = False
    # gpt-neox/pythia: parallel residual but TWO norms — the MLP reads
    # ln2(x) instead of the attention's ln1(x)
    parallel_separate_norms: bool = False
    tie_embeddings: bool = True
    attn_bias: bool = True
    # o-projection bias; None follows attn_bias (qwen2: q/k/v biases
    # but NO o bias)
    attn_out_bias: Optional[bool] = None
    mlp_bias: bool = True
    head_bias: bool = False                   # lm_head bias (phi)
    eps: float = 1e-5
    remat: bool = False                       # jax.checkpoint each layer
    remat_policy: str = "nothing"              # nothing|dots|dots_no_batch
    # xla (stock softmax autodiff) | xla_flash (flash-style custom VJP in
    # pure XLA, ops/xla_attention.py) | flash (Pallas kernel)
    attention_impl: str = "xla_flash"
    # layer-scan unroll factor (lax.scan unroll=): >1 trades compile time
    # for removing per-layer dynamic-update-slice traffic on the scan
    # carries (profiled at ~20% of a GPT-2s step on v5e)
    scan_unroll: int = 1
    # gpt-neo: attention WITHOUT the 1/sqrt(d) scaling; None = default
    attn_scale: Optional[float] = None
    # --- MoE (reference: deepspeed/moe; presets: mixtral) ----------------
    num_experts: int = 1                      # >1 => every layer is MoE
    moe_top_k: int = 2
    # qwen2-moe: a dense "shared expert" MLP of this width runs on every
    # token, sigmoid-gated, added to the routed output; None disables
    moe_shared_ff: Optional[int] = None
    # renormalize kept top-k gate weights to sum 1 (mixtral yes;
    # qwen2-moe norm_topk_prob=False keeps raw softmax probabilities)
    moe_norm_topk: bool = True
    # training only: serving routes without capacity and drops nothing
    capacity_factor: float = 1.25
    min_capacity: int = 4
    noise_policy: Optional[str] = None        # None | Jitter | RSample
    aux_loss_coef: float = 0.01
    # training only: scatter (capacity, EP-shardable) | einsum (GShard
    # dense masks) | ragged (dropless grouped GEMM via lax.ragged_dot).
    # Serving is dropless whatever this says (parallel/moe.py moe_serve)
    moe_dispatch: str = "scatter"

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.attn_out_bias is None:
            self.attn_out_bias = self.attn_bias
        if self.d_ff is None:
            if self.gated_mlp:
                # llama sizing: 2/3 * 4d, rounded up to a multiple of 256
                raw = int(8 * self.d_model / 3)
                self.d_ff = 256 * ((raw + 255) // 256)
            else:
                self.d_ff = 4 * self.d_model
        assert self.d_model % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def rotary_dim(self) -> int:
        """Head dims receiving rotary embedding (even, <= head_dim)."""
        return (int(self.head_dim * self.rope_pct) // 2) * 2


REMAT_POLICIES = {
    "nothing": None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "everything": lambda: jax.checkpoint_policies.nothing_saveable,
    # save flash-attention outputs (its VJP self-recomputes) + non-batch dots
    "flash": lambda: jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        jax.checkpoint_policies.save_only_these_names("flash_out")),
    # save the xla_flash VJP residuals (attention output + per-row lse) so
    # a checkpointed layer's backward re-enters the custom VJP instead of
    # replaying the forward softmax
    "xla_flash": lambda: jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse")),
}


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes).  Per-layer params are stacked on a
    leading 'layers' dimension (scan layout)."""
    keys = jax.random.split(key, 9)
    H, D, Hkv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    dm, dff, nl = cfg.d_model, cfg.d_ff, cfg.num_layers
    out_scale = 1.0 / math.sqrt(dm) / math.sqrt(2.0 * nl)   # GPT-2 depth scaling

    def stack_init(fn, key, *args, **kw):
        """Init one layer's worth with per-layer keys, stacked on dim 0."""
        ks = jax.random.split(key, nl)
        outs = [fn(k, *args, **kw) for k in ks]
        p0, a0 = outs[0]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
        axes = jax.tree.map(lambda ax: ("layers",) + ax, a0,
                            is_leaf=lambda x: isinstance(x, tuple) and
                            all(e is None or isinstance(e, str) for e in x))
        return stacked, axes

    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    params["embed"], axes["embed"] = L.embedding_init(keys[0], cfg.vocab_size, dm)
    if cfg.position == "learned":
        params["pos_embed"], axes["pos_embed"] = (
            {"table": jax.random.normal(keys[1], (cfg.max_seq_len, dm)) * 0.01},
            {"table": (None, "embed")})
    if cfg.embed_norm:                      # bloom word_embeddings_layernorm
        _ninit = (L.layernorm_init if cfg.norm == "layernorm"
                  else L.rmsnorm_init)
        params["ln_embed"], axes["ln_embed"] = _ninit(dm)

    blk_p: Dict[str, Any] = {}
    blk_a: Dict[str, Any] = {}

    # attention — fused qkv as separate heads-aware tensors
    def qkv_init(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        p, a = {}, {}
        p["wq"] = jax.random.normal(k1, (dm, H, D)) / math.sqrt(dm)
        a["wq"] = ("embed", "heads", "head_dim")
        p["wk"] = jax.random.normal(k2, (dm, Hkv, D)) / math.sqrt(dm)
        a["wk"] = ("embed", "kv_heads", "head_dim")
        p["wv"] = jax.random.normal(k3, (dm, Hkv, D)) / math.sqrt(dm)
        a["wv"] = ("embed", "kv_heads", "head_dim")
        p["wo"] = jax.random.normal(k4, (H, D, dm)) * out_scale
        a["wo"] = ("heads", "head_dim", "embed")
        if cfg.attn_bias:
            p["bq"] = jnp.zeros((H, D)); a["bq"] = ("heads", "head_dim")
            p["bk"] = jnp.zeros((Hkv, D)); a["bk"] = ("kv_heads", "head_dim")
            p["bv"] = jnp.zeros((Hkv, D)); a["bv"] = ("kv_heads", "head_dim")
        if cfg.attn_out_bias:
            p["bo"] = jnp.zeros((dm,)); a["bo"] = ("embed",)
        if cfg.qk_norm:
            # seeded scales in (0.5, 1.5), not ones: on random weights a
            # projection's mean square is already near 1, so a forward
            # that left the norm out would pass every comparison.  Their
            # keys are a stream of their own off the layer's key: splitting
            # ``k`` six ways above would re-seed every model's attention
            k5, k6 = jax.random.split(jax.random.fold_in(  # tpulint: disable=rng-discipline
                k, 1))
            p["q_norm"] = jax.random.uniform(k5, (H, D), minval=0.5,
                                             maxval=1.5)
            a["q_norm"] = ("heads", "head_dim")
            p["k_norm"] = jax.random.uniform(k6, (Hkv, D), minval=0.5,
                                             maxval=1.5)
            a["k_norm"] = ("kv_heads", "head_dim")
        return p, a

    blk_p["attn"], blk_a["attn"] = stack_init(qkv_init, keys[2])

    if cfg.num_experts > 1:
        from ..parallel import moe as M

        blk_p["gate"], blk_a["gate"] = stack_init(
            lambda k: M.gate_init(k, dm, cfg.num_experts), keys[7])
        blk_p["experts"], blk_a["experts"] = stack_init(
            lambda k: M.experts_init(k, cfg.num_experts, dm, dff,
                                     gated=cfg.gated_mlp,
                                     out_scale=out_scale), keys[3])
        if cfg.moe_shared_ff:        # qwen2-moe dense shared expert
            sff = cfg.moe_shared_ff

            def shared_init(k):
                k1, k2, k3, k4 = jax.random.split(k, 4)
                p = {"wi": jax.random.normal(k1, (dm, sff))
                     / math.sqrt(dm),
                     "wo": jax.random.normal(k2, (sff, dm)) * out_scale}
                a = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
                if cfg.gated_mlp:
                    p["wg"] = jax.random.normal(k3, (dm, sff)) \
                        / math.sqrt(dm)
                    a["wg"] = ("embed", "mlp")
                p["gate"] = jax.random.normal(k4, (dm, 1)) / math.sqrt(dm)
                a["gate"] = ("embed", None)
                return p, a

            blk_p["shared"], blk_a["shared"] = stack_init(
                shared_init, keys[8])

    def mlp_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        p, a = {}, {}
        p["wi"] = jax.random.normal(k1, (dm, dff)) / math.sqrt(dm)
        a["wi"] = ("embed", "mlp")
        if cfg.gated_mlp:
            p["wg"] = jax.random.normal(k3, (dm, dff)) / math.sqrt(dm)
            a["wg"] = ("embed", "mlp")
        p["wo"] = jax.random.normal(k2, (dff, dm)) * out_scale
        a["wo"] = ("mlp", "embed")
        if cfg.mlp_bias:
            p["bi"] = jnp.zeros((dff,)); a["bi"] = ("mlp",)
            p["bo"] = jnp.zeros((dm,)); a["bo"] = ("embed",)
        return p, a

    if cfg.num_experts <= 1:
        blk_p["mlp"], blk_a["mlp"] = stack_init(mlp_init, keys[3])

    norm_init = L.layernorm_init if cfg.norm == "layernorm" else L.rmsnorm_init
    blk_p["ln1"], blk_a["ln1"] = stack_init(
        lambda k: norm_init(dm), keys[4])
    if not cfg.parallel_block or cfg.parallel_separate_norms:
        blk_p["ln2"], blk_a["ln2"] = stack_init(
            lambda k: norm_init(dm), keys[5])

    params["blocks"] = blk_p
    axes["blocks"] = blk_a

    params["ln_f"], axes["ln_f"] = norm_init(dm)
    if not cfg.tie_embeddings:
        params["lm_head"], axes["lm_head"] = (
            {"kernel": jax.random.normal(keys[6], (dm, cfg.vocab_size))
             / math.sqrt(dm)},
            {"kernel": ("embed", "vocab")})
        if cfg.head_bias:
            params["lm_head"]["bias"] = jnp.zeros((cfg.vocab_size,))
            axes["lm_head"]["bias"] = ("vocab",)
    return params, axes


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Placement:
    """What the caller states about where things live inside the forward.
    The model knows no mesh: it calls these at the points that matter and
    the caller (``ZeroPolicy.placement`` under ZeRO stage 3) decides what
    they constrain.

    ``use(name, subtree, layer_slice=False)``: ``params[name]`` (one
    layer's slice of it inside the scan) as its uses read it.
    ``keep(x)``: an activation whose dim 0 is the batch, kept split over it.
    """
    use: Callable[..., Any]
    keep: Callable[[Any], Any]


_AS_IS = Placement(use=lambda name, sub, layer_slice=False: sub,
                   keep=lambda x: x)


def _norm(cfg):
    fn = L.layernorm if cfg.norm == "layernorm" else L.rmsnorm
    return partial(fn, eps=cfg.eps)


def _shared_expert(sp, h, act, gated: bool):
    """qwen2-moe dense shared expert: a full MLP on every token, scaled
    by a per-token sigmoid gate (reference analog: the qwen_v2_moe v2
    model implementation's shared_expert path)."""
    dt = h.dtype
    u = h @ sp["wi"].astype(dt)
    u = act(h @ sp["wg"].astype(dt)) * u if "wg" in sp else act(u)
    d = u @ sp["wo"].astype(dt)
    g = jax.nn.sigmoid((h @ sp["gate"].astype(dt)).astype(jnp.float32))
    return d * g.astype(dt)


def block_apply(cfg: TransformerConfig, lp, x, cos, sin,
                mask=None, attention_fn: Callable = L.causal_attention,
                rng=None, positions=None):
    """One decoder layer. lp: this layer's (unstacked) params.
    x: [B, S, dm].  ``positions``: optional [B, S] original token
    positions (random-LTD gathered subsequences keep their rotary
    phases).  Returns (x, metrics) — metrics non-empty for MoE."""
    norm = _norm(cfg)
    act = L.ACTIVATIONS[cfg.activation]
    ap = lp["attn"]
    if cfg.attn_scale is not None and attention_fn is L.causal_attention:
        # safety net for call sites that never resolved attention_fn
        # (pipeline stage bodies, streamed sweeps): gpt-neo's unscaled
        # attention must not silently regain the 1/sqrt(d) factor
        attention_fn = partial(L.causal_attention, scale=cfg.attn_scale)

    h = norm(lp["ln1"], x)
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", h, ap["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", h, ap["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, ap["wv"].astype(dt))
    if cfg.attn_bias:
        q = q + ap["bq"].astype(dt)
        k = k + ap["bk"].astype(dt)
        v = v + ap["bv"].astype(dt)
    if cfg.qk_norm:
        q = L.qk_rmsnorm(ap["q_norm"], q, cfg.eps)
        k = L.qk_rmsnorm(ap["k_norm"], k, cfg.eps)
    if cfg.position == "rope":
        q = L.apply_rope(q, cos, sin, positions=positions)
        k = L.apply_rope(k, cos, sin, positions=positions)
    o = attention_fn(q, k, v, mask=mask)
    o = jnp.einsum("bshk,hkd->bsd", o, ap["wo"].astype(dt))
    if cfg.attn_out_bias:
        o = o + ap["bo"].astype(dt)

    if not cfg.parallel_block:
        x = x + o
        h = norm(lp["ln2"], x)
    elif cfg.parallel_separate_norms:
        # gpt-neox: the MLP reads its own norm of the ORIGINAL x
        h = norm(lp["ln2"], x)
    # parallel residual (falcon/phi): the MLP reads the same ln1 output
    metrics: Dict[str, Any] = {}
    if cfg.num_experts > 1:
        from ..parallel import moe as M

        d, metrics = M.moe_ffn(
            lp["gate"], lp["experts"], h, top_k=cfg.moe_top_k,
            capacity_factor=cfg.capacity_factor,
            min_capacity=cfg.min_capacity, activation=act,
            gated=cfg.gated_mlp, rng=rng, noise_policy=cfg.noise_policy,
            dispatch_mode=cfg.moe_dispatch,
            norm_topk=cfg.moe_norm_topk)
        if "shared" in lp:       # qwen2-moe sigmoid-gated shared expert
            d = d + _shared_expert(lp["shared"], h, act, cfg.gated_mlp)
    else:
        mp = lp["mlp"]
        u = h @ mp["wi"].astype(dt)
        if cfg.mlp_bias:
            u = u + mp["bi"].astype(dt)
        if cfg.gated_mlp:
            u = act(h @ mp["wg"].astype(dt)) * u
        else:
            u = act(u)
        d = u @ mp["wo"].astype(dt)
        if cfg.mlp_bias:
            d = d + mp["bo"].astype(dt)
    if cfg.parallel_block:
        return x + o + d, metrics
    return x + d, metrics


def apply(cfg: TransformerConfig, params, input_ids, mask=None,
          attention_fn: Callable = L.causal_attention,
          dtype=None, rng=None, with_aux: bool = False,
          pld_theta=None, ltd_keep: Optional[int] = None,
          placement: Optional[Placement] = None):
    """Forward pass → logits [B, S, vocab] (or (logits, aux) with
    with_aux=True; aux carries MoE load-balancing metrics averaged over
    layers).

    ``pld_theta``: progressive-layer-drop theta (traced scalar; layer i
    is dropped whole-batch with prob (i/L)(1-theta) — reference:
    progressive_layer_drop.py consumed by the BERT forward).
    ``ltd_keep``: random-LTD kept-token count (STATIC int — one compiled
    program per value): a sorted random subset of positions runs through
    the layer stack, dropped positions bypass with their embedding
    (reference: data_routing/basic_layer.py gather/scatter).
    ``placement``: see :class:`Placement`; None states nothing."""
    pl = placement or _AS_IS
    use, keep = pl.use, pl.keep
    dt = dtype or params["embed"]["table"].dtype
    x = L.embed(use("embed", params["embed"]), input_ids).astype(dt)
    if cfg.embed_norm:
        x = _norm(cfg)(use("ln_embed", params["ln_embed"]), x)
    if cfg.position == "learned":
        S = input_ids.shape[1]
        x = x + use("pos_embed",
                    params["pos_embed"])["table"][:S].astype(dt)
        cos = sin = None
    elif cfg.position == "alibi":
        cos = sin = None
        # safety net for direct apply() calls: the default eager
        # attention gains the ALiBi bias (Model wraps attention_fn too)
        if attention_fn is L.causal_attention:
            attention_fn = L.make_alibi_attention()
    else:
        cos, sin = L.rope_freqs(cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta)

    have_rng = rng is not None
    if (pld_theta is not None or ltd_keep is not None) and not have_rng:
        raise ValueError("pld_theta / ltd_keep need a training rng")

    positions = None
    full_x = None
    idx = None
    if ltd_keep is not None and ltd_keep < x.shape[1]:
        from ..runtime.data_pipeline import (random_ltd_scatter,
                                             random_ltd_select)
        rng, sel_rng = jax.random.split(rng)
        full_x = x
        x, idx = random_ltd_select(x, ltd_keep, sel_rng)
        positions = idx
        if mask is not None:
            mask = jnp.take_along_axis(mask, idx, axis=1)

    layer_rngs = (jax.random.split(rng, cfg.num_layers) if have_rng
                  else jnp.zeros((cfg.num_layers, 2), jnp.uint32))

    def body(h, xs):
        lp, r, li = xs
        # inside the (checkpointed) body, so the recomputation and the
        # backward state the same as the forward
        y, metrics = block_apply(cfg, use("blocks", lp, layer_slice=True),
                                 keep(h), cos, sin, mask=mask,
                                 attention_fn=attention_fn,
                                 rng=r if have_rng else None,
                                 positions=positions)
        y = keep(y)
        if pld_theta is not None:
            # whole-batch per-layer coin; deeper layers drop more
            keep_p = 1.0 - (li.astype(jnp.float32) / cfg.num_layers) \
                * (1.0 - pld_theta)
            drop = jax.random.bernoulli(
                jax.random.fold_in(r, 1), 1.0 - keep_p)
            y = jnp.where(drop, h, y)
        return y, metrics

    if cfg.remat:
        policy = REMAT_POLICIES[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy() if policy else None)

    x, metrics = jax.lax.scan(
        body, keep(x),
        (params["blocks"], layer_rngs,
         jnp.arange(cfg.num_layers, dtype=jnp.int32)),
        unroll=min(cfg.scan_unroll, cfg.num_layers))
    if idx is not None:
        # dropped positions bypass the stack with their embedding
        x = random_ltd_scatter(full_x, x, idx)
    x = _norm(cfg)(use("ln_f", params["ln_f"]), x)
    if cfg.tie_embeddings:
        logits = x @ use("embed", params["embed"])["table"].astype(dt).T
    else:
        head = use("lm_head", params["lm_head"])
        logits = x @ head["kernel"].astype(dt)
        if cfg.head_bias:
            logits = logits + head["bias"].astype(dt)
    logits = keep(logits)
    if with_aux:
        aux = {k: v.mean() for k, v in metrics.items()} if metrics else {}
        return logits, aux
    return logits


def rolled_lm_targets(ids, mask=None):
    """Next-token targets by rolling left with the final position masked —
    equivalent to the shift-by-one convention but length-preserving, so it
    divides evenly under sequence/pipeline sharding.  Returns
    (labels, target_mask)."""
    labels = jnp.roll(ids, -1, axis=1)
    S = ids.shape[1]
    tgt_mask = jnp.broadcast_to(
        (jnp.arange(S) < S - 1).astype(jnp.float32)[None, :], ids.shape)
    if mask is not None:
        tgt_mask = tgt_mask * jnp.roll(mask, -1, axis=1)
    return labels, tgt_mask


def cross_entropy_loss(logits, labels, mask=None,
                       keep: Callable = lambda x: x):
    """Next-token LM loss; logits [B,S,V], labels [B,S].  ``keep``:
    :attr:`Placement.keep` for the per-token terms.

    Written as ``lse - target_logit`` with fp32 *reductions* rather than
    ``log_softmax`` so XLA fuses the bf16→fp32 convert into the reduce and
    never materializes an fp32 [B,S,V] buffer (6.6 GB for GPT-2 vocab at
    batch 32·1024 — the difference between fitting in HBM or not)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = keep(lse - tgt.astype(jnp.float32))
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def lm_loss_fn(cfg: TransformerConfig,
               attention_fn: Callable = L.causal_attention,
               pld: bool = False, ltd_keep: Optional[int] = None,
               placement: Optional[Placement] = None):
    """Standard causal-LM loss over a batch {input_ids, [attention_mask]}.

    ``pld``: consume the engine-injected per-row ``_pld_theta`` column
    (progressive layer drop).  ``ltd_keep``: bake a static random-LTD
    kept-token count; the engine swaps programs via ``with_ltd`` as the
    schedule anneals.  ``placement``: what the engine states about
    parameters and activations (:class:`Placement`), handed over through
    ``with_placement``."""

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        mask = batch.get("attention_mask")
        theta = batch["_pld_theta"][0] if pld else None
        logits, aux = apply(cfg, params, ids, mask=mask,
                            attention_fn=attention_fn, rng=rng,
                            with_aux=True, pld_theta=theta,
                            ltd_keep=ltd_keep, placement=placement)
        labels, tgt_mask = rolled_lm_targets(ids, mask)
        loss = cross_entropy_loss(logits, labels, tgt_mask,
                                  keep=(placement or _AS_IS).keep)
        if "moe_aux_loss" in aux:
            loss = loss + cfg.aux_loss_coef * aux["moe_aux_loss"]
            return loss, aux
        return loss

    loss_fn.uses_pld = pld
    loss_fn.with_ltd = lambda keep: lm_loss_fn(
        cfg, attention_fn, pld=pld, ltd_keep=keep, placement=placement)
    loss_fn.with_placement = lambda pl: lm_loss_fn(
        cfg, attention_fn, pld=pld, ltd_keep=ltd_keep, placement=pl)
    if pld or ltd_keep is not None:
        # evaluation must run the clean forward: no theta column in eval
        # batches, no token dropping skewing eval losses
        loss_fn.base_eval = lm_loss_fn(cfg, attention_fn,
                                       placement=placement)
    return loss_fn


def _resolve_attention(cfg: TransformerConfig) -> Callable:
    """attention_impl -> callable; ALiBi wraps the eager attention with
    the per-head bias (the flash kernels have no bias operand)."""
    if cfg.attn_scale is not None and cfg.attention_impl in (
            "flash", "xla_flash"):
        raise ValueError(
            "attn_scale needs the eager attention (attention_impl="
            "'xla'): the flash kernels bake in 1/sqrt(d)")
    if cfg.position == "alibi":
        if cfg.attention_impl in ("flash", "xla_flash"):
            raise ValueError(
                "position='alibi' needs the eager attention "
                "(attention_impl='xla'): the flash kernels carry no "
                "additive-bias operand")
        fn = L.make_alibi_attention()
    elif cfg.attention_impl == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention
    elif cfg.attention_impl == "xla_flash":
        from ..ops.xla_attention import fused_attention
        return fused_attention
    else:
        fn = L.causal_attention
    if cfg.attn_scale is not None:
        base = fn
        s = cfg.attn_scale

        def fn(q, k, v, mask=None, **kw):        # gpt-neo: no 1/sqrt(d)
            return base(q, k, v, mask=mask, scale=s, **kw)
    return fn


class Model:
    """Bundles config+params+loss for ``deepspeed_tpu.initialize(model=…)``."""

    def __init__(self, cfg: TransformerConfig, seed: int = 0,
                 attention_fn: Optional[Callable] = None):
        self.config = cfg
        if attention_fn is None:
            attention_fn = _resolve_attention(cfg)
        self.params, self.param_axes = init_params(cfg, jax.random.PRNGKey(seed))
        self.loss_fn = lm_loss_fn(cfg, attention_fn)
        self.attention_fn = attention_fn

    def apply(self, params, input_ids, **kw):
        kw.setdefault("attention_fn", self.attention_fn)
        return apply(self.config, params, input_ids, **kw)

    @classmethod
    def from_params(cls, cfg: TransformerConfig, params,
                    param_axes=None,
                    attention_fn: Optional[Callable] = None) -> "Model":
        """Build a Model around EXISTING parameters without running the
        initializer (big-model flows: pre-quantized serving trees,
        host-loaded checkpoints — the 16 GB+ random init would otherwise
        dominate or OOM)."""
        m = cls.__new__(cls)
        m.config = cfg
        if attention_fn is None:
            attention_fn = _resolve_attention(cfg)
        m.params = params
        if param_axes is None:
            from ..parallel.sharding import infer_logical_axes
            param_axes = infer_logical_axes(params)
        m.param_axes = param_axes
        m.loss_fn = lm_loss_fn(cfg, attention_fn)
        m.attention_fn = attention_fn
        return m
