"""Decoder-only transformer, scan-over-layers, TPU-first.

The model-family core behind ``deepspeed_tpu.models.gpt2 / llama``:
a single configurable implementation covering the reference's training
model zoo (megatron-style GPT, llama/llama2/llama3, mistral-ish GQA — the
containers of ``module_inject/containers/`` and
``inference/v2/model_implementations/``) as *config presets* rather than
per-model classes.

TPU-first choices:
* layer params are **stacked** on a leading ``layers`` dim and the block is
  applied with ``lax.scan``: one traced layer body regardless of depth, the
  natural ``jax.checkpoint`` remat point and the natural unit for pipeline
  staging.  Up to ``UNROLL_MAX_LAYERS`` layers (12) the scan is unrolled
  whole, so the compiled program holds a copy of the body per layer and no
  per-layer dynamic slice or update of a stack; a deeper model keeps the
  rolled loop (one compiled body, fast compiles);
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
    # a head's size; None => d_model // num_heads (trinity: 32 heads of
    # 128 over d_model 2048, so H * D is 4096 and not d_model)
    head_dim: Optional[int] = None
    d_ff: Optional[int] = None                # None => 4*d_model (or 8/3 gated)
    max_seq_len: int = 1024
    activation: str = "gelu"
    gated_mlp: bool = False                   # SwiGLU-style (llama)
    norm: str = "layernorm"                   # layernorm | rmsnorm
    # learned | rope | alibi | none (granite-4.0-h: no positions at all,
    # neither added to the stream nor rotated into q and k)
    position: str = "learned"
    rope_theta: float = 10000.0
    rope_pct: float = 1.0                     # partial rotary (phi: 0.4)
    # YaRN over the rotated dims (``layers.Yarn``'s fields in order, as
    # the presets write them); None: the plain table.  For "mla" layers:
    # the softmax scale's multiplier lives in ``MLADims.scale``
    rope_yarn: Optional[L.Yarn] = None
    # olmoe / olmo-2: RMSNorm with a learned scale over the WHOLE q and
    # the whole k projection (all heads together), before rotary
    qk_norm: bool = False
    # "projection": that whole-projection norm.  "head" (trinity/afmoe):
    # an RMSNorm per head over head_dim, ONE learned [head_dim] scale for
    # all the query heads and one for the key heads
    qk_norm_form: str = "projection"
    # --- the layer pattern ------------------------------------------------
    # the kind of each layer of one period: "full" (attention over every
    # key up to the query) | "window" (the last ``attn_window`` keys, the
    # query's own among them) | "hybrid" (falcon-h1: full attention AND
    # a Mamba-2 mixer, both reading the same normed input, summed into
    # the residual) | "kda" (delta-rule linear attention with a
    # per-channel decay, ops/kda.py, in place of attention) | "mla"
    # (latent attention, ops/mla.py: one cached vector a token) |
    # "mamba" (granite-4.0-h: a Mamba-2 mixer ALONE in place of
    # attention, ops/ssm.py: a state and no blocks).  The
    # layers behind the leading dense ones repeat it; a last period may
    # be cut short.  The leading dense layers are of the period's first
    # kind.  A model with "kda", "mla" or "mamba" layers stacks each
    # kind's mixer weights apart (``mixer_stacks``; its "full" layers'
    # attention too), a layer reading its kind's stack at its rank among
    # the layers of that kind
    layer_pattern: Tuple[str, ...] = ("full",)
    attn_window: Optional[int] = None
    # sparse-expert models: this many FIRST layers keep a dense MLP of
    # width d_ff (their weights: params["dense_blocks"])
    num_dense_layers: int = 0
    # the layer kinds whose q and k get the rotary embedding; None: all
    # (trinity: window layers only, full layers carry no positions)
    rope_kinds: Optional[Tuple[str, ...]] = None
    # sigmoid(h Wg) [H * D] multiplied into the attention output before
    # the output projection
    attn_gate: bool = False
    # an "mla" layer's output gate: "head" (sigmoid(h Wy) [H], one
    # number a head, where ``attn_gate``'s is one an element) | None
    mla_gate: Optional[str] = None
    # --- a "kda" layer's mixer (ops/kda.py) -------------------------------
    kda_heads: int = 0
    kda_key_dim: int = 0                      # a head's state is [K, V]
    kda_value_dim: int = 0
    kda_conv: int = 4                         # the causal convolution's width
    kda_chunk: int = 64                       # the chunked form's chunk
    kda_gate_bound: float = -5.0              # log decay in (bound, 0)
    # --- an "mla" layer's sizes (ops/mla.py) ------------------------------
    mla_kv_rank: int = 0                      # the cached latent's size
    mla_nope_dim: int = 0                     # a head's unrotated q/k part
    mla_rope_dim: int = 0                     # the rotated part; ONE shared k
    mla_value_dim: int = 0
    # a query latent (DeepSeek-V3's form): q = N_q(h W_qa) W_qb over this
    # many values; 0: q = h W_q in one product
    mla_q_rank: int = 0
    # constant multipliers on the normed latents: sqrt(d_model / rank) on
    # the query's and on the keys' and values' (not on the rotated key)
    mla_scale_latents: bool = False
    # four norms a layer: x + N(attn(N(x))), then x + N(ffn(N(x)))
    sandwich_norm: bool = False
    # the embedding's output is multiplied by this (trinity: sqrt(d_model);
    # falcon-h1: embedding_multiplier)
    embed_scale: Optional[float] = None
    # --- a "hybrid" or "mamba" layer's Mamba-2 mixer (ops/ssm.py) ---------
    ssm_d: int = 0                            # d_ssm = heads * head size
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1                       # B and C are shared by group
    ssm_state: int = 0                        # N, a head's state is [P, N]
    ssm_conv: int = 4                         # the causal convolution's width
    ssm_chunk: int = 128                      # the chunked form's chunk
    # --- constant multipliers (falcon-h1's muP; 1.0 multiplies nothing) ---
    head_scale: float = 1.0                   # the logits
    attn_in_scale: float = 1.0                # the attention's input
    attn_out_scale: float = 1.0               # and its output projection's
    key_scale: float = 1.0                    # the keys
    ssm_in_scale: float = 1.0                 # the mixer's input
    ssm_out_scale: float = 1.0                # and its output projection's
    mlp_gate_scale: float = 1.0               # the MLP's gate, inside the act
    mlp_out_scale: float = 1.0                # the MLP's output
    # over the columns of the mixer's input projection: z, x, B, C, dt
    ssm_col_scales: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    # ONE multiplier on both residual branches of every layer (granite:
    # x + r * mixer(N(x)), then x + r * ffn(N(x)))
    residual_scale: float = 1.0
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
    # gpt-neo: attention WITHOUT the 1/sqrt(d) scaling; None = default
    attn_scale: Optional[float] = None
    # --- MoE (reference: deepspeed/moe; presets: mixtral) ----------------
    num_experts: int = 1                      # >1 => every layer is MoE
    moe_top_k: int = 2
    # qwen2-moe: a dense "shared expert" MLP of this width runs on every
    # token, sigmoid-gated, added to the routed output; None disables
    moe_shared_ff: Optional[int] = None
    # False: the shared expert's output is added as it is (trinity)
    moe_shared_gate: bool = True
    # an expert's width; None => d_ff (a model with leading dense layers
    # has both: d_ff is the dense MLP's)
    moe_d_ff: Optional[int] = None
    # the router's scores: softmax over the experts | sigmoid of each
    moe_score: str = "softmax"
    # a learned per-expert bias added to the scores for the CHOICE of the
    # top-k only; the weights are the unbiased scores (the bias update
    # that balances load in training is not implemented)
    moe_select_bias: bool = False
    # the chosen weights are multiplied by this after renormalisation
    moe_route_scale: float = 1.0
    # the experts in order form this many groups; a token chooses among
    # the experts of the ``moe_groups_kept`` groups that score highest.
    # ``moe_group_score``: a group's score is the sum of its two best
    # (biased) scores, "top2" (DeepSeek-V3's ``noaux_tc``), or its best
    # score, "max" (DeepSeek-V2's ``group_limited_greedy``: a group is
    # the experts of one device, and ``experts_held`` is whole groups)
    moe_groups: int = 1
    moe_groups_kept: int = 1
    moe_group_score: str = "top2"
    # ``(first, count)``: the experts whose weights THIS model instance
    # holds, a chip's share of an expert layer spread over several.  The
    # router keeps its ``num_experts`` outputs and its ``moe_top_k`` a
    # token; an assignment to an expert that is not held adds nothing
    # here.  None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # experts that compute nothing: the router has this many outputs
    # BEHIND its ``num_experts`` (which count the experts with weights,
    # as ``experts_held`` does); a token that takes one gets its input
    # back, times the weight
    moe_zero_experts: int = 0
    # a shortcut-connected expert layer: a period of ``layer_pattern``
    # is ONE layer of two sublayers, each an attention and a dense MLP;
    # the experts read the first sublayer's normed MLP input and their
    # output joins the stream at the period's end.  ``num_layers``
    # counts sublayers; the router and the experts are stacked a period
    moe_shortcut: bool = False
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
        if self.head_dim is None:
            assert self.d_model % self.num_heads == 0
            self.head_dim = self.d_model // self.num_heads
        assert self.num_heads % self.num_kv_heads == 0
        self.layer_pattern = tuple(self.layer_pattern)
        kinds = set(self.layer_pattern)
        assert kinds <= {"full", "window", "hybrid", "kda", "mla", "mamba"}
        assert self.position in ("learned", "rope", "alibi", "none")
        self.ssm_col_scales = tuple(self.ssm_col_scales)
        if "hybrid" in kinds:
            assert self.layer_pattern == ("hybrid",) \
                and self.num_experts == 1 and not self.parallel_block
        if kinds & {"hybrid", "mamba"}:
            assert self.ssm_d == self.ssm_heads * self.ssm_head_dim > 0
            assert self.ssm_heads % self.ssm_groups == 0 and self.ssm_state
            assert len(self.ssm_col_scales) == 5
        if self.mixer_stacks:
            assert kinds <= {"kda", "mla", "mamba", "full"} \
                and not self.parallel_block and not self.sandwich_norm \
                and self.position in ("rope", "none")
            # one pool: no second kind among the layers that hold blocks
            assert not {"mla", "full"} <= kinds
            if "kda" in kinds:
                assert self.kda_heads and self.kda_key_dim \
                    and self.kda_value_dim and self.kda_chunk % 16 == 0
            if "mla" in kinds:
                assert self.mla_kv_rank and self.mla_value_dim \
                    and self.mla_rope_dim == self.rotary_dim \
                    and self.mla_gate in (None, "head")
        if self.rope_yarn is not None:
            self.rope_yarn = L.Yarn(*self.rope_yarn)   # its fields in order
            assert self.position == "rope" and kinds == {"mla"}, \
                "rope_yarn is written for latent layers alone"
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
            first, count = self.experts_held
            assert 0 <= first and count > 0 \
                and first + count <= self.num_experts
        assert self.num_experts % self.moe_groups == 0 \
            and 1 <= self.moe_groups_kept <= self.moe_groups
        assert self.moe_group_score in ("top2", "max")
        if self.held_groups is not None:
            # a group is a device's experts: a share holds whole groups
            per = self.num_experts // self.moe_groups
            first, count = self.experts_held
            if first % per or count % per:
                raise ValueError(
                    f"experts_held={self.experts_held} splits a group of "
                    f"{per} experts (moe_groups={self.moe_groups}, "
                    "moe_group_score='max')")
        assert "window" not in self.layer_pattern or self.attn_window
        assert self.qk_norm_form in ("projection", "head")
        assert self.moe_score in ("softmax", "sigmoid")
        assert 0 <= self.num_dense_layers <= self.num_layers
        assert self.num_dense_layers == 0 or self.num_experts > 1
        assert self.moe_zero_experts == 0 or self.num_experts > 1
        if self.moe_shortcut:
            # the one form written: two stacked-mixer sublayers a layer
            assert self.num_experts > 1 and self.mixer_stacks \
                and len(self.layer_pattern) == 2 \
                and not self.num_dense_layers and not self.moe_shared_ff \
                and self.num_layers % 2 == 0
        if self.moe_d_ff is None:
            self.moe_d_ff = self.d_ff

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The attention kind of every layer, first to last."""
        p = self.layer_pattern
        return (p[0],) * self.num_dense_layers + tuple(
            p[i % len(p)] for i in range(self.num_layers
                                         - self.num_dense_layers))

    @property
    def layer_plan(self) -> Tuple[int, int, int]:
        """``(lead, periods, tail)``: the leading dense layers, the whole
        periods of ``layer_pattern`` behind them (the layer scan's trip
        count) and the layers of a last period cut short.  Both forwards
        read their structure from this."""
        rest = self.num_layers - self.num_dense_layers
        p = len(self.layer_pattern)
        return self.num_dense_layers, rest // p, rest % p

    @property
    def has_ssm(self) -> bool:
        """The model's layers hold a recurrent kind: a served sequence
        owns a state row beside its block table."""
        return self.recurrent_kind is not None

    @property
    def recurrent_kind(self) -> Optional[str]:
        """The layer kind that keeps a recurrent state, or None."""
        return next((k for k in ("hybrid", "kda", "mamba")
                     if k in self.layer_pattern), None)

    @property
    def mixer_stacks(self) -> Tuple[str, ...]:
        """The layer kinds whose mixer weights are stacked apart, by
        kind (``params["blocks"][kind]``); () for a model whose layers
        all hold one ``"attn"`` stack.  Beside a kind that holds no
        attention, a "full" layer's attention is such a stack too."""
        stacks = tuple(k for k in ("kda", "mla", "mamba")
                       if k in self.layer_pattern)
        return stacks + (("full",) if stacks and "full" in self.layer_pattern
                         else ())

    def kind_rank(self, layer: int, of: Optional[str] = None,
                  first: int = 0) -> int:
        """How many layers of kind ``of`` (default: layer ``layer``'s
        own) lie in ``[first, layer)``: a layer's rank among its kind,
        in the model (``first=0``) or in its stack."""
        kinds = self.layer_kinds
        of = of or kinds[layer]
        return sum(1 for k in kinds[first:layer] if k == of)

    def layers_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)

    @property
    def block_layers(self) -> int:
        """The layers that hold blocks of the paged pool: all of them,
        or in a model whose mixers are stacked by kind the "mla" or
        "full" ones (a layer holds ONE kind of cache)."""
        if not self.mixer_stacks:
            return self.num_layers
        return self.layers_of("mla") + self.layers_of("full")

    @property
    def kda_dims(self):
        from ..ops.kda import KDADims
        return KDADims(self.kda_heads, self.kda_key_dim, self.kda_value_dim,
                       self.kda_conv, self.kda_chunk, 16,
                       self.kda_gate_bound)

    @property
    def mla_dims(self):
        from ..ops.mla import MLADims
        dims = MLADims(self.num_heads, self.mla_kv_rank, self.mla_nope_dim,
                       self.mla_rope_dim, self.mla_value_dim,
                       self.mla_q_rank)
        if self.mla_scale_latents:
            dims = dims._replace(
                q_scale=math.sqrt(self.d_model / self.mla_q_rank),
                kv_scale=math.sqrt(self.d_model / self.mla_kv_rank))
        if self.rope_yarn is not None:
            dims = dims._replace(score_scale=self.rope_yarn.score_scale)
        return dims

    @property
    def held_groups(self) -> Optional[Tuple[int, int]]:
        """``(first, count)``: the expert groups a share holds where the
        router limits a token to devices' groups ("max") and
        ``experts_held`` says which are here; None for every other
        model."""
        if self.moe_groups <= 1 or self.moe_group_score != "max" \
                or self.experts_held is None:
            return None
        per = self.num_experts // self.moe_groups
        return self.experts_held[0] // per, self.experts_held[1] // per

    @property
    def router_outputs(self) -> int:
        """The router's width: the experts and the zero-compute ones."""
        return self.num_experts + self.moe_zero_experts

    @property
    def expert_layers(self) -> int:
        """The layers that hold a router and experts."""
        n = self.num_layers - self.num_dense_layers
        return n // len(self.layer_pattern) if self.moe_shortcut else n

    @property
    def experts_here(self) -> int:
        """Experts whose weights the model holds."""
        return self.experts_held[1] if self.experts_held \
            else self.num_experts

    @property
    def ssm_dims(self):
        from ..ops.ssm import SSMDims
        return SSMDims(self.ssm_d, self.ssm_heads, self.ssm_head_dim,
                       self.ssm_groups, self.ssm_state, self.ssm_conv,
                       self.ssm_chunk)

    @property
    def plain_stack(self) -> bool:
        """One block type and none of the per-layer mechanisms: what the
        NVMe weight stream, ZeRO-Inference's weight quantization and
        the pipeline stages were written for."""
        return (self.layer_pattern == ("full",) and not self.num_dense_layers
                and not self.attn_gate and not self.sandwich_norm
                and self.embed_scale is None and self.moe_groups == 1
                and self.experts_held is None and not self.moe_zero_experts)

    def rope_on(self, kind: str) -> bool:
        """Whether a layer of attention kind ``kind`` rotates q and k."""
        return self.position == "rope" and (
            self.rope_kinds is None or kind in self.rope_kinds)

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
    lead = cfg.num_dense_layers
    out_scale = 1.0 / math.sqrt(dm) / math.sqrt(2.0 * nl)   # GPT-2 depth scaling

    def stack_init(fn, key, n=nl - lead):
        """Init ``n`` layers' worth with per-layer keys, stacked on dim 0."""
        ks = jax.random.split(key, n)
        outs = [fn(k) for k in ks]
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

    # attention — fused qkv as separate heads-aware tensors
    def qkv_init(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        p, a = {}, {}
        p["wq"] = jax.random.normal(k1, (dm, H, D)) / math.sqrt(dm)
        a["wq"] = ("embed", "heads", "head_dim")
        p["wk"] = jax.random.normal(k2, (dm, Hkv, D)) / math.sqrt(dm)
        a["wk"] = ("embed", "kv_heads", "head_dim")
        if cfg.key_scale != 1.0:
            # a model with constant multipliers is seeded with each
            # multiplier undone in the weight it stands behind, so that
            # every term of a block is a visible part of its output and
            # a forward that leaves one out reads wrong by 1/multiplier
            p["wk"] = p["wk"] / cfg.key_scale
        p["wv"] = jax.random.normal(k3, (dm, Hkv, D)) / math.sqrt(dm)
        a["wv"] = ("embed", "kv_heads", "head_dim")
        p["wo"] = jax.random.normal(k4, (H, D, dm)) * out_scale
        a["wo"] = ("heads", "head_dim", "embed")
        if cfg.attn_in_scale * cfg.attn_out_scale != 1.0:
            p["wq"] = p["wq"] / cfg.attn_in_scale
            p["wk"] = p["wk"] / cfg.attn_in_scale
            p["wv"] = p["wv"] / cfg.attn_in_scale
            p["wo"] = p["wo"] / cfg.attn_out_scale
        if cfg.attn_scale is not None:
            # a stated score multiplier is undone half in W_q and half in
            # W_k: the scores keep the spread 1/sqrt(D) gives them
            s = math.sqrt(cfg.attn_scale * math.sqrt(D))
            p["wq"] = p["wq"] / s
            p["wk"] = p["wk"] / s
        if cfg.residual_scale != 1.0:
            p["wo"] = p["wo"] / cfg.residual_scale
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
            per_head = cfg.qk_norm_form == "head"
            p["q_norm"] = jax.random.uniform(
                k5, (D,) if per_head else (H, D), minval=0.5, maxval=1.5)
            a["q_norm"] = ("head_dim",) if per_head else ("heads", "head_dim")
            p["k_norm"] = jax.random.uniform(
                k6, (D,) if per_head else (Hkv, D), minval=0.5, maxval=1.5)
            a["k_norm"] = ("head_dim",) if per_head \
                else ("kv_heads", "head_dim")
        if cfg.attn_gate:
            p["wg"] = jax.random.normal(
                jax.random.fold_in(k, 2),  # tpulint: disable=rng-discipline
                (dm, H, D)) / math.sqrt(dm)
            a["wg"] = ("embed", "heads", "head_dim")
        return p, a

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
        if cfg.mlp_gate_scale * cfg.mlp_out_scale != 1.0:
            p["wg"] = p["wg"] / cfg.mlp_gate_scale
            p["wo"] = p["wo"] / cfg.mlp_out_scale
        if cfg.mlp_bias:
            p["bi"] = jnp.zeros((dff,)); a["bi"] = ("mlp",)
            p["bo"] = jnp.zeros((dm,)); a["bo"] = ("embed",)
        return p, a

    def ssm_init(k):
        """A hybrid layer's Mamba-2 mixer.  ``A`` and ``dt`` start as
        Mamba-2 starts them (A uniform in 1..16, softplus(dt_bias)
        log-uniform in 0.001..0.1), so a state decays over tens to
        thousands of tokens; ``D`` and the gated norm's scale are
        seeded away from one, for the reason ``q_norm`` is."""
        sd = cfg.ssm_dims
        ks = jax.random.split(k, 8)
        from ..ops.ssm import column_scales
        w_in = jax.random.normal(ks[0], (dm, sd.in_proj)) / math.sqrt(dm) \
            / (cfg.ssm_in_scale * column_scales(sd, cfg.ssm_col_scales))
        dt0 = jnp.exp(jax.random.uniform(
            ks[3], (sd.heads,), minval=math.log(1e-3), maxval=math.log(0.1)))
        p = {"w_in": w_in,
             "conv_w": jax.random.uniform(ks[1], (sd.conv_channels, sd.conv),
                                          minval=-0.5, maxval=0.5),
             "conv_b": jax.random.uniform(ks[2], (sd.conv_channels,),
                                          minval=-0.5, maxval=0.5),
             "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
             "A_log": jnp.log(jax.random.uniform(ks[4], (sd.heads,),
                                                 minval=1.0, maxval=16.0)),
             "D": jax.random.uniform(ks[5], (sd.heads,), minval=0.5,
                                     maxval=1.5),
             "norm": jax.random.uniform(ks[6], (sd.d_ssm,), minval=0.5,
                                        maxval=1.5),
             "w_out": jax.random.normal(ks[7], (sd.d_ssm, dm)) * out_scale
             / (cfg.ssm_out_scale * cfg.residual_scale)}
        a = {"w_in": ("embed", None), "conv_w": (None, None),
             "conv_b": (None,), "dt_bias": (None,), "A_log": (None,),
             "D": (None,), "norm": (None,), "w_out": (None, "embed")}
        return p, a

    def kda_init(k):
        """A "kda" layer's mixer.  Seeded so that the per-token decays
        ``exp(g)`` spread over about 0.9 to 0.9999 across channels (a
        state lives tens to thousands of tokens) and move with the
        token, and ``beta`` over about 0.1 to 0.9; the norm's scale away
        from one, for the reason ``q_norm`` is."""
        kd = cfg.kda_dims
        H, K, V = kd.heads, kd.key_dim, kd.value_dim
        ks = jax.random.split(k, 9)
        a = jax.random.uniform(ks[4], (H,), minval=0.5, maxval=2.0)
        p = {"w_qkv": jax.random.normal(ks[0], (dm, kd.conv_channels))
             / math.sqrt(dm),
             "conv_w": jax.random.uniform(ks[1], (kd.conv_channels, kd.conv),
                                          minval=-0.5, maxval=0.5),
             "w_f": jax.random.normal(ks[2], (dm, H * K)) / math.sqrt(dm),
             "w_b": jax.random.normal(ks[3], (dm, H)) * 2.0 / math.sqrt(dm),
             "A_log": jnp.log(a),
             # exp(A_log) (a + dt_bias) in about (-10.8, -3.8): the
             # bounded gate's sigmoid in 2e-5 .. 0.021
             "dt_bias": (jax.random.uniform(ks[5], (H, K), minval=-10.8,
                                            maxval=-3.8)
                         / a[:, None]).reshape(H * K),
             "w_g": jax.random.normal(ks[6], (dm, H * V)) / math.sqrt(dm),
             "norm": jax.random.uniform(ks[7], (V,), minval=0.5, maxval=1.5),
             "w_o": jax.random.normal(ks[8], (H * V, dm)) * out_scale}
        ax = {"w_qkv": ("embed", None), "conv_w": (None, None),
              "w_f": ("embed", None), "w_b": ("embed", None),
              "A_log": (None,), "dt_bias": (None,), "w_g": ("embed", None),
              "norm": (None,), "w_o": (None, "embed")}
        return p, ax

    def mla_init(k):
        """An "mla" layer's attention: one key-value latent with its
        norm (scale seeded away from one), one shared rotated key, a gate
        by head; the query in one product, or through a latent with a
        norm of its own (``mla_q_rank``)."""
        md = cfg.mla_dims
        ks = jax.random.split(k, 6)
        r = md.kv_rank
        p = {"wq": jax.random.normal(ks[0], (dm, H, md.nope_dim
                                             + md.rope_dim)) / math.sqrt(dm),
             "w_kva": jax.random.normal(ks[1], (dm, md.row)) / math.sqrt(dm),
             "c_norm": jax.random.uniform(ks[2], (r,), minval=0.5,
                                          maxval=1.5),
             "w_kvb": jax.random.normal(ks[3], (r, H, md.nope_dim
                                                + md.value_dim))
             / math.sqrt(r),
             "wo": jax.random.normal(ks[4], (H, md.value_dim, dm))
             * out_scale}
        ax = {"wq": ("embed", "heads", "head_dim"), "w_kva": ("embed", None),
              "c_norm": (None,), "w_kvb": (None, "heads", "head_dim"),
              "wo": ("heads", "head_dim", "embed")}
        if cfg.mla_gate == "head":
            p["wg"] = jax.random.normal(ks[5], (dm, H)) / math.sqrt(dm)
            ax["wg"] = ("embed", "heads")
        if md.q_rank:
            # the query latent's weights, and the head projections as the
            # matrices their products read ([rank, H * D], [H * V, dm]): a
            # step reads a layer of a stacked matrix where it lies and
            # copies one of a stack of higher rank out first (PERF.md
            # section 6, PR 45).  Each constant multiplier is undone in
            # the weight it stands behind (as ``key_scale``'s is), so a
            # forward that leaves one out reads wrong by the multiplier
            qa, qn = jax.random.split(jax.random.fold_in(  # tpulint: disable=rng-discipline
                k, 3))
            del p["wq"], ax["wq"]
            p["wq_a"] = jax.random.normal(qa, (dm, md.q_rank)) \
                / math.sqrt(dm)
            p["q_norm"] = jax.random.uniform(qn, (md.q_rank,), minval=0.5,
                                             maxval=1.5)
            p["wq_b"] = jax.random.normal(
                ks[0], (md.q_rank, H * (md.nope_dim + md.rope_dim))) \
                / math.sqrt(md.q_rank) / md.q_scale
            p["w_kvb"] = p["w_kvb"].reshape(r, -1) / md.kv_scale
            p["wo"] = p["wo"].reshape(-1, dm)
            ax.update({"wq_a": ("embed", None), "q_norm": (None,),
                       "wq_b": (None, "heads"), "w_kvb": (None, "heads"),
                       "wo": ("heads", "embed")})
        return p, ax

    mixer_inits = {"kda": kda_init, "mla": mla_init, "mamba": ssm_init,
                   "full": qkv_init}

    def mixers_init(key, first, n):
        """The mixers of layers ``[first, first + n)``, a stack a kind
        under the kind's name; one ``"attn"`` stack for a model whose
        layers all hold attention."""
        if not cfg.mixer_stacks:
            return {"attn": stack_init(qkv_init, key, n)}
        out = {}
        for j, kind in enumerate(cfg.mixer_stacks):
            m = cfg.kind_rank(first + n, kind, first)
            if m:
                out[kind] = stack_init(
                    mixer_inits[kind],
                    jax.random.fold_in(key, 11 + j),  # tpulint: disable=rng-discipline
                    m)
        return out

    norm_init = L.layernorm_init if cfg.norm == "layernorm" else L.rmsnorm_init

    def norms_init(n):
        """``n`` layers' norms, stacked: scales at one (the key only
        shapes the stack)."""
        names = ["ln1"]
        if not cfg.parallel_block or cfg.parallel_separate_norms:
            names.append("ln2")
        if cfg.sandwich_norm:
            names += ["ln1_post", "ln2_post"]
        made = {name: stack_init(lambda k: norm_init(dm), keys[4], n)
                for name in names}
        return ({k: v[0] for k, v in made.items()},
                {k: v[1] for k, v in made.items()})

    blk_p: Dict[str, Any] = {}
    blk_a: Dict[str, Any] = {}
    for name, (mp_, ma_) in mixers_init(keys[2], lead, nl - lead).items():
        blk_p[name], blk_a[name] = mp_, ma_
    if "hybrid" in cfg.layer_pattern:
        blk_p["ssm"], blk_a["ssm"] = stack_init(
            ssm_init, jax.random.fold_in(keys[2], 3))  # tpulint: disable=rng-discipline

    if cfg.num_experts > 1:
        from ..parallel import moe as M

        # a shortcut-connected layer's router and experts: one a period
        n_moe = cfg.expert_layers

        def gate_init(k):
            p, a = M.gate_init(k, dm, cfg.router_outputs)
            if cfg.moe_select_bias:
                # seeded away from zero, as a trained router's is: a
                # forward that added it to the weights, or left it out
                # of the choice, would otherwise agree with one that
                # uses it as published.  Small beside the scores' own
                # spread (0.1 at these logits): at +-0.1 it, not the
                # token, chose the experts, the fullest took 7.6 times
                # the mean and a third of them no token at all
                # (softmax scores over E outputs lie about 1/E with a
                # spread of 0.9/E at these logits: the same sixth)
                lim = 0.02 if cfg.moe_score == "sigmoid" \
                    else 0.15 / cfg.router_outputs
                p["bias"] = jax.random.uniform(
                    jax.random.fold_in(k, 1),  # tpulint: disable=rng-discipline
                    (cfg.router_outputs,), minval=-lim, maxval=lim)
                a["bias"] = (None,)
            return p, a

        blk_p["gate"], blk_a["gate"] = stack_init(gate_init, keys[7], n_moe)
        blk_p["experts"], blk_a["experts"] = stack_init(
            lambda k: M.experts_init(k, cfg.experts_here, dm, cfg.moe_d_ff,
                                     gated=cfg.gated_mlp,
                                     out_scale=out_scale
                                     / cfg.residual_scale), keys[3], n_moe)
        if cfg.moe_shared_ff:        # a dense expert every token takes
            sff = cfg.moe_shared_ff

            def shared_init(k):
                k1, k2, k3, k4 = jax.random.split(k, 4)
                p = {"wi": jax.random.normal(k1, (dm, sff))
                     / math.sqrt(dm),
                     "wo": jax.random.normal(k2, (sff, dm))
                     * (out_scale / cfg.residual_scale)}
                a = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
                if cfg.gated_mlp:
                    p["wg"] = jax.random.normal(k3, (dm, sff)) \
                        / math.sqrt(dm)
                    a["wg"] = ("embed", "mlp")
                if cfg.moe_shared_gate:     # qwen2-moe's sigmoid gate
                    p["gate"] = jax.random.normal(k4, (dm, 1)) \
                        / math.sqrt(dm)
                    a["gate"] = ("embed", None)
                return p, a

            blk_p["shared"], blk_a["shared"] = stack_init(
                shared_init, keys[8])
    if cfg.num_experts == 1 or cfg.moe_shortcut:
        blk_p["mlp"], blk_a["mlp"] = stack_init(
            mlp_init, jax.random.fold_in(keys[3], 5)  # tpulint: disable=rng-discipline
            if cfg.moe_shortcut else keys[3])

    for tree, part in zip((blk_p, blk_a), norms_init(nl - lead)):
        tree.update(part)
    params["blocks"] = blk_p
    axes["blocks"] = blk_a

    if lead:
        # the leading dense layers: a stack of their own, so that every
        # leaf of "blocks" keeps one leading size.  Their keys are
        # streams off the stacks' keys: a model without such layers is
        # seeded as before
        def lead_key(i):
            return jax.random.fold_in(keys[i], 7)  # tpulint: disable=rng-discipline

        dp: Dict[str, Any] = {}
        da: Dict[str, Any] = {}
        for name, (mp_, ma_) in mixers_init(lead_key(2), 0, lead).items():
            dp[name], da[name] = mp_, ma_
        dp["mlp"], da["mlp"] = stack_init(mlp_init, lead_key(3), lead)
        for tree, part in zip((dp, da), norms_init(lead)):
            tree.update(part)
        params["dense_blocks"] = dp
        axes["dense_blocks"] = da

    params["ln_f"], axes["ln_f"] = norm_init(dm)
    if not cfg.tie_embeddings:
        params["lm_head"], axes["lm_head"] = (
            {"kernel": jax.random.normal(keys[6], (dm, cfg.vocab_size))
             / math.sqrt(dm)},
            {"kernel": ("embed", "vocab")})
        if cfg.head_scale != 1.0:
            params["lm_head"]["kernel"] = (params["lm_head"]["kernel"]
                                           / cfg.head_scale)
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
    """The dense shared expert: a full MLP on every token, added to the
    routed output.  qwen2-moe scales it by a per-token sigmoid gate
    (reference analog: the qwen_v2_moe v2 model implementation's
    shared_expert path); a model without that gate (trinity) has no
    ``gate`` weight and adds it as it is."""
    dt = h.dtype
    u = h @ sp["wi"].astype(dt)
    u = act(h @ sp["wg"].astype(dt)) * u if "wg" in sp else act(u)
    d = u @ sp["wo"].astype(dt)
    if "gate" not in sp:
        return d
    g = jax.nn.sigmoid((h @ sp["gate"].astype(dt)).astype(jnp.float32))
    return d * g.astype(dt)


def moe_share(cfg) -> Dict[str, Any]:
    """The router's group limit and the share of the experts held, as
    the expert layers take them; {} for a model with neither (its calls
    are what they were)."""
    out = {}
    if cfg.moe_groups > 1:
        out["groups"] = (cfg.moe_groups, cfg.moe_groups_kept)
        if cfg.moe_group_score != "top2":
            out["group_score"] = cfg.moe_group_score
    if cfg.experts_held is not None:
        out["held"] = cfg.experts_held
    if cfg.moe_zero_experts:
        out["zero"] = cfg.moe_zero_experts
    return out


def _mixer_apply(cfg, lp, h, kind: str, cos, sin):
    """A "kda" or "mla" layer's mixer over whole sequences from a zero
    state.  h: [B, S, dm], the normed input → [B, S, dm]."""
    dt = h.dtype
    if kind == "kda":
        from ..ops.kda import mixer_forward
        return mixer_forward(lp["kda"], h, cfg.kda_dims, cfg.eps)
    from ..ops.mla import attention_forward
    ap = lp["mla"]
    o = attention_forward(ap, h, cos, sin, cfg.mla_dims, cfg.eps)
    if cfg.mla_gate == "head":
        g = jnp.tensordot(h, ap["wg"].astype(dt), 1).astype(jnp.float32)
        o = o * jax.nn.sigmoid(g).astype(dt)[..., None]
    wo = ap["wo"].astype(dt)
    return jnp.einsum("bshk,hkd->bsd", o, wo.reshape(o.shape[2:] + (-1,)))


def _dense_mlp(cfg, mp, h):
    """A layer's dense MLP on its normed input."""
    dt = h.dtype
    act = L.ACTIVATIONS[cfg.activation]
    u = h @ mp["wi"].astype(dt)
    if cfg.mlp_bias:
        u = u + mp["bi"].astype(dt)
    if cfg.gated_mlp:
        g = h @ mp["wg"].astype(dt)
        if cfg.mlp_gate_scale != 1.0:
            g = g * jnp.asarray(cfg.mlp_gate_scale, dt)
        u = act(g) * u
    else:
        u = act(u)
    d = u @ mp["wo"].astype(dt)
    if cfg.mlp_bias:
        d = d + mp["bo"].astype(dt)
    if cfg.mlp_out_scale != 1.0:
        d = d * jnp.asarray(cfg.mlp_out_scale, dt)
    return d


def shortcut_layer(cfg, lps, x, cos, sin):
    """One shortcut-connected layer over whole sequences
    (``moe_shortcut``): its sublayers ``lps`` in order, each ``x +
    mixer(N(x))`` then ``x + MLP(N(x))``; the experts read the first
    sublayer's normed MLP input and their output joins the stream at the
    layer's end, so nothing between reads it.  The experts are served
    dropless (``moe_serve``): no capacity dispatch knows this router.
    x: [B, S, dm] → [B, S, dm]."""
    from ..parallel import moe as M

    norm = _norm(cfg)
    B, S, dm = x.shape
    for kind, lp in zip(cfg.layer_pattern, lps):
        with jax.named_scope("attn"):
            x = x + _mixer_apply(cfg, lp, norm(lp["ln1"], x), kind, cos, sin)
        with jax.named_scope("ffn"):
            h = norm(lp["ln2"], x)
            if "gate" in lp:
                skip, _ = M.moe_serve(
                    lp["gate"], lp["experts"], h.reshape(B * S, dm),
                    top_k=cfg.moe_top_k,
                    activation=L.ACTIVATIONS[cfg.activation],
                    gated=cfg.gated_mlp, norm_topk=cfg.moe_norm_topk,
                    score=cfg.moe_score, route_scale=cfg.moe_route_scale,
                    **moe_share(cfg))
            x = x + _dense_mlp(cfg, lp["mlp"], h)
    return x + skip.reshape(B, S, dm)


def _qk_norm(cfg, scale, x):
    """The config's QK-norm of q or k ``[..., heads, head_dim]``."""
    if cfg.qk_norm_form == "head":
        return L.rmsnorm({"scale": scale}, x, cfg.eps)
    return L.qk_rmsnorm(scale, x, cfg.eps)


def block_apply(cfg: TransformerConfig, lp, x, cos, sin,
                mask=None, attention_fn: Callable = L.causal_attention,
                rng=None, positions=None, kind: str = "full",
                dense: bool = False):
    """One decoder layer. lp: this layer's (unstacked) params.
    x: [B, S, dm].  ``positions``: optional [B, S] original token
    positions (random-LTD gathered subsequences keep their rotary
    phases).  ``kind``: the layer's attention kind (``layer_kinds``);
    ``dense``: a leading dense layer of a sparse-expert model.  Both are
    static.  Returns (x, metrics) — metrics non-empty for MoE."""
    norm = _norm(cfg)
    act = L.ACTIVATIONS[cfg.activation]
    ap = lp.get("attn", lp.get("full"))
    if cfg.attn_scale is not None and attention_fn is L.causal_attention:
        # safety net for call sites that never resolved attention_fn
        # (pipeline stage bodies, streamed sweeps): gpt-neo's unscaled
        # attention must not silently regain the 1/sqrt(d) factor
        attention_fn = partial(L.causal_attention, scale=cfg.attn_scale)

    dt = x.dtype
    if kind in ("kda", "mla"):
        with jax.named_scope("attn"):
            o = _mixer_apply(cfg, lp, norm(lp["ln1"], x), kind, cos, sin)
    elif kind == "mamba":
        from ..ops.ssm import mixer_forward
        with jax.named_scope("ssm"):
            o = mixer_forward(lp["mamba"], norm(lp["ln1"], x), cfg.ssm_dims,
                              cfg.ssm_col_scales, cfg.eps)
    else:
        # named scopes at the block's seams, the serving forward's names
        # (metadata only): autodiff and jax.checkpoint add the pass to an
        # operation's JAX path, so a device trace tells a layer's forward
        # from its recomputation and its backward
        with jax.named_scope("qkv"):
            h = norm(lp["ln1"], x)
            hn = h          # a hybrid layer's mixer reads the same normed input
            if cfg.attn_in_scale != 1.0:
                h = h * jnp.asarray(cfg.attn_in_scale, dt)
            q = jnp.einsum("bsd,dhk->bshk", h, ap["wq"].astype(dt))
            k = jnp.einsum("bsd,dhk->bshk", h, ap["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, ap["wv"].astype(dt))
            if cfg.key_scale != 1.0:
                k = k * jnp.asarray(cfg.key_scale, dt)
            if cfg.attn_bias:
                q = q + ap["bq"].astype(dt)
                k = k + ap["bk"].astype(dt)
                v = v + ap["bv"].astype(dt)
            if cfg.qk_norm:
                q = _qk_norm(cfg, ap["q_norm"], q)
                k = _qk_norm(cfg, ap["k_norm"], k)
            if cfg.rope_on(kind):
                q = L.apply_rope(q, cos, sin, positions=positions)
                k = L.apply_rope(k, cos, sin, positions=positions)
            if cfg.attn_gate:
                g = jnp.einsum("bsd,dhk->bshk", h, ap["wg"].astype(dt))
        with jax.named_scope("attn"):
            if kind == "window":
                # only the eager attention takes a window (_resolve_attention)
                o = attention_fn(q, k, v, mask=mask, window=cfg.attn_window)
            else:
                o = attention_fn(q, k, v, mask=mask)
            if cfg.attn_gate:
                o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        with jax.named_scope("attn_out"):
            o = jnp.einsum("bshk,hkd->bsd", o, ap["wo"].astype(dt))
            if cfg.attn_out_bias:
                o = o + ap["bo"].astype(dt)
            if cfg.sandwich_norm:
                o = norm(lp["ln1_post"], o)
            if cfg.attn_out_scale != 1.0:
                o = o * jnp.asarray(cfg.attn_out_scale, dt)
        if kind == "hybrid":
            from ..ops.ssm import mixer_forward
            with jax.named_scope("ssm"):
                m = mixer_forward(lp["ssm"],
                                  hn * jnp.asarray(cfg.ssm_in_scale, dt),
                                  cfg.ssm_dims, cfg.ssm_col_scales, cfg.eps)
                o = o + m * jnp.asarray(cfg.ssm_out_scale, dt)

    if cfg.residual_scale != 1.0:
        o = o * jnp.asarray(cfg.residual_scale, dt)
    with jax.named_scope("ffn"):
        if not cfg.parallel_block:
            x = x + o
            h = norm(lp["ln2"], x)
        elif cfg.parallel_separate_norms:
            # gpt-neox: the MLP reads its own norm of the ORIGINAL x
            h = norm(lp["ln2"], x)
        # parallel residual (falcon/phi): the MLP reads the same ln1 output
        metrics: Dict[str, Any] = {}
        if cfg.num_experts > 1 and not dense:
            from ..parallel import moe as M

            d, metrics = M.moe_ffn(
                lp["gate"], lp["experts"], h, top_k=cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor,
                min_capacity=cfg.min_capacity, activation=act,
                gated=cfg.gated_mlp, rng=rng, noise_policy=cfg.noise_policy,
                dispatch_mode=cfg.moe_dispatch,
                norm_topk=cfg.moe_norm_topk, score=cfg.moe_score,
                route_scale=cfg.moe_route_scale, **moe_share(cfg))
            if "shared" in lp:       # the dense expert every token takes
                d = d + _shared_expert(lp["shared"], h, act, cfg.gated_mlp)
        else:
            d = _dense_mlp(cfg, lp["mlp"], h)
        if cfg.sandwich_norm:
            d = norm(lp["ln2_post"], d)
        if cfg.residual_scale != 1.0:
            d = d * jnp.asarray(cfg.residual_scale, dt)
        if cfg.parallel_block:
            return x + o + d, metrics
        return x + d, metrics


def stack_layer(cfg: TransformerConfig, stack, layer: int, first: int):
    """Model layer ``layer``'s weights out of ``stack`` (``blocks`` or
    ``dense_blocks``), whose first layer is model layer ``first``: row
    ``layer - first`` of every leaf, but of a mixer stacked by kind
    (``mixer_stacks``) the row of the layer's rank among its kind."""
    kind = cfg.layer_kinds[layer]
    out = {}
    for name, sub in stack.items():
        if name in cfg.mixer_stacks:
            if name != kind:
                continue
            at = cfg.kind_rank(layer, kind, first)
        elif cfg.moe_shortcut and name in ("gate", "experts"):
            # a period's first sublayer holds them (``moe_shortcut``)
            at, later = divmod(layer - first, len(cfg.layer_pattern))
            if later:
                continue
        else:
            at = layer - first
        out[name] = jax.tree.map(lambda a, at=at: a[at], sub)
    return out


# The layer scan runs unrolled over its whole trip count where its trips
# hold up to this many layers together (a trip is a period of the layer
# pattern: one layer, or a few), and rolled above that.  Rolled, every
# trip cuts its layer's weights and saved activations out of their stacks
# and writes the weight gradients and the activations into theirs, and
# the matmuls beside them wait on those stacks; unrolled whole, the slices
# are static and a stacked gradient is assembled once.  An unroll between
# 1 and the trip count keeps the stacks and multiplies the bodies, so
# there is none.  The ceiling is the chip's (TPU v5e; PERF.md section 6,
# PR 41): 6 layers of pythia-1.4b train 16% faster unrolled, for 29 s
# more of a first compile and 1.5 s of a warm start; GPT-2 small's 12
# layers without recomputation fit a chip only unrolled (27 GB rolled:
# every residual is kept stacked); pythia-1.4b's 24 layers under ZeRO-3
# over four chips gain nothing (-0.2%) and start 27 s later from a warm
# compile cache.  Between 12 and 24 nothing is measured.
UNROLL_MAX_LAYERS = 12


def layers_unrolled(cfg: TransformerConfig) -> int:
    """The layers :func:`apply`'s scan runs unrolled, outside any loop of
    the compiled program; 0: the scan is rolled."""
    layers = cfg.layer_plan[1] * len(cfg.layer_pattern)
    return layers if layers <= UNROLL_MAX_LAYERS else 0


def apply(cfg: TransformerConfig, params, input_ids, mask=None,
          attention_fn: Callable = L.causal_attention,
          dtype=None, rng=None, with_aux: bool = False,
          pld_theta=None, ltd_keep: Optional[int] = None,
          placement: Optional[Placement] = None):
    """Forward pass → logits [B, S, vocab] (or (logits, aux) with
    with_aux=True; aux carries MoE load-balancing metrics averaged over
    layers).

    The layers run as ``cfg.layer_plan`` says: the leading dense layers
    one by one, then ONE scan over the whole periods of
    ``cfg.layer_pattern`` whose body holds a period's layers, each of a
    static kind, then the layers of a last period cut short.  A model of
    one block type is a period of one layer.

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
    with jax.named_scope("embed"):
        x = L.embed(use("embed", params["embed"]), input_ids).astype(dt)
        if cfg.embed_scale is not None:
            x = x * jnp.asarray(cfg.embed_scale, dt)
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
        elif cfg.position == "none":
            cos = sin = None
        else:
            cos, sin = L.rope_freqs(cfg.rotary_dim, cfg.max_seq_len,
                                    cfg.rope_theta, cfg.rope_yarn)

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
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    pattern = cfg.layer_pattern
    P = len(pattern)
    lead, periods, tail = cfg.layer_plan

    def layer(h, lp, r, li, name="blocks", kind=pattern[0], dense=False):
        # inside the (checkpointed) body, so the recomputation and the
        # backward state the same as the forward
        y, metrics = block_apply(cfg, use(name, lp, layer_slice=True),
                                 keep(h), cos, sin, mask=mask,
                                 attention_fn=attention_fn,
                                 rng=r if have_rng else None,
                                 positions=positions, kind=kind,
                                 dense=dense)
        y = keep(y)
        if pld_theta is not None:
            # whole-batch per-layer coin; deeper layers drop more
            keep_p = 1.0 - (li.astype(jnp.float32) / cfg.num_layers) \
                * (1.0 - pld_theta)
            drop = jax.random.bernoulli(
                jax.random.fold_in(r, 1), 1.0 - keep_p)
            y = jnp.where(drop, h, y)
        return y, metrics

    def body(h, xs):
        lp, r, li = xs
        if P == 1:
            # a period of one layer is that layer: the general path
            # below gives the same numbers, but another compiled program
            # for every model trained before there was a pattern
            return layer(h, lp, r, li)
        ms = []
        for j, kind in enumerate(pattern):
            h, m = layer(h, jax.tree.map(lambda a: a[j], lp), r[j], li[j],
                         kind=kind)
            ms.append(m)
        return h, jax.tree.map(lambda *v: jnp.stack(v), *ms)

    def remat(fn):
        if not cfg.remat:
            return fn
        policy = REMAT_POLICIES[cfg.remat_policy]
        return jax.checkpoint(fn, policy=policy() if policy else None)

    def rows(a, first, n):
        return a if first == 0 and n == a.shape[0] else a[first:first + n]

    def periods_of(a, first):
        """``a``'s rows of the whole periods, from row ``first``: a layer
        a row, or with a longer period a period a row."""
        a = rows(a, first, periods * P)
        return a if P == 1 else a.reshape((periods, P) + a.shape[1:])

    outside_ms = []

    def outside(x, stack, first, n, li, **static):
        """``n`` layers of ``stack`` from its ``first``, one by one;
        ``li``: the first one's index in the model."""
        for i in range(n):
            one = partial(layer, **static, kind=cfg.layer_kinds[li + i])
            x, m = remat(one)(
                x, jax.tree.map(lambda a: a[first + i], stack),
                layer_rngs[li + i], layer_ids[li + i])
            outside_ms.append(m)
        return x

    x = keep(x)
    metrics = {}
    if cfg.moe_shortcut:
        for i in range(0, cfg.num_layers, P):
            x = keep(shortcut_layer(
                cfg, [use("blocks", stack_layer(cfg, params["blocks"],
                                                i + j, 0), layer_slice=True)
                      for j in range(P)], keep(x), cos, sin))
    elif cfg.mixer_stacks:
        # the mixers are stacked by kind, so no scan cuts a period out
        # of one stack: the layers run one by one (the forward that
        # tests and comparisons read; such a model is not trained here)
        for i, kind in enumerate(cfg.layer_kinds):
            name, first = ("dense_blocks", 0) if i < lead else ("blocks",
                                                               lead)
            x, m = remat(partial(layer, name=name, kind=kind,
                                 dense=i < lead))(
                x, stack_layer(cfg, params[name], i, first),
                layer_rngs[i], layer_ids[i])
            outside_ms.append(m)
    else:
        if lead:
            x = outside(x, params["dense_blocks"], 0, lead, 0,
                        name="dense_blocks", dense=True)
        # the scan's own work (a layer's weights cut out of the stack,
        # the saved activations and the weight gradients stacked and cut
        # again) lies outside the body: it takes this scope, the layers
        # theirs.  Unrolled it stays ONE scan: the body is traced once
        # and the transposed scan stacks a leaf's gradient with one
        # concatenate (a Python loop over a[i] pads every layer's to the
        # whole stack and sums)
        with jax.named_scope("layer_scan"):
            x, metrics = jax.lax.scan(
                remat(body), x,
                (jax.tree.map(lambda a: periods_of(a, 0), params["blocks"]),
                 periods_of(layer_rngs, lead), periods_of(layer_ids, lead)),
                unroll=periods if layers_unrolled(cfg) else 1)
        if tail:
            x = outside(x, params["blocks"], periods * P, tail,
                        lead + periods * P)
    if idx is not None:
        # dropped positions bypass the stack with their embedding
        x = random_ltd_scatter(full_x, x, idx)
    with jax.named_scope("unembed"):
        x = _norm(cfg)(use("ln_f", params["ln_f"]), x)
        if cfg.tie_embeddings:
            logits = x @ use("embed", params["embed"])["table"].astype(dt).T
        else:
            head = use("lm_head", params["lm_head"])
            logits = x @ head["kernel"].astype(dt)
            if cfg.head_bias:
                logits = logits + head["bias"].astype(dt)
        if cfg.head_scale != 1.0:
            logits = logits * jnp.asarray(cfg.head_scale, dt)
        logits = keep(logits)
    if with_aux:
        aux = {k: v.mean() for k, v in metrics.items()} if metrics else {}
        tail_ms = [m for m in outside_ms if m]
        if tail_ms and not metrics:       # no layer ran inside a scan
            aux = {k: sum(m[k] for m in tail_ms) / len(tail_ms)
                   for k in tail_ms[0]}
        elif tail_ms:   # expert layers outside the scan count as its do
            aux = {k: (v.sum() + sum(m[k] for m in tail_ms))
                   / (v.size + len(tail_ms)) for k, v in metrics.items()}
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
    with jax.named_scope("loss"):
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
            with jax.named_scope("loss"):
                loss = loss + cfg.aux_loss_coef * aux["moe_aux_loss"]
            return loss, aux
        return loss

    loss_fn.uses_pld = pld
    # read when the engine builds its step, as apply reads it when traced
    loss_fn.layers_unrolled = lambda: layers_unrolled(cfg)
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
    if "window" in cfg.layer_pattern and cfg.attention_impl in (
            "flash", "xla_flash"):
        raise ValueError(
            "window layers need the eager attention (attention_impl="
            "'xla'): the flash kernels take no window")
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
