"""Model-family presets (the reference's model zoo as configs).

Covers the families the reference injects/implements (SURVEY §2.6:
gpt2/neo/neox/j, llama/llama2/llama3, mistral, opt, qwen2 — containers in
``module_inject/containers/`` and ``inference/v2/model_implementations/``)
as :class:`TransformerConfig` presets for the single transformer core.
"""

from __future__ import annotations

from typing import Dict

from .transformer import Model, TransformerConfig

PRESETS: Dict[str, dict] = {
    # --- GPT-2 family ---------------------------------------------------
    "gpt2": dict(vocab_size=50257, num_layers=12, d_model=768, num_heads=12,
                 max_seq_len=1024, activation="gelu_new", norm="layernorm",
                 position="learned", tie_embeddings=True),
    "gpt2-medium": dict(vocab_size=50257, num_layers=24, d_model=1024,
                        num_heads=16, max_seq_len=1024,
                        activation="gelu_new", position="learned"),
    "gpt2-large": dict(vocab_size=50257, num_layers=36, d_model=1280,
                       num_heads=20, max_seq_len=1024,
                       activation="gelu_new", position="learned"),
    "gpt2-xl": dict(vocab_size=50257, num_layers=48, d_model=1600,
                    num_heads=25, max_seq_len=1024,
                    activation="gelu_new", position="learned"),
    # --- Llama family ---------------------------------------------------
    "llama-tiny": dict(vocab_size=32000, num_layers=4, d_model=256,
                       num_heads=8, num_kv_heads=4, d_ff=688,
                       max_seq_len=2048, activation="silu", gated_mlp=True,
                       norm="rmsnorm", position="rope", tie_embeddings=False,
                       attn_bias=False, mlp_bias=False, eps=1e-5),
    "llama2-7b": dict(vocab_size=32000, num_layers=32, d_model=4096,
                      num_heads=32, d_ff=11008, max_seq_len=4096,
                      activation="silu", gated_mlp=True, norm="rmsnorm",
                      position="rope", tie_embeddings=False,
                      attn_bias=False, mlp_bias=False),
    "llama3-8b": dict(vocab_size=128256, num_layers=32, d_model=4096,
                      num_heads=32, num_kv_heads=8, d_ff=14336,
                      max_seq_len=8192, activation="silu", gated_mlp=True,
                      norm="rmsnorm", position="rope", rope_theta=500000.0,
                      tie_embeddings=False, attn_bias=False, mlp_bias=False),
    "llama3-70b": dict(vocab_size=128256, num_layers=80, d_model=8192,
                       num_heads=64, num_kv_heads=8, d_ff=28672,
                       max_seq_len=8192, activation="silu", gated_mlp=True,
                       norm="rmsnorm", position="rope", rope_theta=500000.0,
                       tie_embeddings=False, attn_bias=False, mlp_bias=False),
    # --- Qwen2 (llama layout + qkv biases, no o bias) --------------------
    "qwen2-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                       num_heads=8, num_kv_heads=4, d_ff=688,
                       max_seq_len=2048, activation="silu", gated_mlp=True,
                       norm="rmsnorm", position="rope",
                       rope_theta=1000000.0, tie_embeddings=False,
                       attn_bias=True, attn_out_bias=False,
                       mlp_bias=False, eps=1e-6),
    "qwen2-7b": dict(vocab_size=152064, num_layers=28, d_model=3584,
                     num_heads=28, num_kv_heads=4, d_ff=18944,
                     max_seq_len=32768, activation="silu", gated_mlp=True,
                     norm="rmsnorm", position="rope",
                     rope_theta=1000000.0, tie_embeddings=False,
                     attn_bias=True, attn_out_bias=False,
                     mlp_bias=False, eps=1e-6),
    # --- GPT-J (partial rotary + parallel residual, single shared LN) -----
    "gptj-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                      num_heads=8, max_seq_len=2048, activation="gelu_new",
                      norm="layernorm", position="rope", rope_pct=0.25,
                      parallel_block=True, tie_embeddings=False,
                      attn_bias=False, mlp_bias=True, head_bias=True),
    "gptj-6b": dict(vocab_size=50400, num_layers=28, d_model=4096,
                    num_heads=16, max_seq_len=2048, activation="gelu_new",
                    norm="layernorm", position="rope", rope_pct=0.25,
                    parallel_block=True, tie_embeddings=False,
                    attn_bias=False, mlp_bias=True, head_bias=True),
    # --- GPT-NeoX / Pythia (parallel residual, SEPARATE norms) ------------
    "gpt-neox-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                          num_heads=8, max_seq_len=2048,
                          activation="gelu", norm="layernorm",
                          position="rope", rope_pct=0.25,
                          parallel_block=True, parallel_separate_norms=True,
                          tie_embeddings=False, attn_bias=True,
                          mlp_bias=True),
    "pythia-1.4b": dict(vocab_size=50304, num_layers=24, d_model=2048,
                        num_heads=16, max_seq_len=2048,
                        activation="gelu", norm="layernorm",
                        position="rope", rope_pct=0.25,
                        parallel_block=True, parallel_separate_norms=True,
                        tie_embeddings=False, attn_bias=True,
                        mlp_bias=True),
    # --- Mistral (GQA + high theta) --------------------------------------
    "mistral-7b": dict(vocab_size=32000, num_layers=32, d_model=4096,
                       num_heads=32, num_kv_heads=8, d_ff=14336,
                       max_seq_len=8192, activation="silu", gated_mlp=True,
                       norm="rmsnorm", position="rope", rope_theta=1000000.0,
                       tie_embeddings=False, attn_bias=False, mlp_bias=False),
    # --- Mixtral (MoE, reference: v2 model_implementations/mixtral) -------
    "mixtral-tiny": dict(vocab_size=32000, num_layers=4, d_model=256,
                         num_heads=8, num_kv_heads=4, d_ff=512,
                         max_seq_len=2048, activation="silu", gated_mlp=True,
                         norm="rmsnorm", position="rope",
                         tie_embeddings=False, attn_bias=False,
                         mlp_bias=False, num_experts=8, moe_top_k=2),
    "mixtral-8x7b": dict(vocab_size=32000, num_layers=32, d_model=4096,
                         num_heads=32, num_kv_heads=8, d_ff=14336,
                         max_seq_len=8192, activation="silu", gated_mlp=True,
                         norm="rmsnorm", position="rope",
                         rope_theta=1000000.0, tie_embeddings=False,
                         attn_bias=False, mlp_bias=False,
                         num_experts=8, moe_top_k=2),
    # --- Falcon (MQA + parallel residual, reference: containers/falcon) --
    "falcon-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                        num_heads=8, num_kv_heads=1, max_seq_len=2048,
                        activation="gelu", norm="layernorm",
                        position="rope", parallel_block=True,
                        tie_embeddings=True, attn_bias=False,
                        mlp_bias=False),
    "falcon-7b": dict(vocab_size=65024, num_layers=32, d_model=4544,
                      num_heads=71, num_kv_heads=1, max_seq_len=2048,
                      activation="gelu", norm="layernorm", position="rope",
                      parallel_block=True, tie_embeddings=True,
                      attn_bias=False, mlp_bias=False),
    # --- Phi (partial rotary + parallel residual + biased head) ----------
    "phi-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                     num_heads=8, max_seq_len=2048, activation="gelu_new",
                     norm="layernorm", position="rope", rope_pct=0.4,
                     parallel_block=True, tie_embeddings=False,
                     attn_bias=True, mlp_bias=True, head_bias=True),
    "phi-2": dict(vocab_size=51200, num_layers=32, d_model=2560,
                  num_heads=32, max_seq_len=2048, activation="gelu_new",
                  norm="layernorm", position="rope", rope_pct=0.4,
                  parallel_block=True, tie_embeddings=False,
                  attn_bias=True, mlp_bias=True, head_bias=True),
    # --- BLOOM (ALiBi + word-embedding layernorm; reference container:
    # module_inject/containers/bloom.py) ---------------------------------
    "bloom-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                       num_heads=8, max_seq_len=2048,
                       activation="gelu_new", norm="layernorm",
                       position="alibi", embed_norm=True,
                       tie_embeddings=True, attn_bias=True,
                       mlp_bias=True, attention_impl="xla"),
    "bloom-560m": dict(vocab_size=250880, num_layers=24, d_model=1024,
                       num_heads=16, max_seq_len=2048,
                       activation="gelu_new", norm="layernorm",
                       position="alibi", embed_norm=True,
                       tie_embeddings=True, attn_bias=True,
                       mlp_bias=True, attention_impl="xla"),
    "bloom-7b1": dict(vocab_size=250880, num_layers=30, d_model=4096,
                      num_heads=32, max_seq_len=2048,
                      activation="gelu_new", norm="layernorm",
                      position="alibi", embed_norm=True,
                      tie_embeddings=True, attn_bias=True,
                      mlp_bias=True, attention_impl="xla"),
    # --- OPT ------------------------------------------------------------
    "opt-125m": dict(vocab_size=50272, num_layers=12, d_model=768,
                     num_heads=12, max_seq_len=2048, activation="relu",
                     norm="layernorm", position="learned"),
    # --- Phi-3 (llama-ish: rmsnorm + gated silu, fused qkv/gate_up in
    # the HF checkpoint — reference: inference/v2/model_implementations/
    # phi3/policy.py) -----------------------------------------------------
    "phi3-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                      num_heads=8, d_ff=512, max_seq_len=2048,
                      activation="silu", gated_mlp=True, norm="rmsnorm",
                      position="rope", tie_embeddings=False,
                      attn_bias=False, mlp_bias=False, eps=1e-5),
    "phi3-mini": dict(vocab_size=32064, num_layers=32, d_model=3072,
                      num_heads=32, d_ff=8192, max_seq_len=4096,
                      activation="silu", gated_mlp=True, norm="rmsnorm",
                      position="rope", tie_embeddings=False,
                      attn_bias=False, mlp_bias=False, eps=1e-5),
    # --- InternLM (llama layout + q/k/v/o biases — reference:
    # module_inject/containers/internlm.py) -------------------------------
    "internlm-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                          num_heads=8, d_ff=688, max_seq_len=2048,
                          activation="silu", gated_mlp=True,
                          norm="rmsnorm", position="rope",
                          tie_embeddings=False, attn_bias=True,
                          attn_out_bias=True, mlp_bias=False, eps=1e-6),
    "internlm-7b": dict(vocab_size=103168, num_layers=32, d_model=4096,
                        num_heads=32, d_ff=11008, max_seq_len=2048,
                        activation="silu", gated_mlp=True, norm="rmsnorm",
                        position="rope", tie_embeddings=False,
                        attn_bias=True, attn_out_bias=True,
                        mlp_bias=False, eps=1e-6),
    # --- GPT-Neo (learned positions, UNSCALED attention, no qkv biases —
    # reference: module_inject/containers/gptneo.py.  Like the reference
    # injection kernels, the alternating 256-token local-attention
    # windows serve as dense causal attention) ----------------------------
    "gpt-neo-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                         num_heads=8, max_seq_len=2048,
                         activation="gelu_new", norm="layernorm",
                         position="learned", tie_embeddings=True,
                         attn_bias=False, attn_out_bias=True,
                         mlp_bias=True, attn_scale=1.0,
                         attention_impl="xla"),
    "gpt-neo-1.3b": dict(vocab_size=50257, num_layers=24, d_model=2048,
                         num_heads=16, max_seq_len=2048,
                         activation="gelu_new", norm="layernorm",
                         position="learned", tie_embeddings=True,
                         attn_bias=False, attn_out_bias=True,
                         mlp_bias=True, attn_scale=1.0,
                         attention_impl="xla"),
    # --- Qwen2-MoE (sparse experts + sigmoid-gated dense shared expert,
    # raw softmax top-k probs — reference: inference/v2/
    # model_implementations/qwen_v2_moe/model.py) -------------------------
    "qwen2-moe-tiny": dict(vocab_size=1024, num_layers=4, d_model=256,
                           num_heads=8, num_kv_heads=4, d_ff=352,
                           max_seq_len=2048, activation="silu",
                           gated_mlp=True, norm="rmsnorm",
                           position="rope", rope_theta=1000000.0,
                           tie_embeddings=False, attn_bias=True,
                           attn_out_bias=False, mlp_bias=False,
                           eps=1e-6, num_experts=4, moe_top_k=2,
                           moe_shared_ff=704, moe_norm_topk=False),
    "qwen2-moe-a2.7b": dict(vocab_size=151936, num_layers=24,
                            d_model=2048, num_heads=16, num_kv_heads=16,
                            d_ff=1408, max_seq_len=8192,
                            activation="silu", gated_mlp=True,
                            norm="rmsnorm", position="rope",
                            rope_theta=1000000.0, tie_embeddings=False,
                            attn_bias=True, attn_out_bias=False,
                            mlp_bias=False, eps=1e-6, num_experts=60,
                            moe_top_k=4, moe_shared_ff=5632,
                            moe_norm_topk=False),
    # --- OLMoE (every layer sparse: 64 small experts, top-8 with the raw
    # softmax probabilities; one RMSNorm over the whole q and the whole k
    # projection before rotary; as many kv heads as query heads —
    # allenai/OLMoE-1B-7B-0125-Instruct config.json + modeling_olmoe.py) --
    "olmoe-tiny": dict(vocab_size=1024, num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=4, d_ff=32,
                       max_seq_len=256, activation="silu", gated_mlp=True,
                       norm="rmsnorm", position="rope", rope_theta=10000.0,
                       tie_embeddings=False, attn_bias=False,
                       mlp_bias=False, eps=1e-5, qk_norm=True,
                       num_experts=8, moe_top_k=4, moe_norm_topk=False),
    "olmoe-1b-7b": dict(vocab_size=50304, num_layers=16, d_model=2048,
                        num_heads=16, num_kv_heads=16, d_ff=1024,
                        max_seq_len=4096, activation="silu",
                        gated_mlp=True, norm="rmsnorm", position="rope",
                        rope_theta=10000.0, tie_embeddings=False,
                        attn_bias=False, mlp_bias=False, eps=1e-5,
                        qk_norm=True, num_experts=64, moe_top_k=8,
                        moe_norm_topk=False),
    # --- Trinity (afmoe: three window layers of 2048 then one full layer
    # without positions; 32 query heads of 128 over 4 KV heads on a
    # hidden size of 2048; an RMSNorm per head on q and k; a sigmoid
    # output gate; four norms a layer; 128 sigmoid-scored experts, top-8
    # chosen with a bias that the weights leave out, renormalised and
    # scaled by 2.826, beside an ungated shared expert; two leading dense
    # layers of width 6144; embeddings scaled by sqrt(d) —
    # arcee-ai/Trinity-Mini config.json + modeling_afmoe.py).  The
    # published ``layer_types`` repeat (window, window, window, full)
    # from layer 0 and the two dense layers are its first two; the
    # pattern here starts BEHIND the dense layers (which are of its
    # first kind), so at the published depth it reads (window, full,
    # window, window): seven periods and two layers of an eighth ------
    "trinity-tiny": dict(vocab_size=1024, num_layers=9, d_model=64,
                         num_heads=4, num_kv_heads=2, head_dim=32,
                         d_ff=96, moe_d_ff=32, max_seq_len=256,
                         activation="silu", gated_mlp=True, norm="rmsnorm",
                         position="rope", rope_theta=10000.0,
                         rope_kinds=("window",), tie_embeddings=False,
                         attn_bias=False, mlp_bias=False, eps=1e-5,
                         qk_norm=True, qk_norm_form="head", attn_gate=True,
                         sandwich_norm=True, embed_scale=8.0,
                         layer_pattern=("window", "window", "window",
                                        "full"),
                         attn_window=16, num_dense_layers=1,
                         num_experts=8, moe_top_k=2, moe_shared_ff=32,
                         moe_shared_gate=False, moe_score="sigmoid",
                         moe_select_bias=True, moe_norm_topk=True,
                         moe_route_scale=2.826, moe_dispatch="ragged",
                         attention_impl="xla"),
    "trinity-mini": dict(vocab_size=200192, num_layers=32, d_model=2048,
                         num_heads=32, num_kv_heads=4, head_dim=128,
                         d_ff=6144, moe_d_ff=1024, max_seq_len=131072,
                         activation="silu", gated_mlp=True, norm="rmsnorm",
                         position="rope", rope_theta=10000.0,
                         rope_kinds=("window",), tie_embeddings=False,
                         attn_bias=False, mlp_bias=False, eps=1e-5,
                         qk_norm=True, qk_norm_form="head", attn_gate=True,
                         sandwich_norm=True, embed_scale=2048 ** 0.5,
                         layer_pattern=("window", "full", "window",
                                        "window"),
                         attn_window=2048, num_dense_layers=2,
                         num_experts=128, moe_top_k=8, moe_shared_ff=1024,
                         moe_shared_gate=False, moe_score="sigmoid",
                         moe_select_bias=True, moe_norm_topk=True,
                         moe_route_scale=2.826, moe_dispatch="ragged",
                         attention_impl="xla"),
    # --- Falcon-H1 (every block: a Mamba-2 mixer BESIDE grouped-query
    # attention, both reading the same normed input and summed, then a
    # gated MLP; fourteen constant multipliers; untied head —
    # tiiuae/Falcon-H1-34B-Instruct config.json + modeling_falcon_h1.py).
    # Another model than "falcon-7b" above (multi-query attention, a
    # parallel residual, no state-space layer) ---------------------------
    "falcon-h1-tiny": dict(vocab_size=1024, num_layers=4, d_model=64,
                           num_heads=4, num_kv_heads=2, head_dim=32,
                           d_ff=160, max_seq_len=256,
                           activation="silu", gated_mlp=True, norm="rmsnorm",
                           position="rope", rope_theta=1e6,
                           tie_embeddings=False, attn_bias=False,
                           mlp_bias=False, eps=1e-5,
                           layer_pattern=("hybrid",),
                           ssm_d=64, ssm_heads=4, ssm_head_dim=16,
                           ssm_groups=2, ssm_state=16, ssm_conv=4,
                           ssm_chunk=8,
                           embed_scale=4.0, head_scale=0.125,
                           attn_in_scale=0.75, attn_out_scale=0.3,
                           key_scale=0.4, ssm_in_scale=0.5,
                           ssm_out_scale=0.35, mlp_gate_scale=0.6,
                           mlp_out_scale=0.2,
                           ssm_col_scales=(0.7, 0.5, 0.35, 0.8, 0.6),
                           attention_impl="xla"),
    "falcon-h1-34b": dict(vocab_size=261120, num_layers=72, d_model=5120,
                          num_heads=20, num_kv_heads=4, head_dim=128,
                          d_ff=21504, max_seq_len=262144,
                          activation="silu", gated_mlp=True, norm="rmsnorm",
                          position="rope", rope_theta=1e11,
                          tie_embeddings=False, attn_bias=False,
                          mlp_bias=False, eps=1e-5,
                          layer_pattern=("hybrid",),
                          ssm_d=4096, ssm_heads=32, ssm_head_dim=128,
                          ssm_groups=2, ssm_state=256, ssm_conv=4,
                          ssm_chunk=128,
                          embed_scale=5.656854249492381,
                          head_scale=0.0078125,
                          attn_in_scale=1.0, attn_out_scale=0.0375,
                          key_scale=0.011048543456039804,
                          ssm_in_scale=0.25,
                          ssm_out_scale=0.08838834764831845,
                          mlp_gate_scale=0.1767766952966369,
                          mlp_out_scale=0.011160714285714284,
                          ssm_col_scales=(0.3535533905932738, 0.25,
                                          0.1767766952966369, 0.5,
                                          0.3535533905932738),
                          attention_impl="xla"),
    # --- Ling-3.0-flash (inclusionAI/Ling-3.0-flash config.json,
    # model_type bailing_hybrid): delta-rule linear attention with a
    # per-channel decay (KDA) in five layers of six and latent attention
    # (MLA, no query latent, a head-wise output gate) in the sixth; two
    # leading dense layers, then 512 sigmoid-routed experts in 8 groups
    # of which 4 stay, 8 a token, a selection bias, one shared expert.
    # Layer l is MLA where (l + 1) % 6 == 0: behind the two dense layers
    # the period reads kda, kda, kda, mla, kda, kda.  The multi-token-
    # prediction module is not part of the served forward; the SwiGLU
    # clamp of the last eight layers has no form in the config and none
    # here ---------------------------------------------------------------
    "ling-tiny": dict(vocab_size=1024, num_layers=7, d_model=64,
                      num_heads=4, head_dim=32, d_ff=160, max_seq_len=512,
                      activation="silu", gated_mlp=True, norm="rmsnorm",
                      position="rope", rope_theta=6e6, rope_pct=0.25,
                      tie_embeddings=False, attn_bias=False, mlp_bias=False,
                      eps=1e-6, layer_pattern=("kda", "kda", "mla"),
                      num_dense_layers=1,
                      kda_heads=4, kda_key_dim=16, kda_value_dim=16,
                      kda_conv=4, kda_chunk=64, kda_gate_bound=-5.0,
                      mla_kv_rank=16, mla_nope_dim=16, mla_rope_dim=8,
                      mla_value_dim=16, mla_gate="head",
                      num_experts=16, moe_top_k=4, moe_d_ff=48,
                      moe_shared_ff=48, moe_shared_gate=False,
                      moe_score="sigmoid", moe_select_bias=True,
                      moe_norm_topk=True, moe_route_scale=2.5,
                      moe_groups=4, moe_groups_kept=2,
                      moe_dispatch="ragged", attention_impl="xla"),
    "ling-3.0-flash": dict(vocab_size=157184, num_layers=42, d_model=2560,
                           num_heads=32, head_dim=128, d_ff=6144,
                           max_seq_len=262144,
                           activation="silu", gated_mlp=True, norm="rmsnorm",
                           position="rope", rope_theta=6e6, rope_pct=0.5,
                           tie_embeddings=False, attn_bias=False,
                           mlp_bias=False, eps=1e-6,
                           layer_pattern=("kda", "kda", "kda", "mla",
                                          "kda", "kda"),
                           num_dense_layers=2,
                           kda_heads=32, kda_key_dim=128, kda_value_dim=128,
                           kda_conv=4, kda_chunk=64, kda_gate_bound=-5.0,
                           mla_kv_rank=512, mla_nope_dim=128,
                           mla_rope_dim=64, mla_value_dim=128,
                           mla_gate="head",
                           num_experts=512, moe_top_k=8, moe_d_ff=768,
                           moe_shared_ff=768, moe_shared_gate=False,
                           moe_score="sigmoid", moe_select_bias=True,
                           moe_norm_topk=True, moe_route_scale=2.5,
                           moe_groups=8, moe_groups_kept=4,
                           moe_dispatch="ragged", attention_impl="xla"),
    # --- LongCat-Flash (meituan-longcat/LongCat-Flash-Chat config.json):
    # 28 shortcut-connected layers, each TWO latent-attention sublayers
    # (MLA with a query latent of 1536 and constant multipliers on both
    # normed latents) and two dense MLPs of width 12288, with ONE expert
    # layer that reads the first sublayer's normed MLP input and joins
    # the stream at the layer's end: 512 experts of width 2048 and 256
    # zero-compute (identity) experts behind one softmax router of 768
    # outputs, 12 a token, a selection bias, weights 6 x the unbiased
    # scores, not renormalised, no shared expert.  ``num_layers`` counts
    # sublayers (``moe_shortcut``) -----------------------------------------
    "longcat-tiny": dict(vocab_size=1024, num_layers=4, d_model=64,
                         num_heads=4, head_dim=16, d_ff=160,
                         max_seq_len=512, activation="silu", gated_mlp=True,
                         norm="rmsnorm", position="rope", rope_theta=1e7,
                         rope_pct=0.5, tie_embeddings=False, attn_bias=False,
                         mlp_bias=False, eps=1e-5,
                         layer_pattern=("mla", "mla"), kda_chunk=16,
                         mla_kv_rank=16, mla_nope_dim=16, mla_rope_dim=8,
                         mla_value_dim=16, mla_q_rank=24,
                         mla_scale_latents=True, moe_shortcut=True,
                         num_experts=16, moe_zero_experts=8, moe_top_k=4,
                         moe_d_ff=48, moe_score="softmax",
                         moe_select_bias=True, moe_norm_topk=False,
                         moe_route_scale=6.0, moe_dispatch="ragged",
                         attention_impl="xla"),
    "longcat-flash": dict(vocab_size=131072, num_layers=56, d_model=6144,
                          num_heads=64, head_dim=128, d_ff=12288,
                          max_seq_len=131072, activation="silu",
                          gated_mlp=True, norm="rmsnorm", position="rope",
                          rope_theta=1e7, rope_pct=0.5, tie_embeddings=False,
                          attn_bias=False, mlp_bias=False, eps=1e-5,
                          layer_pattern=("mla", "mla"), kda_chunk=64,
                          mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64,
                          mla_value_dim=128, mla_q_rank=1536,
                          mla_scale_latents=True, moe_shortcut=True,
                          num_experts=512, moe_zero_experts=256,
                          moe_top_k=12, moe_d_ff=2048, moe_score="softmax",
                          moe_select_bias=True, moe_norm_topk=False,
                          moe_route_scale=6.0, moe_dispatch="ragged",
                          attention_impl="xla"),
    # --- DeepSeek-V2 (deepseek-ai/DeepSeek-V2 config.json, model_type
    # deepseek_v2): latent attention with a query latent of 1536 in all
    # 60 layers, 128 heads (128 unrotated + 64 rotated, values of 128)
    # over a cached row of 512 + 64, no multipliers on the latents; YaRN
    # (factor 40 over 4096 original positions, mscale = mscale_all_dim =
    # 0.707: the table's multiplier is 1 and the softmax scale's 1.5896);
    # layer 0 dense at 12288, then 160 softmax-routed experts of width
    # 1536 in 8 device groups of which a token opens the 3 whose BEST
    # score is highest (``group_limited_greedy``), 6 a token, weights 16
    # x the scores and not renormalised, no bias, beside two shared
    # experts held as one ungated MLP of 3072 ----------------------------
    "deepseek-v2-tiny": dict(vocab_size=1024, num_layers=4, d_model=64,
                             num_heads=4, head_dim=16, d_ff=160,
                             max_seq_len=512, activation="silu",
                             gated_mlp=True, norm="rmsnorm",
                             position="rope", rope_theta=100.0,
                             rope_pct=0.5,
                             rope_yarn=(8.0, 64, 32.0, 1.0, 0.707, 0.707),
                             tie_embeddings=False, attn_bias=False,
                             mlp_bias=False, eps=1e-6,
                             layer_pattern=("mla",), num_dense_layers=1,
                             kda_chunk=16,
                             mla_kv_rank=16, mla_nope_dim=16, mla_rope_dim=8,
                             mla_value_dim=16, mla_q_rank=24,
                             num_experts=16, moe_top_k=4, moe_d_ff=48,
                             moe_shared_ff=96, moe_shared_gate=False,
                             moe_score="softmax", moe_norm_topk=False,
                             moe_route_scale=4.0, moe_groups=8,
                             moe_groups_kept=3, moe_group_score="max",
                             moe_dispatch="ragged", attention_impl="xla"),
    "deepseek-v2": dict(vocab_size=102400, num_layers=60, d_model=5120,
                        num_heads=128, head_dim=128, d_ff=12288,
                        max_seq_len=163840, activation="silu",
                        gated_mlp=True, norm="rmsnorm", position="rope",
                        rope_theta=10000.0, rope_pct=0.5,
                        rope_yarn=(40.0, 4096, 32.0, 1.0, 0.707, 0.707),
                        tie_embeddings=False, attn_bias=False,
                        mlp_bias=False, eps=1e-6,
                        layer_pattern=("mla",), num_dense_layers=1,
                        kda_chunk=64,
                        mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64,
                        mla_value_dim=128, mla_q_rank=1536,
                        num_experts=160, moe_top_k=6, moe_d_ff=1536,
                        moe_shared_ff=3072, moe_shared_gate=False,
                        moe_score="softmax", moe_norm_topk=False,
                        moe_route_scale=16.0, moe_groups=8,
                        moe_groups_kept=3, moe_group_score="max",
                        moe_dispatch="ragged", attention_impl="xla"),
    # --- Granite-4.0-H (ibm-granite/granite-4.0-h-small config.json,
    # model_type granitemoehybrid): a layer holds ONE mixer, a Mamba-2
    # mixer (128 heads of 64, state 128, ONE group, chunk 256) in nine
    # layers of ten and grouped-query attention with NO positions in the
    # sixth (published layers 5, 15, 25, 35); every layer's feed-forward
    # is 72 softmax-routed experts of width 768, ten a token (softmax
    # over the ten chosen logits), beside an ungated shared MLP of width
    # 1536.  One multiplier on both residual branches (0.22), on the
    # embedding (12), the scores (1/128) and the logits (1/16); the
    # embedding is tied.  ``d_ff`` is the file's ``intermediate_size``,
    # an expert's width: no layer holds a dense MLP of it ------------------
    "granite-h-tiny": dict(vocab_size=1024, num_layers=6, d_model=64,
                           num_heads=4, num_kv_heads=2, d_ff=32,
                           max_seq_len=512, activation="silu",
                           gated_mlp=True, norm="rmsnorm", position="none",
                           tie_embeddings=True, attn_bias=False,
                           mlp_bias=False, eps=1e-5,
                           layer_pattern=("mamba", "mamba", "full",
                                          "mamba"),
                           ssm_d=128, ssm_heads=8, ssm_head_dim=16,
                           ssm_groups=1, ssm_state=16, ssm_conv=4,
                           ssm_chunk=8,
                           embed_scale=6.0, head_scale=0.25,
                           attn_scale=0.125, residual_scale=0.3,
                           num_experts=8, moe_top_k=3, moe_shared_ff=64,
                           moe_shared_gate=False, moe_score="softmax",
                           moe_norm_topk=True, moe_dispatch="ragged",
                           attention_impl="xla"),
    "granite-4.0-h-small": dict(vocab_size=100352, num_layers=40,
                                d_model=4096, num_heads=32, num_kv_heads=8,
                                d_ff=768, max_seq_len=131072,
                                activation="silu", gated_mlp=True,
                                norm="rmsnorm", position="none",
                                tie_embeddings=True, attn_bias=False,
                                mlp_bias=False, eps=1e-5,
                                layer_pattern=("mamba",) * 5 + ("full",)
                                + ("mamba",) * 4,
                                ssm_d=8192, ssm_heads=128, ssm_head_dim=64,
                                ssm_groups=1, ssm_state=128, ssm_conv=4,
                                ssm_chunk=256,
                                embed_scale=12.0, head_scale=0.0625,
                                attn_scale=0.0078125, residual_scale=0.22,
                                num_experts=72, moe_top_k=10,
                                moe_shared_ff=1536, moe_shared_gate=False,
                                moe_score="softmax", moe_norm_topk=True,
                                moe_dispatch="ragged",
                                attention_impl="xla"),
    # --- Megatron-GPT (gpt2 architecture, megatron-lm checkpoint naming
    # with per-head-interleaved fused QKV — reference:
    # module_inject/containers/megatron_gpt.py) ---------------------------
    "megatron-gpt2-345m": dict(vocab_size=50304, num_layers=24,
                               d_model=1024, num_heads=16,
                               max_seq_len=1024, activation="gelu_new",
                               norm="layernorm", position="learned",
                               tie_embeddings=True),
}


def build_config(name: str, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise ValueError(f"Unknown model preset {name!r}; "
                         f"known: {sorted(PRESETS)}")
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_model(name: str, seed: int = 0, **overrides) -> Model:
    return Model(build_config(name, **overrides), seed=seed)
