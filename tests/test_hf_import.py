"""HF checkpoint import parity: convert REAL (tiny, randomly initialized)
transformers models and match logits (reference analog: the AutoTP /
module_inject injection tests and inference/v2 model implementations —
here parity is end-to-end numerics, not per-module)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeed_tpu.checkpoint.hf import family_of, load_hf_state_dict
from deepspeed_tpu.models import build_model


def _logits_close(model, hf_model, ids, atol=2e-3):
    params = load_hf_state_dict(model.config, hf_model.state_dict(),
                                family=hf_model.config.model_type,
                                reference_params=model.params)
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.float().numpy()
    got = np.asarray(model.apply(
        jax.tree.map(jnp.asarray, params), jnp.asarray(ids),
        dtype=jnp.float32))
    np.testing.assert_allclose(got, ref, atol=atol, rtol=1e-3)


IDS = np.random.RandomState(0).randint(1, 250, (2, 16))


class TestHFParity:
    def test_gpt2(self):
        from transformers import GPT2Config, GPT2LMHeadModel
        hf = GPT2LMHeadModel(GPT2Config(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
            n_head=4, activation_function="gelu_new",
            attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)).eval()
        m = build_model("gpt2", vocab_size=256, num_layers=2, d_model=64,
                        num_heads=4, max_seq_len=64)
        _logits_close(m, hf, IDS)

    def test_llama_gqa(self):
        from transformers import LlamaConfig, LlamaForCausalLM
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            rms_norm_eps=1e-5)).eval()
        m = build_model("llama-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        max_seq_len=64)
        _logits_close(m, hf, IDS)

    def test_falcon_mqa_parallel(self):
        from transformers import FalconConfig, FalconForCausalLM
        hf = FalconForCausalLM(FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=1, new_decoder_architecture=False,
            multi_query=True, parallel_attn=True, bias=False,
            max_position_embeddings=64, rope_theta=10000.0,
            attention_dropout=0.0, hidden_dropout=0.0, alibi=False)).eval()
        m = build_model("falcon-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=1,
                        max_seq_len=64)
        _logits_close(m, hf, IDS)

    def test_phi_partial_rotary(self):
        from transformers import PhiConfig, PhiForCausalLM
        hf = PhiForCausalLM(PhiConfig(
            vocab_size=256, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            partial_rotary_factor=0.5, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            embd_pdrop=0.0, resid_pdrop=0.0)).eval()
        m = build_model("phi-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, d_ff=256, rope_pct=0.5,
                        max_seq_len=64)
        _logits_close(m, hf, IDS)

    def test_mixtral_moe(self):
        from transformers import MixtralConfig, MixtralForCausalLM
        hf = MixtralForCausalLM(MixtralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, num_local_experts=4,
            num_experts_per_tok=2, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            router_jitter_noise=0.0)).eval()
        m = build_model("mixtral-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        num_experts=4, moe_top_k=2, max_seq_len=64,
                        # large capacity: HF routes without dropping
                        capacity_factor=4.0)
        params = load_hf_state_dict(m.config, hf.state_dict(),
                                    family="mixtral",
                                    reference_params=m.params)
        with torch.no_grad():
            ref = hf(torch.tensor(IDS)).logits.float().numpy()
        got = np.asarray(m.apply(
            jax.tree.map(jnp.asarray, params), jnp.asarray(IDS),
            dtype=jnp.float32))
        # MoE routing uses capacity limits; allow slightly looser match
        np.testing.assert_allclose(got, ref, atol=2e-2, rtol=1e-2)

    def test_family_detection(self):
        assert family_of("mixtral-8x7b") == "mixtral"
        assert family_of("tiiuae/falcon-7b") == "falcon"
        assert family_of("microsoft/phi-2") == "phi"
        assert family_of("meta-llama/Llama-3-8B") == "llama"


class TestHFParityNewFamilies:
    def test_qwen2_gqa_qkv_bias(self):
        """qwen2: llama layout + q/k/v biases, no o bias."""
        from transformers import Qwen2Config, Qwen2ForCausalLM
        hf = Qwen2ForCausalLM(Qwen2Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            rms_norm_eps=1e-6, tie_word_embeddings=False)).eval()
        m = build_model("qwen2-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        max_seq_len=64, rope_theta=10000.0)
        assert "bq" in m.params["blocks"]["attn"]
        assert "bo" not in m.params["blocks"]["attn"]
        _logits_close(m, hf, IDS)

    def test_gptj_partial_rotary_parallel(self):
        """gpt-j: interleaved partial rotary (converter permutes to the
        half-split convention) + single-LN parallel residual."""
        from transformers import GPTJConfig, GPTJForCausalLM
        hf = GPTJForCausalLM(GPTJConfig(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
            n_head=4, rotary_dim=8, activation_function="gelu_new",
            attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)).eval()
        m = build_model("gptj-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, max_seq_len=64,
                        rope_pct=0.5)        # rotary_dim 8 of head_dim 16
        _logits_close(m, hf, IDS)

    def test_bloom_alibi_embed_norm(self):
        """bloom: ALiBi position (no table, per-head key-position bias),
        word-embedding layernorm, head-interleaved fused
        query_key_value, tied embeddings."""
        from transformers import BloomConfig, BloomForCausalLM
        hf = BloomForCausalLM(BloomConfig(
            vocab_size=256, hidden_size=64, n_layer=2, n_head=4,
            attention_dropout=0.0, hidden_dropout=0.0,
            layer_norm_epsilon=1e-5, tie_word_embeddings=True)).eval()
        m = build_model("bloom-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, max_seq_len=64)
        assert "ln_embed" in m.params
        assert "pos_embed" not in m.params
        _logits_close(m, hf, IDS)

    def test_bloom_trains(self):
        """ALiBi end-to-end through the engine (eager attention)."""
        import deepspeed_tpu as ds
        m = build_model("bloom-tiny", vocab_size=128, num_layers=2,
                        d_model=32, num_heads=4, max_seq_len=32)
        eng = ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "mesh": {"data": 8}, "steps_per_print": 1000})
        ids = np.random.RandomState(0).randint(
            0, 128, (eng.train_batch_size, 32))
        losses = [float(np.asarray(eng.train_batch(
            {"input_ids": ids})["loss"])) for _ in range(4)]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_gpt_neox_separate_norm_parallel(self):
        """gpt-neox/pythia: parallel residual with SEPARATE ln1/ln2
        (attn reads ln1(x), mlp reads ln2(x)) + fused head-interleaved
        query_key_value + partial half-split rotary."""
        from transformers import GPTNeoXConfig, GPTNeoXForCausalLM
        hf = GPTNeoXForCausalLM(GPTNeoXConfig(
            vocab_size=256, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, rotary_pct=0.25,
            use_parallel_residual=True, attention_dropout=0.0,
            hidden_dropout=0.0, layer_norm_eps=1e-5)).eval()
        m = build_model("gpt-neox-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, max_seq_len=64)
        _logits_close(m, hf, IDS)


class TestBertEncoder:
    """BERT-class encoder family (reference containers:
    module_inject/containers/bert.py:13, distil_bert.py)."""

    def _pair(self):
        from transformers import BertConfig, BertModel
        from deepspeed_tpu.models.encoder import Encoder, EncoderConfig
        hf = BertModel(BertConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64, type_vocab_size=2,
            hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0)).eval()
        cfg = EncoderConfig(vocab_size=256, d_model=64, num_layers=2,
                            num_heads=4, d_ff=128, max_seq_len=64)
        from deepspeed_tpu.checkpoint.hf import load_hf_bert
        params = jax.tree.map(
            jnp.asarray, load_hf_bert(cfg, hf.state_dict()))
        return hf, Encoder.from_params(cfg, params)

    def test_hidden_and_pooled_parity(self):
        hf, enc = self._pair()
        ids = np.random.RandomState(1).randint(1, 250, (2, 12))
        mask = np.ones_like(ids)
        mask[1, 8:] = 0
        types = np.zeros_like(ids)
        types[0, 6:] = 1
        with torch.no_grad():
            out = hf(torch.tensor(ids), attention_mask=torch.tensor(mask),
                     token_type_ids=torch.tensor(types))
        from deepspeed_tpu.models.encoder import encode, pooled
        h = encode(enc.config, enc.params, jnp.asarray(ids),
                   attention_mask=jnp.asarray(mask),
                   token_type_ids=jnp.asarray(types))
        # padded positions are garbage on both sides; compare live ones
        got = np.asarray(h)
        ref = out.last_hidden_state.numpy()
        np.testing.assert_allclose(got[0], ref[0], atol=2e-3, rtol=1e-3)
        np.testing.assert_allclose(got[1, :8], ref[1, :8],
                                   atol=2e-3, rtol=1e-3)
        np.testing.assert_allclose(
            np.asarray(pooled(enc.config, enc.params, h)),
            out.pooler_output.numpy(), atol=2e-3, rtol=1e-3)

    def test_encode_batch_serving(self):
        """The embedding-serving surface: ragged requests, bucketed
        padding, CLS/mean pooling."""
        _, enc = self._pair()
        reqs = [[5, 17, 99], [3, 1, 4, 1, 5, 9, 2, 6], [42]]
        embs = enc.encode_batch(reqs, pool="cls")
        assert embs.shape == (3, 64)
        means = enc.encode_batch(reqs, pool="mean")
        assert means.shape == (3, 64)
        # padding must not leak: same request alone == in a batch
        solo = enc.encode_batch([reqs[1]], pool="cls")
        np.testing.assert_allclose(solo[0], embs[1], atol=1e-5)

    def test_fresh_encoder_trains_nothing_but_runs(self):
        """Random-init Encoder forward runs standalone (no HF)."""
        from deepspeed_tpu.models import Encoder, EncoderConfig
        enc = Encoder(EncoderConfig(vocab_size=64, d_model=32,
                                    num_layers=2, num_heads=2,
                                    max_seq_len=32))
        out = enc.encode_batch([[1, 2, 3], [4, 5]], pool="none")
        assert out[0].shape == (3, 32) and out[1].shape == (2, 32)

    def test_distilbert_parity(self):
        """DistilBERT: no segment embeddings, no pooler, q_lin naming
        (reference container: distil_bert.py)."""
        from transformers import DistilBertConfig, DistilBertModel
        from deepspeed_tpu.models.encoder import (Encoder, EncoderConfig,
                                                  encode)
        from deepspeed_tpu.checkpoint.hf import load_hf_distilbert
        hf = DistilBertModel(DistilBertConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=4,
            hidden_dim=128, max_position_embeddings=64,
            dropout=0.0, attention_dropout=0.0)).eval()
        cfg = EncoderConfig(vocab_size=256, d_model=64, num_layers=2,
                            num_heads=4, d_ff=128, max_seq_len=64,
                            type_vocab_size=0, pooler=False)
        params = jax.tree.map(jnp.asarray,
                              load_hf_distilbert(cfg, hf.state_dict()))
        ids = np.random.RandomState(2).randint(1, 250, (2, 10))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).last_hidden_state.numpy()
        got = np.asarray(encode(cfg, params, jnp.asarray(ids)))
        np.testing.assert_allclose(got, ref, atol=2e-3, rtol=1e-3)
        enc = Encoder.from_params(cfg, params)
        embs = enc.encode_batch([[5, 3], [9, 8, 7]], pool="mean")
        assert embs.shape == (2, 64)


class TestNewFamilies:
    """Round-5 serving families (reference: phi3/policy.py,
    qwen_v2_moe/model.py, containers/internlm.py, containers/gptneo.py,
    containers/megatron_gpt.py)."""

    def test_phi3_fused_qkv_gateup(self):
        from transformers import Phi3Config, Phi3ForCausalLM
        hf = Phi3ForCausalLM(Phi3Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            resid_pdrop=0.0, embd_pdrop=0.0, rms_norm_eps=1e-5,
            pad_token_id=0)).eval()
        m = build_model("phi3-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, d_ff=128, max_seq_len=64)
        _logits_close(m, hf, IDS)

    def test_internlm_biased_llama(self):
        """InternLM-1 = llama layout + q/k/v/o biases (HF expresses it
        as LlamaConfig(attention_bias=True))."""
        from transformers import LlamaConfig, LlamaForCausalLM
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            attention_bias=True, attention_dropout=0.0,
            rms_norm_eps=1e-6)).eval()
        m = build_model("internlm-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, d_ff=128, max_seq_len=64)
        params = load_hf_state_dict(m.config, hf.state_dict(),
                                    family="internlm",
                                    reference_params=m.params)
        with torch.no_grad():
            ref = hf(torch.tensor(IDS)).logits.float().numpy()
        got = np.asarray(m.apply(jax.tree.map(jnp.asarray, params),
                                 jnp.asarray(IDS), dtype=jnp.float32))
        np.testing.assert_allclose(got, ref, atol=2e-3, rtol=1e-3)

    def test_gpt_neo_unscaled_attention(self):
        from transformers import GPTNeoConfig, GPTNeoForCausalLM
        hf = GPTNeoForCausalLM(GPTNeoConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_position_embeddings=64, intermediate_size=256,
            attention_types=[[["global", "local"], 1]], window_size=256,
            attention_dropout=0.0, embed_dropout=0.0,
            resid_dropout=0.0)).eval()
        m = build_model("gpt-neo-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, max_seq_len=64)
        assert m.config.attn_scale == 1.0
        _logits_close(m, hf, IDS)

    def test_qwen2_moe_shared_expert(self):
        from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM
        torch.manual_seed(0)    # near-tie routing is seed-sensitive
        hf = Qwen2MoeForCausalLM(Qwen2MoeConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=96, shared_expert_intermediate_size=160,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, num_experts=4, num_experts_per_tok=2,
            norm_topk_prob=False, decoder_sparse_step=1,
            max_position_embeddings=64, rope_theta=10000.0,
            attention_dropout=0.0, rms_norm_eps=1e-6,
            output_router_logits=False)).eval()
        m = build_model("qwen2-moe-tiny", vocab_size=256, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2,
                        d_ff=96, moe_shared_ff=160, max_seq_len=64,
                        num_experts=4, moe_top_k=2,
                        capacity_factor=4.0)     # dropless at test scale
        # routed-expert accumulation order differs from torch's dense
        # loop — tolerance covers f32 round-off, not routing flips
        _logits_close(m, hf, IDS, atol=8e-3)

    def test_megatron_interleaved_qkv_roundtrip(self):
        """No transformers class for raw megatron-lm checkpoints: pack a
        known core model INTO megatron naming (per-head interleaved
        fused QKV), convert back, and require identical logits."""
        from deepspeed_tpu.checkpoint.hf import load_hf_state_dict
        m = build_model("megatron-gpt2-345m", vocab_size=256,
                        num_layers=2, d_model=64, num_heads=4,
                        max_seq_len=64)
        p = jax.tree.map(np.asarray, m.params)
        H, D, dm = 4, 16, 64
        sd = {"language_model.embedding.word_embeddings.weight":
              p["embed"]["table"],
              "language_model.embedding.position_embeddings.weight":
              p["pos_embed"]["table"],
              "language_model.transformer.final_layernorm.weight":
              p["ln_f"]["scale"],
              "language_model.transformer.final_layernorm.bias":
              p["ln_f"]["bias"]}
        for i in range(2):
            a = {k: v[i] for k, v in p["blocks"]["attn"].items()}
            # [dm,H,D] -> per-head interleaved [H,3,D,dm] -> [3HD, dm]
            w = np.stack([np.transpose(a["wq"], (1, 2, 0)),
                          np.transpose(a["wk"], (1, 2, 0)),
                          np.transpose(a["wv"], (1, 2, 0))], axis=1)
            b = np.stack([a["bq"], a["bk"], a["bv"]], axis=1)
            Lp = f"language_model.transformer.layers.{i}."
            sd[Lp + "attention.query_key_value.weight"] = \
                w.reshape(H * 3 * D, dm)
            sd[Lp + "attention.query_key_value.bias"] = \
                b.reshape(H * 3 * D)
            sd[Lp + "attention.dense.weight"] = \
                a["wo"].reshape(H * D, dm).T
            sd[Lp + "attention.dense.bias"] = a["bo"]
            mlp = {k: v[i] for k, v in p["blocks"]["mlp"].items()}
            sd[Lp + "mlp.dense_h_to_4h.weight"] = mlp["wi"].T
            sd[Lp + "mlp.dense_h_to_4h.bias"] = mlp["bi"]
            sd[Lp + "mlp.dense_4h_to_h.weight"] = mlp["wo"].T
            sd[Lp + "mlp.dense_4h_to_h.bias"] = mlp["bo"]
            for ln, nm in (("ln1", "input_layernorm"),
                           ("ln2", "post_attention_layernorm")):
                sd[Lp + nm + ".weight"] = p["blocks"][ln]["scale"][i]
                sd[Lp + nm + ".bias"] = p["blocks"][ln]["bias"][i]
        params = load_hf_state_dict(m.config, sd, family="megatron",
                                    reference_params=m.params)
        got = np.asarray(m.apply(jax.tree.map(jnp.asarray, params),
                                 jnp.asarray(IDS), dtype=jnp.float32))
        ref = np.asarray(m.apply(m.params, jnp.asarray(IDS),
                                 dtype=jnp.float32))
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


class TestCLIP:
    """CLIP dual-tower (reference container:
    module_inject/containers/clip.py:13)."""

    def _pair(self):
        from transformers import CLIPConfig as HFCLIPConfig, CLIPModel
        from deepspeed_tpu.models.clip import (CLIP, CLIPConfig,
                                               CLIPTowerConfig)
        from deepspeed_tpu.checkpoint.hf import load_hf_clip
        torch.manual_seed(0)
        from transformers import CLIPTextConfig, CLIPVisionConfig
        hf = CLIPModel(HFCLIPConfig.from_text_vision_configs(
            CLIPTextConfig(
                vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=32, hidden_act="quick_gelu",
                attention_dropout=0.0,
                # our encode_text pools at the highest token id (the
                # original-CLIP EOT convention); align HF's eos pooling
                eos_token_id=255),
            CLIPVisionConfig(
                hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                image_size=32, patch_size=8, hidden_act="quick_gelu",
                attention_dropout=0.0),
            projection_dim=48)).eval()
        cfg = CLIPConfig(
            embed_dim=48, image_size=32, patch_size=8, vocab_size=256,
            max_text_len=32,
            vision=CLIPTowerConfig(width=64, num_layers=2, num_heads=4,
                                   d_ff=128),
            text=CLIPTowerConfig(width=64, num_layers=2, num_heads=4,
                                 d_ff=128))
        m = CLIP.from_params(cfg, jax.tree.map(
            jnp.asarray, load_hf_clip(cfg, hf.state_dict())))
        return hf, m

    def test_dual_tower_parity(self):
        hf, m = self._pair()
        r = np.random.RandomState(0)
        imgs = r.randn(2, 32, 32, 3).astype(np.float32)
        ids = r.randint(1, 250, (3, 10)).astype(np.int64)
        ids[:, -1] = 255                       # EOT = highest id
        with torch.no_grad():
            out = hf(input_ids=torch.tensor(ids),
                     pixel_values=torch.tensor(
                         np.transpose(imgs, (0, 3, 1, 2))))
            # forward() returns NORMALIZED embeds; the unnormalized
            # tower outputs come from get_*_features
            img_ref = hf.get_image_features(torch.tensor(
                np.transpose(imgs, (0, 3, 1, 2)))).numpy()
            txt_ref = hf.get_text_features(torch.tensor(ids)).numpy()
        np.testing.assert_allclose(
            np.asarray(m.encode_image(jnp.asarray(imgs))),
            img_ref, atol=2e-3, rtol=1e-3)
        np.testing.assert_allclose(
            np.asarray(m.encode_text(jnp.asarray(ids))),
            txt_ref, atol=2e-3, rtol=1e-3)
        lpi, lpt = m.similarity(jnp.asarray(imgs), jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(lpi),
                                   out.logits_per_image.numpy(),
                                   atol=5e-3, rtol=1e-3)

    def test_retrieval_smoke(self):
        """Serving surface: embed a gallery, rank against a query."""
        _, m = self._pair()
        r = np.random.RandomState(1)
        gallery = jnp.asarray(r.randn(4, 32, 32, 3), jnp.float32)
        q = np.full((1, 8), 5, np.int64); q[0, -1] = 255
        lpi, _ = m.similarity(gallery, jnp.asarray(q))
        assert lpi.shape == (4, 1)
        assert np.isfinite(np.asarray(lpi)).all()
