"""The benchmark's plain reference for ``granite-4.0-h-small-d10``
(``benchmarks/reference/granite-4.0-h-small-d10.py``, imported by path)
at the tiny preset's sizes (``granite-h-tiny``: a Mamba-2 mixer alone in
most layers, grouped-query attention without positions in the rest,
softmax-routed experts beside a shared MLP in every layer): against
``transformers``' ``GraniteMoeHybridForCausalLM`` on the seeded weights,
every multiplier away from one; ``apply`` against it; the two halves of an
expert layer adding up to the uncut layer.  The engine against it:
``tests/test_granite_hybrid.py``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import apply, init_params
from deepspeed_tpu.parallel import moe as M
from test_falcon_h1 import TOL, _load, rel


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/granite-4.0-h-small-d10.py",
                 "granite_ref")


def seeded(cfg):
    axes = {}

    def init(key):          # one compiled call, not an operation at a time
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(3)), axes["axes"]


@pytest.fixture(scope="module")
def tiny():
    return seeded(build_config("granite-h-tiny"))


def ref_config(cfg):
    """What the reference reads of a configuration file, for ``cfg``."""
    return dict(
        num_hidden_layers=cfg.num_layers, rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta, attention_multiplier=cfg.attn_scale,
        embedding_multiplier=cfg.embed_scale,
        logits_scaling=1.0 / cfg.head_scale,
        residual_multiplier=cfg.residual_scale,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state,
        mamba_d_conv=cfg.ssm_conv, num_experts_per_tok=cfg.moe_top_k,
        num_local_experts=cfg.num_experts,
        experts_held=list(cfg.experts_held or (0, cfg.num_experts)),
        layer_types=["attention" if k == "full" else k
                     for k in cfg.layer_kinds])


def test_tiny_preset_is_the_block(tiny):
    cfg, params, _ = tiny
    assert cfg.has_ssm and cfg.recurrent_kind == "mamba"
    assert not cfg.plain_stack and cfg.mixer_stacks == ("mamba", "full")
    # a period of four and a last one cut short
    assert cfg.layer_plan == (0, 1, 2)
    assert cfg.layer_kinds == ("mamba", "mamba", "full", "mamba", "mamba",
                               "mamba")
    assert cfg.position == "none" and cfg.tie_embeddings
    assert cfg.block_layers == 1 and cfg.layers_of("mamba") == 5
    # every multiplier away from one, the scores' away from 1/sqrt(D)
    assert all(m != 1.0 for m in (cfg.embed_scale, cfg.head_scale,
                                  cfg.residual_scale))
    assert cfg.attn_scale != cfg.head_dim ** -0.5
    # a mixer's stack holds the layers of its kind and no other
    b = params["blocks"]
    assert b["mamba"]["w_in"].shape == (5, 64, cfg.ssm_dims.in_proj)
    assert b["full"]["wq"].shape == (1, 64, 4, 16)
    assert "attn" not in b and "ssm" not in b and "mlp" not in b
    assert b["experts"]["wi"].shape == (6, 8, 64, 32)
    assert b["shared"]["wi"].shape == (6, 64, 64) \
        and "gate" not in b["shared"]
    assert "pos_embed" not in params and "lm_head" not in params


def test_published_preset_is_the_catalog_entry():
    cfg = build_config("granite-4.0-h-small")
    sd = cfg.ssm_dims
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.moe_d_ff, cfg.moe_shared_ff, cfg.vocab_size,
            cfg.num_experts, cfg.moe_top_k) \
        == (40, 4096, 32, 8, 128, 768, 1536, 100352, 72, 10)
    assert (sd.heads, sd.head_dim, sd.groups, sd.state, sd.chunk,
            sd.in_proj, sd.conv_channels) == (128, 64, 1, 128, 256, 16768,
                                              8448)
    assert cfg.layer_plan == (0, 4, 0)
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "full"] \
        == [5, 15, 25, 35]
    assert (cfg.embed_scale, cfg.attn_scale, cfg.residual_scale,
            1 / cfg.head_scale) == (12.0, 1 / 128, 0.22, 16.0)


def test_reference_agrees_with_transformers(tiny, ref):
    """The plain reference against the modelling code the configuration
    names, on the seeded weights, all eight experts held."""
    # (the modelling code needs no TensorFlow, and importing it is a
    # third of this test)
    os.environ.setdefault("USE_TF", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers")
    if not hasattr(hf, "GraniteMoeHybridForCausalLM"):
        pytest.skip("transformers has no GraniteMoeHybridForCausalLM")
    cfg, params, _ = tiny
    c = ref_config(cfg)
    sd = cfg.ssm_dims
    hc = hf.GraniteMoeHybridConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.moe_d_ff, num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, hidden_act="silu",
        rms_norm_eps=cfg.eps, tie_word_embeddings=True,
        attention_bias=False, attention_dropout=0.0,
        embedding_multiplier=cfg.embed_scale,
        logits_scaling=c["logits_scaling"],
        residual_multiplier=cfg.residual_scale,
        attention_multiplier=cfg.attn_scale,
        num_local_experts=cfg.num_experts,
        num_experts_per_tok=cfg.moe_top_k,
        shared_intermediate_size=cfg.moe_shared_ff,
        position_embedding_type="nope", layer_types=c["layer_types"],
        mamba_n_heads=sd.heads, mamba_n_groups=sd.groups,
        mamba_d_state=sd.state, mamba_d_head=sd.head_dim,
        mamba_d_conv=sd.conv, mamba_expand=sd.d_ssm // cfg.d_model,
        mamba_chunk_size=sd.chunk, mamba_conv_bias=True,
        mamba_proj_bias=False, attn_implementation="eager")
    model = hf.GraniteMoeHybridForCausalLM(hc).to(torch.float32).eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    b = params["blocks"]
    state = {"model.embed_tokens.weight": t(params["embed"]["table"]),
             "lm_head.weight": t(params["embed"]["table"]),
             "model.norm.weight": t(params["ln_f"]["scale"])}
    kinds = cfg.layer_kinds
    d = cfg.d_model
    for i, kind in enumerate(kinds):
        p = f"model.layers.{i}."
        rank = kinds[:i].count(kind)
        state[p + "input_layernorm.weight"] = t(b["ln1"]["scale"][i])
        state[p + "post_attention_layernorm.weight"] = t(b["ln2"]["scale"][i])
        if kind == "full":
            a = jax.tree.map(lambda w: w[rank], b["full"])
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj")):
                state[p + f"self_attn.{theirs}.weight"] = t(
                    a[ours].reshape(d, -1).T)
            state[p + "self_attn.o_proj.weight"] = t(
                a["wo"].reshape(-1, d).T)
        else:
            m = jax.tree.map(lambda w: w[rank], b["mamba"])
            state.update({
                p + "mamba.in_proj.weight": t(m["w_in"].T),
                p + "mamba.conv1d.weight": t(m["conv_w"][:, None, :]),
                p + "mamba.conv1d.bias": t(m["conv_b"]),
                p + "mamba.dt_bias": t(m["dt_bias"]),
                p + "mamba.A_log": t(m["A_log"]), p + "mamba.D": t(m["D"]),
                p + "mamba.norm.weight": t(m["norm"]),
                p + "mamba.out_proj.weight": t(m["w_out"].T)})
        e = jax.tree.map(lambda w: w[i], b["experts"])
        # [a | b] in one matrix: silu(a) * b
        state[p + "block_sparse_moe.input_linear.weight"] = t(
            jnp.concatenate([e["wg"], e["wi"]], -1).transpose(0, 2, 1))
        state[p + "block_sparse_moe.output_linear.weight"] = t(
            e["wo"].transpose(0, 2, 1))
        state[p + "block_sparse_moe.router.layer.weight"] = t(
            b["gate"]["kernel"][i].T)
        s = jax.tree.map(lambda w: w[i], b["shared"])
        state[p + "shared_mlp.input_linear.weight"] = t(
            jnp.concatenate([s["wg"], s["wi"]], -1).T)
        state[p + "shared_mlp.output_linear.weight"] = t(s["wo"].T)
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not [k for k in missing
                                   if "rotary" not in k], (missing,
                                                           unexpected)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 29)
    with torch.no_grad():
        want = model(torch.tensor(ids)[None]).logits[0].numpy()
    got = np.asarray(ref.logits(params, ids, c))
    assert rel(got, want) < TOL


@pytest.mark.parametrize("n", [37, 8])
def test_apply_agrees_with_the_reference(tiny, ref, n):
    """Lengths that the mixer's chunk of 8 divides and does not."""
    cfg, params, _ = tiny
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, n)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: apply(cfg, p, i))(
            params, jnp.asarray(ids)[None]))[0]
    assert rel(got, want) < TOL


# ---- a share of the experts -------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(tiny, ref):
    """The expert layer run twice, each holding half of the experts (the
    deployment's ``(0, 36)`` and ``(36, 36)``): the routed parts summed,
    the shared MLP counted once, equal the uncut reference's layer."""
    cfg, params, _ = tiny
    b = params["blocks"]
    gate, experts, shared = (jax.tree.map(lambda a: a[1], b[k])
                             for k in ("gate", "experts", "shared"))
    h = jax.random.normal(jax.random.PRNGKey(1), (23, cfg.d_model))
    kw = dict(top_k=cfg.moe_top_k, activation=jax.nn.silu, gated=True,
              norm_topk=True, score="softmax")
    # (the reference takes the experts as the stack of all layers)
    stack = lambda e: jax.tree.map(lambda a: a[None], e)   # noqa: E731
    lp = {"gate": gate, "experts": stack(experts), "shared": shared}
    with jax.default_matmul_precision("highest"):
        own = jnp.full((23, cfg.moe_top_k), -1)
        want, _ = ref._experts(h, lp, ref_config(cfg), None, own)
        sh = ref._swiglu(h, shared)
        whole, stats = M.moe_serve(gate, experts, h, **kw)
        parts, computed = 0.0, 0
        for first in (0, 4):
            mine = jax.tree.map(lambda a: a[first:first + 4], experts)
            y, st, ids = M.moe_serve(gate, mine, h, held=(first, 4),
                                     with_ids=True, **kw)
            parts = parts + y
            computed += int(st[0])
            # the router's own numbering, all of its choices
            assert ids.shape == (23, cfg.moe_top_k) and int(ids.max()) > 3
            # and against the reference that holds the same share
            c = dict(ref_config(cfg), experts_held=[first, 4])
            one, _ = ref._experts(h, {**lp, "experts": stack(mine)}, c,
                                  None, own)
            assert rel(np.asarray(y), np.asarray(one - sh)) < TOL
    assert computed == int(stats[0]) == 23 * cfg.moe_top_k
    assert rel(np.asarray(parts + sh), np.asarray(want)) < TOL
    assert rel(np.asarray(whole + sh), np.asarray(want)) < TOL
