"""Seeded random weights, made on the device in ONE jitted call, in the
type they are used in (serving: bf16, cast inside the initializer so the
fp32 tensors never all exist; training: fp32 masters)."""

import jax


def transformer_config(config: dict, **extra):
    """The repo's preset named in the configuration file, checked against
    the published sizes and switches the file states (widths, depth,
    rotary share and base, norm and its eps, residual form, biases), so
    the file cannot say one thing and run another."""
    from deepspeed_tpu.models.presets import build_config

    sysm = config["system"]
    cfg = build_config(sysm["preset"], **sysm.get("overrides", {}), **extra)
    same = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.d_model // cfg.num_heads,
            "num_hidden_layers": cfg.num_layers,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta, "rotary_emb_base": cfg.rope_theta,
            "rotary_pct": cfg.rope_pct,
            "layer_norm_eps": cfg.eps, "rms_norm_eps": cfg.eps,
            "hidden_act": cfg.activation,
            "tie_word_embeddings": cfg.tie_embeddings,
            "use_parallel_residual": cfg.parallel_block,
            "arith.parallel_separate_norms": cfg.parallel_separate_norms,
            "arith.gated_mlp": cfg.gated_mlp, "arith.norm": cfg.norm,
            "arith.attn_bias": cfg.attn_bias, "arith.mlp_bias": cfg.mlp_bias}
    told = {**config, **{"arith." + k: v
                         for k, v in config.get("arith", {}).items()}}
    for k, v in same.items():
        if k in told and told[k] != v:
            raise SystemExit(f"configuration file says {k}={told[k]}, "
                             f"the system would run {v}")
    return cfg


def make_model(cfg, seed: int, dtype=None, shardings_for=None):
    """``shardings_for(abstract_params) -> shardings`` places the leaves
    as they are made (several chips); None leaves them on one device."""
    from deepspeed_tpu.models.transformer import Model, init_params

    axes = {}

    def init(key):
        params, axes["axes"] = init_params(cfg, key)
        if dtype is not None:
            params = jax.tree.map(lambda x: x.astype(dtype), params)
        return params

    key = jax.random.PRNGKey(seed % (2 ** 31))
    out_sh = None
    if shardings_for is not None:
        out_sh = shardings_for(jax.eval_shape(init, key))
    params = jax.jit(init, out_shardings=out_sh)(key)
    return Model.from_params(cfg, params, param_axes=axes["axes"])


def spread_over(mesh, axis: str):
    """Shard every leaf's largest divisible dimension over ``axis``; a
    plain rule for making a big model without one chip holding it all
    (the engine reshards to its own layout when it takes the params)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape[axis]

    def one(x):
        dims = [i for i, s in enumerate(x.shape) if s % n == 0 and s >= n]
        if not dims:
            return NamedSharding(mesh, P())
        best = max(dims, key=lambda i: x.shape[i])
        spec = [None] * len(x.shape)
        spec[best] = axis
        return NamedSharding(mesh, P(*spec))

    return lambda tree: jax.tree.map(one, tree)
