"""Plain reference for ``mistral-7b-d16``.

The architecture as published (mistralai/Mistral-7B-Instruct-v0.2
``config.json``; Mistral 7B, arXiv:2310.06825), written out in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``:
no kernel, no cache, no batching, no sharing of code with
``deepspeed_tpu.models`` or ``deepspeed_tpu.inference``.  It READS the
system's seeded bf16 parameter tree and upcasts one layer at a time, so
that it fits beside the engine.

  x      = embed[ids]
  layer:   x = x + attn(rms1(x));  x = x + mlp(rms2(x))   (eps 1e-5)
  attn:    q = h Wq (32 heads), k = h Wk, v = h Wv (8 heads, each shared
           by 4 query heads); rotary on all 128 dims of q and k
           (rotate-half pairing, base 1e6); causal
           softmax(q k^T / sqrt(128)) v; Wo.  No biases.
           v0.2 has no sliding window (``sliding_window: null``).
  mlp:     (silu(h Wgate) * (h Wup)) Wdown
  logits = rms_f(x) W_head                                 (untied)

Departures from the publication: none in the mathematics.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    S, _, D = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    a, m = lp["attn"], lp["mlp"]
    S = x.shape[0]
    H, D = a["wq"].shape[-2:]
    Hkv = a["wk"].shape[-2]
    h = _rms(x, lp["ln1"]["scale"], c["rms_norm_eps"])
    q = _rotary(jnp.einsum("sd,dhk->shk", h, a["wq"]), c["rope_theta"])
    k = _rotary(jnp.einsum("sd,dhk->shk", h, a["wk"]), c["rope_theta"])
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    # grouped-query attention: query head j reads kv head j // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v)
    x = x + jnp.einsum("qhk,hkd->qd", o, a["wo"])
    h = _rms(x, lp["ln2"]["scale"], c["rms_norm_eps"])
    return x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


@functools.lru_cache(maxsize=None)
def _programs(eps, theta):
    c = {"rms_norm_eps": eps, "rope_theta": theta}
    return (jax.jit(lambda x, lp: _layer(x, lp, c)),
            jax.jit(lambda x, s, w: _rms(x, s.astype(F32), eps)
                    @ w.astype(F32)))


def logits(params, ids, c):
    """[S] token ids -> [S, vocab] float32, one layer upcast at a time."""
    layer, head = _programs(c["rms_norm_eps"], c["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        for i in range(c["num_hidden_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[i], params["blocks"]))
        return head(x, params["ln_f"]["scale"], params["lm_head"]["kernel"])
