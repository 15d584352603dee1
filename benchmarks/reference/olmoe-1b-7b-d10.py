"""Plain reference for ``olmoe-1b-7b-d10``.

The architecture as published (allenai/OLMoE-1B-7B-0125-Instruct
``config.json``, ``model_type: olmoe``; OLMoE, arXiv:2409.02060; the
mechanisms the config does not spell out are those of transformers'
``modeling_olmoe.py``: ``OlmoeAttention`` with its ``q_norm``/``k_norm``,
``OlmoeSparseMoeBlock``), written out in ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no sort, no capacity, no sharing of code with
``deepspeed_tpu.models``, ``.inference`` or ``.parallel``.  It READS the
system's seeded bf16 parameter tree and upcasts one layer at a time, so
that it fits beside the engine.

  x      = embed[ids]
  layer:   x = x + attn(rms1(x));  x = x + moe(rms2(x))           (eps 1e-5)
  attn:    q = rms_q(h Wq), k = rms_k(h Wk): ONE RMSNorm over all 2048
           outputs of the projection, with a learned scale, before the
           head split; v = h Wv; 16 heads of 128, one KV head each;
           rotary on all 128 dims of q and k (rotate-half pairing, base
           1e4); causal softmax(q k^T / sqrt(128)) v; Wo.  No biases, no
           clipping (``clip_qkv: null``).
  moe:     p = softmax_f32(h Wr) over the 64 experts; S = the 8 largest;
           the weights are p_e as they are (``norm_topk_prob: false``:
           not renormalised);
           y = sum_{e in S} p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e, expert
           width 1024; no shared expert; nothing dropped.  Computed here
           for all 64 experts densely, then masked to S.
  logits = rms_f(x) W_head                                         (untied)

Departures from the publication: none in the mathematics.  The
published model multiplies an expert's output by ``p_e`` cast to
bfloat16; in float32 that cast is the identity.  The system keeps the
norm scales as ``[heads, head_dim]``, the flat published vector viewed
by head; here they are flattened back.

A token whose eighth and ninth probabilities lie within bfloat16's
rounding of each other can take another eighth expert in the
system than here.  ``logits`` prints, as an earlier line of the run,
how many (token, layer) pairs stand that close (``near_ties``: the two
probabilities within 2^-7 of each other, relatively), and ``chosen``
returns every pair's set S, for the one-off count of the pairs in which
the system really chose another (PERF.md, PR 26).
"""

import functools
import json
import sys

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
    a, e = lp["attn"], lp["experts"]
    S = x.shape[0]
    H, D = a["wq"].shape[-2:]
    eps = c["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    # the projections as the flat vectors the norm is taken over
    q = _rms(h @ a["wq"].reshape(-1, H * D), a["q_norm"].reshape(-1), eps)
    k = _rms(h @ a["wk"].reshape(-1, H * D), a["k_norm"].reshape(-1), eps)
    v = h @ a["wv"].reshape(-1, H * D)
    q = _rotary(q.reshape(S, H, D), c["rope_theta"])
    k = _rotary(k.reshape(S, H, D), c["rope_theta"])
    v = v.reshape(S, H, D)
    s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v)
    x = x + jnp.einsum("qhk,hkd->qd", o, a["wo"])

    h = _rms(x, lp["ln2"]["scale"], eps)
    probs = jax.nn.softmax(h @ lp["gate"]["kernel"], axis=-1)      # [S, 64]
    ranked = jnp.sort(probs, axis=-1)
    kth = ranked[:, -c["num_experts_per_tok"]]
    margin = 1.0 - ranked[:, -c["num_experts_per_tok"] - 1] / kth   # [S]
    chosen = probs >= kth[:, None]                                 # [S, 64]
    # every expert on every token, then only the chosen ones count
    up = jnp.einsum("sd,edf->esf", h, e["wi"])
    gate = jnp.einsum("sd,edf->esf", h, e["wg"])
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up, e["wo"])
    w = jnp.where(chosen, probs, 0.0)                              # [S, 64]
    return x + jnp.einsum("se,esd->sd", w, y), chosen, margin


@functools.lru_cache(maxsize=None)
def _programs(eps, theta, top_k):
    c = {"rms_norm_eps": eps, "rope_theta": theta,
         "num_experts_per_tok": top_k}
    return (jax.jit(lambda x, lp: _layer(x, lp, c)),
            jax.jit(lambda x, s, w: _rms(x, s.astype(F32), eps)
                    @ w.astype(F32)))


def _forward(params, ids, c):
    layer, head = _programs(c["rms_norm_eps"], float(c["rope_theta"]),
                            c["num_experts_per_tok"])
    sets, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        for i in range(c["num_hidden_layers"]):
            x, chosen, margin = layer(x, jax.tree.map(lambda a: a[i],
                                                      params["blocks"]))
            sets.append(chosen)
            margins.append(margin)
        return (head(x, params["ln_f"]["scale"],
                     params["lm_head"]["kernel"]),
                jnp.stack(sets), jnp.stack(margins))


def logits(params, ids, c):
    """[S] token ids -> [S, vocab] float32, one layer upcast at a time."""
    out, _, margins = _forward(params, ids, c)
    sys.stdout.write(json.dumps({
        "note": "reference_router", "tokens": int(margins.shape[1]),
        "layers": int(margins.shape[0]),
        "near_ties": int((margins < 2.0 ** -7).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def chosen(params, ids, c):
    """[S] token ids -> [layers, S, 64] bool: the experts each token
    takes in each layer."""
    return _forward(params, ids, c)[1]
