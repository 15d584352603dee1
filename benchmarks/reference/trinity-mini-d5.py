"""Plain reference for ``trinity-mini-d5``.

The architecture as published (arcee-ai/Trinity-Mini ``config.json``,
``model_type: afmoe``; the mechanisms the config's keys do not spell out
are those of transformers' ``modeling_afmoe.py`` and stand under
``assumed`` in the configuration's file), written out in ``jax.numpy``
and float32 under ``default_matmul_precision("highest")``: no kernel, no
cache, no batching, no sort, no capacity, no sharing of code with
``deepspeed_tpu.models``, ``.inference`` or ``.parallel``.  It READS the
system's seeded bf16 parameter tree (``dense_blocks``: the leading dense
layers, ``blocks``: the expert layers) and upcasts one layer at a time,
so that it fits beside the engine's 8.48 GB.

  x      = embed[ids] * sqrt(2048)                      (mup_enabled)
  layer:   a = x + rms_pa(attn(rms_in(x)));  x = a + rms_pm(F(rms_m(a)))
           four norms a layer, eps 1e-5
  attn:    q = h Wq [32, 128], k = h Wk [4, 128], v = h Wv [4, 128],
           g = h Wg [4096]; q and k get an RMSNorm PER HEAD over the 128,
           one learned [128] scale for the query heads and one for the
           key heads.  ``layer_types[l]``: a sliding layer puts rotary
           (base 1e4, all 128 dims, rotate-half pairing) on q and k and
           lets query i see keys j with i - 2048 < j <= i; a full layer
           puts NO positions on q and k and sees every j <= i.
           softmax(q k^T / sqrt(128)) v, 8 query heads to a KV head;
           ((o flattened to 4096) * sigmoid(g)) Wo.  No biases.
  F dense: (silu(h W1) * (h W3)) W2, width 6144   (l < num_dense_layers)
  F moe:   s = sigmoid_f32(h Wr) over the 128 experts; S = the 8 largest
           of s + b (b: a per-expert bias, for the choice only);
           w_e = 2.826 * s_e / (sum_{S} s + 1e-20);
           y = sum_{e in S} w_e * (silu(h Wg_e) * (h Wu_e)) Wd_e, width
           1024, + the shared expert (silu(h Wg_s) * (h Wu_s)) Wd_s,
           width 1024, ungated, every token.  Computed here for all 128
           experts densely, then masked to S.
  logits = rms_f(x) W_head                              (untied)

Departures from the publication: none in the mathematics.  The window is
a mask over the full score matrix (computed 512 rows at a time).  Of the
memory: the experts run over
blocks of 512 tokens and are upcast 32 at a time, one group after the
other, so that neither a 3,000-token sequence's [128, S, 1024]
intermediates nor a whole expert layer in float32 (3.4 GB) has to fit
beside the engine's weights.  The system divides the
chosen scores by ``max(sum, 1e-9)``, this by ``sum + 1e-20`` as
published: eight sigmoids sum to about 4.

``wrong`` computes the forward with one thing done wrongly, for the
readings that show what the cell's tolerance refuses (PERF.md): the
window left out (``no_window``), rotary in the full layers too
(``rope_in_full``), the output gate left out (``no_gate``), the shared
expert left out (``no_shared``), softmax for sigmoid scores
(``softmax_scores``), the selection bias added to the weights
(``bias_in_weights``), every layer matrix rounded to int8 with one scale
a row (``int8``).

A token whose eighth and ninth biased scores lie within bfloat16's
rounding of each other can take another eighth expert in the system than
here; ``logits`` prints how many (token, layer) pairs stand that close,
as ``olmoe-1b-7b-d10.py`` does.  Here such a swap is no small thing: the
eight weights are near equal and renormalised, so it moves a third of
the expert layer's output, and the layer's norm passes that on whole.
``following`` therefore computes the same forward with the router's
CHOICE given (the experts the system took, token by token and layer by
layer) and everything else its own: scores, weights, experts, attention.
It also says how far the given choice lies from its own, as the largest
amount by which a given expert's biased score falls short of the eighth
largest: nothing for its own choice, a rounding for a near-tie taken the
other way, the scores' whole spread for a choice made by another rule.
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_window", "rope_in_full", "no_gate", "no_shared",
         "softmax_scores", "bias_in_weights", "int8")
EXPERT_TOKENS = 512         # the experts run over blocks of this many
EXPERT_GROUP = 32           # and are upcast this many at a time
QUERY_ROWS = 512            # rows of the score matrix computed at a time


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    S, _, D = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(w):
    """``w`` rounded to int8 with one scale a row of its last axis."""
    scale = jnp.maximum(jnp.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(w / scale) * scale


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def _attention(x, lp, c, sliding, wrong):
    a = lp["attn"]
    S = x.shape[0]
    H, D = a["wq"].shape[-2:]
    G = a["wk"].shape[-2]
    eps = c["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rms((h @ a["wq"].reshape(-1, H * D)).reshape(S, H, D),
             a["q_norm"], eps)
    k = _rms((h @ a["wk"].reshape(-1, G * D)).reshape(S, G, D),
             a["k_norm"], eps)
    v = (h @ a["wv"].reshape(-1, G * D)).reshape(S, G, D)
    if sliding or wrong == "rope_in_full":
        q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    j = jnp.arange(S)[None, :]

    def rows(qi):
        # a block of queries against every key: the full score matrix,
        # a block of its rows at a time
        qb, i = qi
        see = j <= i[:, None]
        if sliding and wrong != "no_window":
            see &= j > i[:, None] - c["sliding_window"]
        s = jnp.einsum("qgrk,tgk->grqt", qb, k) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqt,tgk->qgrk", p, v)

    n = -(-S // QUERY_ROWS)
    pad = n * QUERY_ROWS - S
    qp = jnp.pad(q.reshape(S, G, H // G, D), ((0, pad),) + ((0, 0),) * 3)
    o = jax.lax.map(rows, (qp.reshape((n, QUERY_ROWS) + qp.shape[1:]),
                           jnp.arange(n * QUERY_ROWS).reshape(n, -1)))
    o = o.reshape(n * QUERY_ROWS, H * D)[:S]
    if wrong != "no_gate":
        o = o * jax.nn.sigmoid(h @ a["wg"].reshape(-1, H * D))
    return x + _rms(o @ a["wo"].reshape(H * D, -1),
                    lp["ln1_post"]["scale"], eps)


def _experts(h, lp, c, wrong, given=None):
    """[n, d] tokens through the router and all the experts → (y, [the
    margin between the eighth and the ninth biased score, how far the
    lowest chosen score falls short of the eighth]).  ``given [n, 8]``:
    the experts to choose, in place of the eight largest.  ``lp``'s
    experts are still in the type they are stored in: they are upcast a
    group at a time."""
    k = c["num_experts_per_tok"]
    logit = h @ lp["gate"]["kernel"]                               # [n, E]
    score = jax.nn.softmax(logit, -1) if wrong == "softmax_scores" \
        else jax.nn.sigmoid(logit)
    biased = score + lp["gate"]["bias"]
    ranked = jnp.sort(biased, axis=-1)
    if given is None:
        chosen = biased >= ranked[:, -k][:, None]
    else:
        chosen = (given[:, :, None] == jnp.arange(biased.shape[-1])).any(1)
    margin = ranked[:, -k] - ranked[:, -k - 1]
    short = ranked[:, -k] - jnp.where(chosen, biased, jnp.inf).min(-1)
    w = jnp.where(chosen, biased if wrong == "bias_in_weights" else score,
                  0.0)
    if c["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * c["route_scale"]

    def group(xs):
        # every expert of the group on every token, then only the chosen
        # ones count (their weight is zero elsewhere)
        e, wg = xs
        e = jax.tree.map(lambda a: _prepared(a, wrong), e)
        up = jnp.einsum("sd,edf->esf", h, e["wi"])
        gate = jnp.einsum("sd,edf->esf", h, e["wg"])
        y = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up, e["wo"])
        return jnp.einsum("es,esd->sd", wg, y)

    E = w.shape[-1]
    g = min(EXPERT_GROUP, E)
    grouped = jax.tree.map(lambda a: a.reshape((E // g, g) + a.shape[1:]),
                           lp["experts"])
    y = jax.lax.map(group, (grouped, w.T.reshape(E // g, g, -1))).sum(0)
    if wrong != "no_shared":
        y = y + _swiglu(h, lp["shared"])
    return y, jnp.stack([margin, short])


def _prepared(a, wrong):
    """A stored weight as the reference computes with it: float32, and
    for the ``int8`` control rounded first."""
    a = a.astype(F32)
    return _int8(a) if wrong == "int8" and a.ndim >= 2 else a


def _layer(x, lp, c, sliding, dense, wrong, given=None):
    # the experts are upcast a group at a time (``_experts``): a whole
    # expert layer in float32 is 3.4 GB beside the engine's weights
    lp = {k: v if k == "experts"
          else jax.tree.map(lambda a: _prepared(a, wrong), v)
          for k, v in lp.items()}
    eps = c["rms_norm_eps"]
    x = _attention(x, lp, c, sliding, wrong)
    h = _rms(x, lp["ln2"]["scale"], eps)
    if dense:
        y, router = _swiglu(h, lp["mlp"]), jnp.ones((2,) + x.shape[:1], F32)
    else:
        S = h.shape[0]
        n = -(-S // EXPERT_TOKENS)
        pad = ((0, n * EXPERT_TOKENS - S), (0, 0))
        blocks = [jnp.pad(a, pad).reshape((n, EXPERT_TOKENS) + a.shape[1:])
                  for a in ((h,) if given is None else (h, given))]
        y, router = jax.lax.map(
            lambda b: _experts(b[0], lp, c, wrong, *b[1:]), blocks)
        y = y.reshape(-1, y.shape[-1])[:S]
        router = router.transpose(1, 0, 2).reshape(2, -1)[:, :S]
    return x + _rms(y, lp["ln2_post"]["scale"], eps), router


@functools.lru_cache(maxsize=None)
def _programs(eps, theta, window, top_k, route_norm, route_scale, wrong):
    c = {"rms_norm_eps": eps, "rope_theta": theta, "sliding_window": window,
         "num_experts_per_tok": top_k, "route_norm": route_norm,
         "route_scale": route_scale}
    return ({(s, d): jax.jit(lambda x, lp, given=None, s=s, d=d: _layer(
                 x, lp, c, s, d, wrong, given))
             for s in (False, True) for d in (False, True)},
            jax.jit(lambda x, s, w: _rms(x, s.astype(F32), eps)
                    @ w.astype(F32)))


def _forward(params, ids, c, wrong=None, last=None, routing=None):
    """→ (logits, [expert layers, 2, S]: each token's margin and how far
    its chosen experts fall short of the eighth score).  ``routing
    [expert layers, S, 8]``: the choice to follow."""
    assert wrong is None or wrong in WRONG, wrong
    layer, head = _programs(
        c["rms_norm_eps"], float(c["rope_theta"]), c["sliding_window"],
        c["num_experts_per_tok"], bool(c["route_norm"]),
        float(c["route_scale"]), wrong)
    lead = c["num_dense_layers"]
    routers = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32) \
            * jnp.sqrt(F32(c["hidden_size"]))
        for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
            stack, at = (params["dense_blocks"], i) if i < lead \
                else (params["blocks"], i - lead)
            given = None if routing is None or i < lead \
                else jnp.asarray(routing[at])
            x, router = layer[kind == "sliding_attention", i < lead](
                x, jax.tree.map(lambda a: a[at], stack), given)
            if i >= lead:
                routers.append(router)
        return (head(x if last is None else x[-last:],
                     params["ln_f"]["scale"], params["lm_head"]["kernel"]),
                jnp.stack(routers))


def logits(params, ids, c, wrong=None, last=None):
    """[S] token ids -> [S, vocab] float32, one layer upcast at a time.
    ``wrong``: one of ``WRONG``, see above.  ``last``: only that many
    last rows go through the head (a 3,000-token sequence's [S, 200192]
    is 2.4 GB that nothing reads)."""
    out, routers = _forward(params, ids, c, wrong, last)
    margins = routers[:, 0]
    sys.stdout.write(json.dumps({
        "note": "reference_router", "tokens": int(margins.shape[1]),
        "layers": int(margins.shape[0]), "wrong": wrong,
        # scores near a half: bfloat16 rounds them to 2^-9
        "near_ties": int((margins < 2.0 ** -8).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def following(params, ids, c, routing, wrong=None, last=None):
    """``logits`` with the router's choice given: ``routing [expert
    layers, S, 8]``, the experts each token took.  → (logits, the largest
    amount by which a given expert's biased score falls short of the
    token's eighth largest, over tokens and layers)."""
    out, routers = _forward(params, ids, c, wrong, last, routing)
    return out, float(routers[:, 1].max())
