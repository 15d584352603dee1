"""Plain reference for ``granite-4.0-h-small-d10``.

The architecture as published (ibm-granite/granite-4.0-h-small
``config.json``, ``model_type: granitemoehybrid``; what its keys do not
say is from that model type's public modelling code, ``transformers``
``models/granitemoehybrid/modeling_granitemoehybrid.py``, and listed under
``assumed`` in the configuration's file), written out in ``jax.numpy`` and
float32 under ``default_matmul_precision("highest")``: no kernel, no
cache, no batching, no chunks, no sort, no sharing of code with
``deepspeed_tpu``.  It READS the system's seeded bf16 parameter tree
(``blocks``: a layer's mixer under its kind's name, ``mamba`` or ``full``,
at its rank among the layers of that kind; router, experts, shared MLP and
norms a row a layer) and upcasts one layer at a time, the experts a few at
a time cut out of the stacked weights where they lie, the head a block of
the vocabulary at a time.  ``tests/test_granite_reference.py`` holds it to
``GraniteMoeHybridForCausalLM`` at a tiny size.

``N(.)`` is an RMSNorm with a learned scale, eps ``rms_norm_eps``;
``r = residual_multiplier``.

  x0     = embed[ids] * embedding_multiplier
  layer:   a = x + r * Mix(N_1(x));  x' = a + r * (MoE(N_2(a)) + Shared(N_2(a)))
  Mix, ``layer_types[l] == "attention"``: q = h W_q (32 heads of 128),
           k = h W_k, v = h W_v (8 heads, each shared by 4 query heads);
           NO positions (``position_embedding_type: nope``); causal
           softmax(q k^T * attention_multiplier) v over a full masked
           score matrix (a block of its rows at a time); W_o.  No biases.
  Mix, ``"mamba"``: p = h W_in cut in order into z [8192], xBC [8192 +
           2*128], dt [128].
           xBC_t <- silu(b_c + sum_{j=0..3} w_c[:, j] * xBC_{t-3+j})
           (depthwise, causal, zeros before the first token): a sum of
           four shifted products.  Cut into x_t [128, 64], B_t, C_t [128]
           (ONE group: every head reads the same B and C).
           dt_t = softplus(dt_t + dt_bias), no clamp; A = -exp(A_log).
           Per head i, TOKEN BY TOKEN (a lax.scan over t of exactly this
           line, no chunks):
               S_t = exp(dt_t A_i) S_{t-1} + dt_t * x_t (outer) B_t
               y_t = S_t C_t + D_i x_t             S_0 = 0, S: [64, 128]
           y <- RMSNorm_8192(y * silu(z)) * w (over ALL channels: one
           group); y W_out [8192 -> 4096].
  MoE:     l = h W_r over ALL ``router_outputs`` experts (72); S = the ten
           largest l; g = softmax over those ten; y = sum over e in S AND
           HELD HERE of g_e * (silu(h W_g,e) * (h W_i,e)) W_o,e.
           ``experts_held`` [first, count]: the experts whose weights the
           tree holds; the others' terms belong to the other chip and are
           not added, nothing stands in.
  Shared:  (silu(h W_g) * (h W_i)) W_o at width 1536, added as it is.
  logits = N_f(x) embed^T / logits_scaling                       (tied)

Departures from the publication: none in the mathematics of what is kept.
The modelling code computes ``softmax`` over the ten chosen logits, which
is the softmax over all 72 renormalised over the chosen; its experts' input
projection holds ``[a | b]`` in one matrix, the tree here ``W_g`` and
``W_i`` apart.

``following`` computes the forward with the router's choice GIVEN (the
experts the engine took, its numbering) and everything else its own, and
says how far a taken expert's logit falls short of the reference's own
tenth.

``wrong`` computes the forward with one thing done wrongly, for the
comparison's limits to be fitted against (``WRONG``; ``name@position`` for
those that happen at a position): every layer matrix rounded to int8 with
one scale a row (``int8``); the residual multiplier, the attention
multiplier, the logits' divisor or the embedding multiplier left out
(``no_residual_scale``, ``no_attn_scale``, ``no_logits_scale``,
``no_embed_scale``); the recurrent state zeroed before token ``at``
(``state_reset``: a state lost at a step's boundary); the convolution
reading zeros before token ``at`` (``no_tail``: a tail not carried over a
boundary); every Mamba layer started from what the same tokens in reverse
leave (``old_state``: a slot's old state and tail kept by the next
sequence); the shared MLP left out (``no_shared``); rotary positions
(``rope_theta``) added to q and k (``rope``); the weights of the chosen
as the softmax over all 72, not renormalised (``softmax_all``); the gated
norm over each head's 64 channels apart (``norm_by_head``).
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("int8", "no_residual_scale", "no_attn_scale", "no_logits_scale",
         "no_embed_scale", "state_reset", "no_tail", "old_state",
         "no_shared", "rope", "softmax_all", "norm_by_head")
SCORE_ROWS = 256        # rows of the score matrix at a time
EXPERT_GROUP = 6        # experts upcast at a time
EXPERT_TOKENS = 512     # tokens through a group of experts at a time
HEAD_BLOCKS = 32        # blocks of the vocabulary


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _int8(w):
    """``w`` rounded to int8 with one scale a row of its last axis."""
    scale = jnp.maximum(jnp.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(w / scale) * scale


def _prepared(a, wrong):
    """A stored weight as the reference computes with it: float32, and
    for the ``int8`` control rounded first."""
    a = a.astype(F32)
    return _int8(a) if wrong == "int8" and a.ndim >= 2 else a


def _rotary(x, base):
    S, _, D = x.shape
    inv = 1.0 / (F32(base) ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def _attention(h, a, c, wrong):
    S, d = h.shape
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    q = (h @ a["wq"].reshape(d, -1)).reshape(S, H, -1)
    k = (h @ a["wk"].reshape(d, -1)).reshape(S, Hkv, -1)
    v = (h @ a["wv"].reshape(d, -1)).reshape(S, Hkv, -1)
    D = q.shape[-1]
    if wrong == "rope":
        q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    scale = D ** -0.5 if wrong == "no_attn_scale" \
        else c["attention_multiplier"]
    # grouped-query attention: query head j reads kv head j // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    B = min(SCORE_ROWS, S)
    nb = -(-S // B)
    qb = jnp.pad(q, ((0, nb * B - S), (0, 0), (0, 0))).reshape(nb, B, H, D)

    def rows(xs):
        i, qs = xs
        s = jnp.einsum("qhk,thk->hqt", qs, k) * F32(scale)
        causal = (i * B + jnp.arange(B))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    o = jax.lax.map(rows, (jnp.arange(nb), qb)).reshape(nb * B, H * D)[:S]
    return o @ a["wo"].reshape(H * D, -1)


def _mamba(h, m, c, wrong, at, init):
    """→ (the mixer's output [S, d], (the state it leaves [H, P, N], its
    last three raw convolution inputs)).  ``init``: such a pair to start
    from; None: zeros.  ``at``: a token index, static."""
    S = h.shape[0]
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    G, N, W = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"]
    d, C = H * P, H * P + 2 * G * N
    p = h @ m["w_in"]
    z, xbc, dt = p[:, :d], p[:, d:d + C], p[:, d + C:]
    s0, tail = init if init is not None else (
        jnp.zeros((H, P, N), F32), jnp.zeros((W - 1, C), F32))
    # the convolution: a sum of four shifted products, zeros (or what
    # ``init`` holds) before the sequence's first token
    padded = jnp.concatenate([tail, xbc])
    acc = m["conv_b"]
    t = jnp.arange(S)
    for j in range(W):
        term = padded[j:j + S]
        if wrong == "no_tail":
            # what a run starting at ``at`` would read with no tail
            cut = (t >= at) & (t + j - (W - 1) < at)
            term = jnp.where(cut[:, None], 0.0, term)
        acc = acc + m["conv_w"][:, j] * term
    xbc_c = jax.nn.silu(acc)
    x = xbc_c[:, :d].reshape(S, H, P)
    b = xbc_c[:, d:d + G * N].reshape(S, G, N)
    cc = xbc_c[:, d + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    a = -jnp.exp(m["A_log"])

    def token(s, xs):
        i, x_t, b_t, c_t, dt_t = xs
        if wrong == "state_reset":
            s = jnp.where(i == at, 0.0, s)
        # head i reads group i // (H / G)
        b_h, c_h = jnp.repeat(b_t, H // G, 0), jnp.repeat(c_t, H // G, 0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_h) + m["D"][:, None] * x_t

    s_last, y = jax.lax.scan(token, s0, (t, x, b, cc, dt))
    y = y.reshape(S, d) * jax.nn.silu(z)
    groups = H if wrong == "norm_by_head" else G
    y = y.reshape(S, groups, d // groups)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + c["rms_norm_eps"])
    return (y.reshape(S, d) * m["norm"]) @ m["w_out"], \
        (s_last, padded[-(W - 1):])


def _experts(h, lp, c, wrong, given, li=0):
    """[n, d] tokens through the router and the held experts → (y, [the
    margin between the tenth and the eleventh logit, how far the taken
    experts fall short of the tenth]).  ``given [n, k]``: the experts to
    take, in the router's numbering; a row of -1: the reference's own
    choice (one program serves both).  ``lp``'s experts are the STACK of
    all the layers' ``[layers, count, ...]``, in the type they are stored
    in, this layer's at ``li``: a few are cut out of it where they lie."""
    k = c["num_experts_per_tok"]
    first, count = c["experts_held"]
    logit = h @ lp["gate"]["kernel"]                               # [n, E]
    E = logit.shape[-1]
    ranked = jnp.sort(logit, axis=-1)
    tenth = ranked[:, -k]
    chosen = jnp.where(given[:, :1] < 0, logit >= tenth[:, None],
                       (given[:, :, None] == jnp.arange(E)).any(1))
    margin = tenth - ranked[:, -k - 1]
    short = jnp.where(chosen, jnp.maximum(tenth[:, None] - logit, 0.0),
                      0.0).max(-1)
    if wrong == "softmax_all":
        w = jnp.where(chosen, jax.nn.softmax(logit, axis=-1), 0.0)
    else:       # the softmax over the chosen logits
        w = jax.nn.softmax(jnp.where(chosen, logit, -jnp.inf), axis=-1)
    w = w[:, first:first + count]          # only the held experts' terms

    # a group of experts is upcast ONCE and runs over the tokens a block
    # at a time
    g = min(EXPERT_GROUP, count)
    while count % g:
        g -= 1
    n = h.shape[0]
    nb = -(-n // EXPERT_TOKENS)
    pad = nb * EXPERT_TOKENS - n
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, EXPERT_TOKENS, -1)

    def group(y, xs):
        j, wg = xs                                        # wg: [g, n]
        e = jax.tree.map(lambda a: _prepared(jax.lax.dynamic_slice(
            a, (li, j * g) + (0,) * (a.ndim - 2),
            (1, g) + a.shape[2:])[0], wrong), lp["experts"])

        def block(xs):
            hs, ws = xs                        # [tokens, d], [g, tokens]
            up = jnp.einsum("sd,edf->esf", hs, e["wi"])
            gate = jnp.einsum("sd,edf->esf", hs, e["wg"])
            out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up, e["wo"])
            return jnp.einsum("es,esd->sd", ws, out)

        wb = jnp.pad(wg, ((0, 0), (0, pad))).reshape(g, nb, EXPERT_TOKENS)
        return y + jax.lax.map(block, (hb, wb.transpose(1, 0, 2))), None

    y, _ = jax.lax.scan(group, jnp.zeros_like(hb),
                        (jnp.arange(count // g),
                         w.T.reshape(count // g, g, n)))
    y = y.reshape(nb * EXPERT_TOKENS, -1)[:n]
    if wrong != "no_shared":
        y = y + _swiglu(h, lp["shared"])
    return y, jnp.stack([margin, short])


def _layer(x, lp, c, kind, wrong, at, init, given, li):
    lp = {k: v if k == "experts"
          else jax.tree.map(lambda a: _prepared(a, wrong), v)
          for k, v in lp.items()}
    eps = c["rms_norm_eps"]
    r = F32(1.0 if wrong == "no_residual_scale"
            else c["residual_multiplier"])
    h = _rms(x, lp["ln1"]["scale"], eps)
    left = None
    if kind == "mamba":
        mix, left = _mamba(h, lp["mamba"], c, wrong, at, init)
    else:
        mix = _attention(h, lp["full"], c, wrong)
    x = x + r * mix
    y, router = _experts(_rms(x, lp["ln2"]["scale"], eps), lp, c, wrong,
                         given, li)
    return x + r * y, router, left


def _head(x, scale, table, c, wrong):
    """The tied head in blocks of the vocabulary, each cut out of the
    table and upcast where it is used."""
    h = _rms(x, scale.astype(F32), c["rms_norm_eps"])
    V, d = table.shape
    blocks = HEAD_BLOCKS
    while V % blocks:
        blocks -= 1
    out = jax.lax.map(
        lambda i: h @ jax.lax.dynamic_slice(
            table, (i * (V // blocks), 0), (V // blocks, d)).astype(F32).T,
        jnp.arange(blocks))                          # [blocks, S, V/blocks]
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)
    return out if wrong == "no_logits_scale" \
        else out / F32(c["logits_scaling"])


_KEYS = ("rms_norm_eps", "rope_theta", "attention_multiplier",
         "embedding_multiplier", "logits_scaling", "residual_multiplier",
         "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
         "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
         "num_experts_per_tok")


@functools.lru_cache(maxsize=None)
def _programs(keys, held, wrong, at):
    c = dict(zip(_KEYS, keys), experts_held=held)
    return ({kind: jax.jit(
                 lambda x, lp, init, given, li, kind=kind:
                 _layer(x, lp, c, kind, wrong, at, init, given, li))
             for kind in ("mamba", "full")},
            jax.jit(lambda x, s, t: _head(x, s, t, c, wrong)))


def _held(c):
    return tuple(c.get("experts_held") or (0, c["num_local_experts"]))


def _kinds(c):
    """The kept layers' kinds, as the tree names them."""
    return ["full" if k == "attention" else k
            for k in c["layer_types"][:c["num_hidden_layers"]]]


def _forward(params, ids, c, wrong=None, last=None, routing=None,
             inits=None):
    """→ (logits, [layers, 2, S], what the Mamba layers leave).
    ``routing [layers, S, k]``: the choice to follow.  ``inits``: what
    each Mamba layer starts from."""
    name, _, at = (wrong or "").partition("@")
    name = name or None
    assert name is None or name in WRONG, wrong
    if name == "old_state" and inits is None:
        *_, inits = _forward(params, list(ids)[::-1], c)
    layer, head = _programs(tuple(c[k] for k in _KEYS), _held(c), name,
                            int(at) if at else len(ids) // 2)
    kinds = _kinds(c)
    blocks = params["blocks"]
    routers, lefts = [], []
    own = jnp.full((len(ids), c["num_experts_per_tok"]), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        if name != "no_embed_scale":
            x = x * F32(c["embedding_multiplier"])
        for i, kind in enumerate(kinds):
            # a layer's mixer lies at its rank among its kind
            rank = kinds[:i].count(kind)
            # (the experts go in as the stack, cut a few at a time)
            lp = {k: v if k == "experts" else jax.tree.map(
                lambda a, at_=rank if k in ("mamba", "full") else i:
                a[at_], v) for k, v in blocks.items()
                if k not in ("mamba", "full") or k == kind}
            given = own if routing is None else jnp.asarray(routing[i],
                                                            jnp.int32)
            init = inits[len(lefts)] if inits is not None \
                and kind == "mamba" else None
            x, router, left = layer[kind](x, lp, init, given, jnp.int32(i))
            if kind == "mamba":
                lefts.append(left)
            routers.append(router)
        return (head(x if last is None else x[-last:],
                     params["ln_f"]["scale"], params["embed"]["table"]),
                jnp.stack(routers), lefts)


def logits(params, ids, c, wrong=None, last=None):
    """[S] token ids -> [S, vocab] float32, one layer upcast at a time.
    ``wrong``: one of ``WRONG`` (``name@position`` for those that happen
    at a position), see above.  ``last``: only that many last rows go
    through the head."""
    out, routers, _ = _forward(params, ids, c, wrong, last)
    margins = routers[:, 0]
    sys.stdout.write(json.dumps({
        "note": "reference_router", "tokens": int(margins.shape[1]),
        "layers": int(margins.shape[0]), "wrong": wrong,
        # logits of order one: bfloat16 rounds them to about 2^-8
        "near_ties": int((margins < 2.0 ** -7).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def following(params, ids, c, routing, wrong=None, last=None):
    """``logits`` with the router's choice given: ``routing [layers, S,
    k]``, the experts each token took (the router's numbering).
    → (logits, the largest shortfall of a taken expert's logit under the
    reference's own tenth, over tokens and layers)."""
    out, routers, _ = _forward(params, ids, c, wrong, last, routing)
    return out, float(routers[:, 1].max())
