"""Plain reference for ``ling-3.0-flash-d7``.

The architecture as published (inclusionAI/Ling-3.0-flash ``config.json``,
``model_type: bailing_hybrid``; what the config's keys do not settle
stands under ``assumed`` in the configuration's file), written out in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``:
no kernel, no cache, no batching, no chunks, no sort, no sharing of code
with ``deepspeed_tpu.models``, ``.inference``, ``.ops`` or ``.parallel``.
It READS the system's seeded bf16 parameter tree (``dense_blocks``: the
leading dense layers, ``blocks``: the expert layers, a layer's mixer
under its kind's name at its rank among the layers of that kind) and
upcasts one layer at a time, the experts one group at a time, cut out
of the stacked weights where they lie.

  x      = embed[ids]
  layer:   a = x + Mix(rms_1(x));  x = a + F(rms_2(a)),  eps 1e-6
  Mix kda: [q~ | k~ | v~] = h W_qkv  (32 heads of 128 each), a causal
           depthwise convolution of width 4 over each channel (zeros
           before the first token; four shifted products), silu;
           q = q~ / |q~| * 128^-0.5, k = k~ / |k~| (L2 over a head, eps
           1e-6 under the root), v = v~;
           g = -5 * sigmoid(exp(A_log)[head] * (h W_f + dt_bias)) per
           head and key channel, beta = sigmoid(h W_b) per head;
           per head, S [128 key, 128 value] zero before the first token,
           TOKEN BY TOKEN (``lax.scan`` over t):
               S^  = exp(g_t)[:, None] * S
               S   = S^ + beta_t * k_t (outer) (v_t - S^^T k_t)
               o_t = S^T q_t
           o = rms_head(o) (one learned [128] scale) * sigmoid(h W_g),
           then (o flattened to 4096) W_o.
  Mix mla: q = h W_q [32, 192] cut into q_n [128] and q_r [64];
           [c | k_r] = h W_kva [512 | 64], c = rms_c(c);
           [k_n | v] = c W_kvb [32, 128 | 128], EXPANDED for every token;
           rotary (theta 6e6, adjacent pairs) over q_r of every head and
           over the one k_r all heads share;
           softmax((q_n . k_n + q_r . k_r) / sqrt(192)) over a full
           masked score matrix (a block of its rows at a time), o = p v;
           o_head *= sigmoid(h W_y)[head]; (o flattened to 4096) W_o.
  F dense: (silu(h W_g) * (h W_i)) W_o, width 6144  (l < first_k_dense)
  F moe:   s = sigmoid_f32(h W_r) over ALL ``router_outputs`` experts;
           s' = s + b for the choice; the experts in order form n_group
           groups, a group's score the sum of its two largest s'; the
           topk_group best groups stay; S = the 8 largest s' among their
           experts; w_e = 2.5 * s_e / sum_{S} s;
           y = sum over e in S AND HELD HERE of w_e E_e(h), + the shared
           expert once, ungated.  ``experts_held`` [first, count]: the
           experts whose weights the tree holds; the others' terms
           belong to other chips and are not added, nothing stands in.
  logits = rms_f(x) W_head  (untied; the vocabulary's slice)

Departures from the publication: none in the mathematics of what is
kept.  Left out, as the configuration's file says: the multi-token-
prediction module, the SwiGLU clamp of the last eight layers (none of
the kept layers has one).  Of the memory: the score matrix 256 rows at
a time, the experts over blocks of 512 tokens and upcast 16 at a time
(beside the engine's weights and both caches the reference's temporaries
made the set-up's peak 16.9 GB of the chip's 17.2 at 32 and 512: my chip
runs, PR 44).
The system divides the chosen scores by ``max(sum, 1e-9)``, this by
``sum + 1e-20``: eight sigmoids sum to about 4.

``wrong`` computes the forward with one thing done wrongly, for the
readings that show what the cell's tolerance refuses (PERF.md):
``no_kda`` (the KDA mixers add nothing), ``no_delta`` (``S = S^ + beta k
v^T``), ``head_decay`` (the decay's mean over a head's channels),
``softplus_gate`` (``g = -exp(A_log) softplus(a)``), ``state_reset@P``
(every state zeroed before position P), ``no_tail@P`` (the convolution
at P and after sees zeros before P), ``old_state`` (the states and tails
start where this same sequence, reversed, left them), ``no_rope_key``
(k_r left out of the scores), ``no_c_norm``, ``no_head_gate``,
``norm_over_held`` (the weights normalised over the held experts taken),
``no_route_scale``, ``no_group_limit``, ``int8`` (every layer matrix
rounded to int8 with one scale a row).

``following`` computes the same forward with the router's CHOICE given
(the experts the system took, by token and layer, in the router's own
numbering) and everything else its own, and says how far that choice
lies from its own: the largest amount by which a taken expert's biased
score falls short of this reference's own eighth inside its own kept
groups; for a taken expert of a group it did not keep, by how much that
group's score falls short of the last kept group's as well.
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_kda", "no_delta", "head_decay", "softplus_gate", "state_reset",
         "no_tail", "old_state", "no_rope_key", "no_c_norm", "no_head_gate",
         "norm_over_held", "no_route_scale", "no_group_limit", "int8")
EXPERT_TOKENS = 512         # the experts run over blocks of this many
EXPERT_GROUP = 16           # and are upcast this many at a time
QUERY_ROWS = 256            # rows of the score matrix computed at a time


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _l2(x, eps):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + eps)


def _rotary_pairs(x, base):
    """Adjacent pairs (x0, x1), (x2, x3), ... of the last axis rotated by
    the position (axis 0) times base^(-2i/R)."""
    S, R = x.shape[0], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, R, 2, dtype=F32) / R))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (R // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _int8(w):
    """``w`` rounded to int8 with one scale a row of its last axis."""
    scale = jnp.maximum(jnp.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(w / scale) * scale


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def _kda(h, m, c, wrong, at, init):
    """The KDA mixer over ``h [S, d]`` → (out [S, d], (the last state
    [H, K, V], the last inputs of the convolution)).  ``init``: a state
    and a tail to start from, or None."""
    S = h.shape[0]
    H = m["A_log"].shape[0]
    K = m["w_f"].shape[1] // H
    V = m["w_g"].shape[1] // H
    W = m["conv_w"].shape[1]
    eps = c["rms_norm_eps"]
    raw = h @ m["w_qkv"]                                        # [S, C]
    before = jnp.zeros((W - 1, raw.shape[1]), F32) if init is None \
        else init[1]
    padded = jnp.concatenate([before, raw])
    pos = jnp.arange(S)
    conv = 0.0
    for j in range(W):            # four shifted products
        shifted = padded[j:j + S]
        if wrong == "no_tail":
            shifted = jnp.where(((pos >= at) & (pos - (W - 1 - j) < at))
                                [:, None], 0.0, shifted)
        conv = conv + m["conv_w"][:, j] * shifted
    x = jax.nn.silu(conv)
    q = _l2(x[:, :H * K].reshape(S, H, K), eps) * K ** -0.5
    k = _l2(x[:, H * K:2 * H * K].reshape(S, H, K), eps)
    v = x[:, 2 * H * K:].reshape(S, H, V)
    a = (h @ m["w_f"] + m["dt_bias"]).reshape(S, H, K)
    rate = jnp.exp(m["A_log"])[:, None]
    if wrong == "softplus_gate":
        g = -rate * jax.nn.softplus(a)
    else:
        g = c["kda_lower_bound"] * jax.nn.sigmoid(rate * a)
    if wrong == "head_decay":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ m["w_b"])                          # [S, H]

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs
        if wrong == "state_reset":
            s = jnp.where(t == at, 0.0, s)
        s_hat = jnp.exp(g_t)[:, :, None] * s
        pred = jnp.einsum("hkv,hk->hv", s_hat, k_t)
        target = v_t if wrong == "no_delta" else v_t - pred
        s = s_hat + b_t[:, None, None] * k_t[:, :, None] * target[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((H, K, V), F32) if init is None else init[0]
    last, o = jax.lax.scan(token, s0, (q, k, v, g, beta, pos))
    o = _rms(o, m["norm"], eps).reshape(S, H * V) \
        * jax.nn.sigmoid(h @ m["w_g"])
    out = o @ m["w_o"]
    return (jnp.zeros_like(out) if wrong == "no_kda" else out), \
        (last, padded[-(W - 1):])


def _mla(h, m, c, wrong):
    S = h.shape[0]
    H = m["wq"].shape[1]
    n, r, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank = c["kv_lora_rank"]
    eps = c["rms_norm_eps"]
    q = (h @ m["wq"].reshape(h.shape[1], -1)).reshape(S, H, n + r)
    q_n, q_r = q[..., :n], _rotary_pairs(q[..., n:], c["rope_theta"])
    kva = h @ m["w_kva"]
    lat = kva[:, :rank]
    if wrong != "no_c_norm":
        lat = _rms(lat, m["c_norm"], eps)
    k_r = _rotary_pairs(kva[:, rank:], c["rope_theta"])          # [S, r]
    kv = (lat @ m["w_kvb"].reshape(rank, -1)).reshape(S, H, n + vd)
    k_n, v = kv[..., :n], kv[..., n:]          # expanded for every token
    j = jnp.arange(S)[None, :]

    def rows(qi):
        qn, qr, i = qi
        s = jnp.einsum("qhn,thn->hqt", qn, k_n)
        if wrong != "no_rope_key":
            s = s + jnp.einsum("qhr,tr->hqt", qr, k_r)
        s = jnp.where((j <= i[:, None])[None], s / jnp.sqrt(F32(n + r)),
                      -jnp.inf)
        return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v)

    nb = -(-S // QUERY_ROWS)
    pad = ((0, nb * QUERY_ROWS - S), (0, 0), (0, 0))
    o = jax.lax.map(rows, (
        jnp.pad(q_n, pad).reshape(nb, QUERY_ROWS, H, n),
        jnp.pad(q_r, pad).reshape(nb, QUERY_ROWS, H, r),
        jnp.arange(nb * QUERY_ROWS).reshape(nb, -1)))
    o = o.reshape(nb * QUERY_ROWS, H, vd)[:S]
    if wrong != "no_head_gate":
        o = o * jax.nn.sigmoid(h @ m["wg"])[:, :, None]
    return o.reshape(S, H * vd) @ m["wo"].reshape(H * vd, -1)


def _experts(h, lp, c, wrong, given=None, li=0):
    """[n, d] tokens through the router and the held experts → (y, [the
    margin between the eighth and the ninth biased score inside the kept
    groups, how far the taken experts fall short of the eighth]).
    ``given [n, 8]``: the experts to take, in the router's numbering.
    ``lp``'s experts are the STACK of all the expert layers' ``[layers,
    count, ...]``, in the type they are stored in, this layer's at
    ``li``: a group is cut out of it where it lies (a layer cut out
    whole is a copy of 1.4 GB beside the engine's caches)."""
    k = c["num_experts_per_tok"]
    G, keep = c["n_group"], c["topk_group"]
    first, count = c["experts_held"]
    score = jax.nn.sigmoid(h @ lp["gate"]["kernel"])              # [n, E]
    E = score.shape[-1]
    biased = score + lp["gate"]["bias"]
    # a group's score: the sum of its two largest biased scores
    two = jnp.sort(biased.reshape(-1, G, E // G), axis=-1)[..., -2:].sum(-1)
    ranked_g = jnp.sort(two, axis=-1)
    kept = two >= ranked_g[:, -keep][:, None]                      # [n, G]
    if wrong == "no_group_limit":
        kept = jnp.ones_like(kept)
    inside = jnp.where(jnp.repeat(kept, E // G, axis=1), biased, -jnp.inf)
    ranked = jnp.sort(inside, axis=-1)
    eighth = ranked[:, -k]
    if given is None:
        chosen = inside >= eighth[:, None]
    else:
        chosen = (given[:, :, None] == jnp.arange(E)).any(1)
    margin = eighth - ranked[:, -k - 1]
    # a taken expert's shortfall: of its score below the eighth, and of
    # its group's score below the last kept group's
    lack = jnp.maximum(eighth[:, None] - biased, 0.0) + jnp.repeat(
        jnp.maximum(ranked_g[:, -keep][:, None] - two, 0.0), E // G, axis=1)
    short = jnp.where(chosen, lack, 0.0).max(-1)
    here = (jnp.arange(E) >= first) & (jnp.arange(E) < first + count)
    w = jnp.where(chosen, score, 0.0)
    total = jnp.where(here, w, 0.0).sum(-1, keepdims=True) \
        if wrong == "norm_over_held" else w.sum(-1, keepdims=True)
    if c["norm_topk_prob"]:
        w = w / (total + 1e-20)
    if wrong != "no_route_scale":
        w = w * c["routed_scaling_factor"]
    w = w[:, first:first + count]          # only the held experts' terms

    # a group of experts is upcast ONCE and runs over the tokens a block
    # at a time (the other nesting, blocks outside and groups inside,
    # had the compiler lift every group's float32 weights out of the
    # blocks' loop: 6 GB at once)
    g = min(EXPERT_GROUP, count)
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
    return y + _swiglu(h, lp["shared"]), jnp.stack([margin, short])


def _prepared(a, wrong):
    """A stored weight as the reference computes with it: float32, and
    for the ``int8`` control rounded first."""
    a = a.astype(F32)
    return _int8(a) if wrong == "int8" and a.ndim >= 2 else a


def _layer(x, lp, c, kind, dense, wrong, at, init=None, given=None, li=0):
    lp = {k: v if k == "experts"
          else jax.tree.map(lambda a: _prepared(a, wrong), v)
          for k, v in lp.items()}
    eps = c["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    left = None
    if kind == "kda":
        mix, left = _kda(h, lp["kda"], c, wrong, at, init)
    else:
        mix = _mla(h, lp["mla"], c, wrong)
    x = x + mix
    h = _rms(x, lp["ln2"]["scale"], eps)
    if dense:
        y, router = _swiglu(h, lp["mlp"]), jnp.zeros((2,) + x.shape[:1], F32)
    else:
        y, router = _experts(h, lp, c, wrong, given, li)
    return x + y, router, left


_KEYS = ("rms_norm_eps", "rope_theta", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "kda_lower_bound",
         "n_group", "topk_group", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor")


@functools.lru_cache(maxsize=None)
def _programs(keys, held, wrong, at):
    c = dict(zip(_KEYS, keys), experts_held=held)
    return ({(kind, d): jax.jit(
                 lambda x, lp, init=None, given=None, li=0, kind=kind, d=d:
                 _layer(x, lp, c, kind, d, wrong, at, init, given, li))
             for kind in ("kda", "mla") for d in (False, True)},
            jax.jit(lambda x, s, w: _rms(x, s.astype(F32), keys[0])
                    @ w.astype(F32)))


def _held(c):
    return tuple(c.get("experts_held") or (0, c["num_experts"]))


def _forward(params, ids, c, wrong=None, last=None, routing=None,
             inits=None):
    """→ (logits, [expert layers, 2, S], what the KDA layers leave).
    ``routing [expert layers, S, 8]``: the choice to follow.  ``inits``:
    what each KDA layer starts from."""
    name, _, at = (wrong or "").partition("@")
    name = name or None
    assert name is None or name in WRONG, wrong
    if name == "old_state" and inits is None:
        *_, inits = _forward(params, list(ids)[::-1], c)
    layer, head = _programs(tuple(c[k] for k in _KEYS), _held(c), name,
                            int(at) if at else len(ids) // 2)
    lead = c["first_k_dense_replace"]
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    routers, lefts = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        for i, kind in enumerate(kinds):
            stack, first = (params["dense_blocks"], 0) if i < lead \
                else (params["blocks"], lead)
            # a layer's mixer lies at its rank among its kind in its stack
            rank = kinds[first:i].count(kind)
            # (the experts go in as the stack, cut a group at a time)
            lp = {k: v if k == "experts" else jax.tree.map(
                lambda a, at_=rank if k in ("kda", "mla") else i - first:
                a[at_], v) for k, v in stack.items()
                if k not in ("kda", "mla") or k == kind}
            given = None if routing is None or i < lead \
                else jnp.asarray(routing[i - lead])
            init = inits[len(lefts)] if inits is not None and kind == "kda" \
                else None
            x, router, left = layer[kind, i < lead](
                x, lp, init, given, jnp.int32(max(i - lead, 0)))
            if kind == "kda":
                lefts.append(left)
            if i >= lead:
                routers.append(router)
        return (head(x if last is None else x[-last:],
                     params["ln_f"]["scale"], params["lm_head"]["kernel"]),
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
        # scores near a half: bfloat16 rounds them to 2^-9
        "near_ties": int((margins < 2.0 ** -8).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def following(params, ids, c, routing, wrong=None, last=None):
    """``logits`` with the router's choice given: ``routing [expert
    layers, S, 8]``, the experts each token took (the router's numbering).
    → (logits, the largest shortfall of a taken expert, over tokens and
    layers)."""
    out, routers, _ = _forward(params, ids, c, wrong, last, routing)
    return out, float(routers[:, 1].max())
