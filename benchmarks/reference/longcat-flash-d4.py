"""Plain reference for ``longcat-flash-d4``.

The architecture as published (meituan-longcat/LongCat-Flash-Chat
``config.json``; what the config's keys do not settle stands under
``assumed`` in the configuration's file), written out in ``jax.numpy``
and float32 under ``default_matmul_precision("highest")``: no kernel, no
cache, no batching, no chunks, no sort, no sharing of code with
``deepspeed_tpu.models``, ``.inference``, ``.ops`` or ``.parallel``.  It
READS the system's seeded bf16 parameter tree (``blocks``: a row a
SUBLAYER of the latent attentions ``mla``, the dense MLPs ``mlp`` and
the norms ``ln1`` / ``ln2``; a row a LAYER of the router ``gate`` and
the held experts ``experts``) and upcasts one sublayer's attention, one
dense MLP or one expert at a time, cut out of the stacks where they lie.

  x     = embed[ids]
  layer (two sublayers, four norms, eps 1e-5):
          a0 = x  + MLA_0(rms(x));   h0 = rms(a0);  m = MoE(h0)
          b0 = a0 + FFN_0(h0)
          a1 = b0 + MLA_1(rms(b0));  h1 = rms(a1)
          x' = a1 + FFN_1(h1) + m
          The expert layer reads the FIRST sublayer's normed input and
          joins the stream at the layer's END; nothing between reads it.
  MLA:    c_q = 2 * rms_q(h W_qa) [1536]  (sqrt(6144 / 1536));
          q = c_q W_qb [64, 192] cut into q_n [128] and q_r [64];
          [c | k_r] = h W_kva [512 | 64];  c = sqrt(12) * rms_c(c)
          (sqrt(6144 / 512); k_r is not scaled);
          [k_n | v] = c W_kvb [64, 128 | 128], EXPANDED for every token;
          rotary (theta 1e7, adjacent pairs) over q_r of every head and
          over the one k_r all heads share;
          softmax((q_n . k_n + q_r . k_r) / sqrt(192)) over a full
          masked score matrix (a block of its rows at a time), o = p v;
          (o flattened to 8192) W_o.  No gate, no bias.
  FFN:    (silu(h W_g) * (h W_i)) W_o, width 12288.
  MoE:    s = softmax_f32(h W_r) over ALL ``router_outputs`` (512 experts
          with weights, then 256 that compute nothing); s' = s + b for
          the CHOICE; S = the 12 largest s'; w_e = 6 * s_e (unbiased,
          NOT renormalised);
          y = sum over e in S, e < 512 AND HELD HERE, of w_e E_e(h)
              + (sum over e in S, e >= 512, of w_e) * h
          ``experts_held`` [first, count]: the experts whose weights the
          tree holds; the others' terms belong to other chips and are
          not added, nothing stands in.  The identity part needs no
          weights: it is this chip's for its own rows.
  logits = rms_f(x) W_head  (untied; the vocabulary's slice)

Departures from the publication: none in the mathematics of what is
kept.  Of the memory (the engine's weights and pool hold 13.4 GB of the
chip's 16 beside it): the score matrix ``QUERY_ROWS`` rows at a time,
the MLPs and the experts over blocks of ``TOKENS`` tokens, the experts
upcast ONE at a time, the attention and each dense MLP as a program of
its own, so that no more than 0.9 GB of float32 weights exists at once.

``wrong`` computes the forward with one thing done wrongly, for the
readings that show what the cell's tolerance refuses (PERF.md):
``no_q_scale`` (c_q without its 2), ``no_kv_scale`` (c without its
sqrt(12)), ``scaled_rope_key`` (k_r times sqrt(12) too: the other
reading of ``mla_scale_kv_lora``), ``no_q_norm``, ``no_rope_key``,
``renormalised`` (w divided by the 12's sum), ``no_route_scale``, ``no_zero``
(the identity part left out), ``zero_once`` (the identity part weighted
1 a token, whatever it took), ``early_skip`` (m added behind the FIRST
sublayer's MLP), ``second_input`` (the experts read the SECOND
sublayer's normed input), ``no_bias`` (the choice by the unbiased
scores), ``int8`` (every layer matrix rounded to int8 with one scale a
row).

``following`` computes the same forward with the router's CHOICE given
(the experts the system took, by token and layer, in the router's own
numbering 0..767) and everything else its own, and says how far that
choice lies from its own: the largest amount by which a taken expert's
biased score falls short of this reference's own twelfth, as a share of
that twelfth (softmax scores over 768 outputs lie about 1.3e-3).
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_q_scale", "no_kv_scale", "scaled_rope_key", "no_q_norm",
         "no_rope_key", "renormalised", "no_route_scale", "no_zero",
         "zero_once", "early_skip", "second_input", "no_bias", "int8")
TOKENS = 512                # the MLPs and experts run over blocks of this
QUERY_ROWS = 128            # rows of the score matrix computed at a time


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


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


def _prepared(a, wrong):
    """A stored weight as the reference computes with it: float32, and
    for the ``int8`` control rounded first."""
    a = a.astype(F32)
    return _int8(a) if wrong == "int8" and a.ndim >= 2 else a


def _blocks(fn, h):
    """``fn`` over ``h [S, d]`` a block of ``TOKENS`` rows at a time."""
    S = h.shape[0]
    nb = -(-S // TOKENS)
    hb = jnp.pad(h, ((0, nb * TOKENS - S), (0, 0))).reshape(nb, TOKENS, -1)
    return jax.lax.map(fn, hb).reshape(nb * TOKENS, -1)[:S]


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def _mla(x, norm, m, c, wrong):
    """``x + MLA(rms(x))`` over ``x [S, d]``."""
    m = jax.tree.map(lambda a: _prepared(a, wrong), m)
    S, d = x.shape
    H = c["num_attention_heads"]
    n, r, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, q_rank = c["kv_lora_rank"], c["q_lora_rank"]
    eps = c["rms_norm_eps"]
    h = _rms(x, norm.astype(F32), eps)
    c_q = h @ m["wq_a"]
    if wrong != "no_q_norm":
        c_q = _rms(c_q, m["q_norm"], eps)
    if c["mla_scale_q_lora"] and wrong != "no_q_scale":
        c_q = c_q * (d / q_rank) ** 0.5
    q = (c_q @ m["wq_b"]).reshape(S, H, n + r)
    q_n, q_r = q[..., :n], _rotary_pairs(q[..., n:], c["rope_theta"])
    kva = h @ m["w_kva"]
    lat = _rms(kva[:, :rank], m["c_norm"], eps)
    k_r = kva[:, rank:]
    if c["mla_scale_kv_lora"] and wrong != "no_kv_scale":
        lat = lat * (d / rank) ** 0.5
        if wrong == "scaled_rope_key":
            k_r = k_r * (d / rank) ** 0.5
    k_r = _rotary_pairs(k_r, c["rope_theta"])                    # [S, r]
    kv = (lat @ m["w_kvb"]).reshape(S, H, n + vd)
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
    o = o.reshape(nb * QUERY_ROWS, H * vd)[:S]
    return x + o @ m["wo"]


def _normed(x, norm, c):
    return _rms(x, norm.astype(F32), c["rms_norm_eps"])


def _ffn(x, h, mp, skip, wrong):
    """``x + FFN(h)`` (and ``skip``, where the layer ends here)."""
    mp = jax.tree.map(lambda a: _prepared(a, wrong), mp)
    y = x + _blocks(lambda hb: _swiglu(hb, mp), h)
    return y if skip is None else y + skip


def _route(h, gate, c, wrong, given):
    """→ (w [S, outputs] the taken experts' weights, zeros elsewhere;
    [margin, shortfall] [2, S])."""
    k = c["moe_topk"]
    score = jax.nn.softmax(h @ gate["kernel"].astype(F32), axis=-1)
    E = score.shape[-1]
    biased = score if wrong == "no_bias" else score + gate["bias"].astype(F32)
    ranked = jnp.sort(biased, axis=-1)
    twelfth = ranked[:, -k]
    if given is None:
        chosen = biased >= twelfth[:, None]
    else:
        chosen = (given[:, :, None] == jnp.arange(E)).any(1)
    margin = (twelfth - ranked[:, -k - 1]) / twelfth
    short = jnp.where(chosen, jnp.maximum(twelfth[:, None] - biased, 0.0),
                      0.0).max(-1) / twelfth
    w = jnp.where(chosen, score, 0.0)
    if wrong == "renormalised":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if wrong != "no_route_scale":
        w = w * c["routed_scaling_factor"]
    return w, jnp.stack([margin, short])


def _one_expert(y, h, w_e, experts, li, e, wrong):
    """``y + w_e * E_e(h)``: expert ``e`` of layer ``li`` cut out of the
    stack where it lies, upcast alone."""
    p = jax.tree.map(lambda a: _prepared(jax.lax.dynamic_slice(
        a, (li, e) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:])[0, 0],
        wrong), experts)
    S = h.shape[0]
    nb = -(-S // TOKENS)
    pad = nb * TOKENS - S
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, TOKENS, -1)
    wb = jnp.pad(w_e, (0, pad)).reshape(nb, TOKENS)
    out = jax.lax.map(lambda xs: xs[1][:, None] * _swiglu(xs[0], p),
                      (hb, wb))
    return y + out.reshape(nb * TOKENS, -1)[:S]


_KEYS = ("rms_norm_eps", "rope_theta", "num_attention_heads",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "kv_lora_rank", "q_lora_rank", "mla_scale_q_lora",
         "mla_scale_kv_lora", "moe_topk", "routed_scaling_factor",
         "zero_expert_num")


@functools.lru_cache(maxsize=None)
def _programs(keys, wrong):
    """One compiled program a part and a sequence length: the attention,
    the norm, the dense MLP, the router, one expert, the head."""
    c = dict(zip(_KEYS, keys))
    return dict(
        mla=jax.jit(lambda x, n, m: _mla(x, n, m, c, wrong)),
        norm=jax.jit(lambda x, n: _normed(x, n, c)),
        ffn=jax.jit(lambda x, h, mp, skip=None: _ffn(x, h, mp, skip, wrong)),
        route=jax.jit(lambda h, g, given=None: _route(h, g, c, wrong, given)),
        expert=jax.jit(lambda y, h, w, ex, li, e:
                       _one_expert(y, h, w, ex, li, e, wrong)),
        head=jax.jit(lambda x, s, w: _rms(x, s.astype(F32), keys[0])
                     @ w.astype(F32)))


def _held(c):
    return tuple(c.get("experts_held") or (0, c["n_routed_experts"]))


def _moe(run, h, blocks, li, c, wrong, given):
    """The expert layer ``li`` on ``h [S, d]`` → (m, [margin, short])."""
    first, count = _held(c)
    gate = jax.tree.map(lambda a: a[li], blocks["gate"])
    w, router = run["route"](h, gate, given)
    real = w.shape[1] - c["zero_expert_num"]
    if wrong == "no_zero":
        m = jnp.zeros_like(h)
    elif wrong == "zero_once":
        m = h
    else:
        m = w[:, real:].sum(-1, keepdims=True) * h
    for e in range(count):          # only the held experts' terms
        m = run["expert"](m, h, w[:, first + e], blocks["experts"],
                          jnp.int32(li), jnp.int32(e))
    return m, router


def _forward(params, ids, c, wrong=None, last=None, routing=None):
    """→ (logits, [expert layers, 2, S]).  ``routing [expert layers, S,
    12]``: the choice to follow."""
    assert wrong is None or wrong in WRONG, wrong
    run = _programs(tuple(c[k] for k in _KEYS), wrong)
    blocks = params["blocks"]

    def sub(name, i):
        return jax.tree.map(lambda a: a[i], blocks[name])

    routers = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        for li in range(c["num_layers"]):
            s0, s1 = 2 * li, 2 * li + 1
            given = None if routing is None else jnp.asarray(routing[li])
            a0 = run["mla"](x, blocks["ln1"]["scale"][s0], sub("mla", s0))
            h0 = run["norm"](a0, blocks["ln2"]["scale"][s0])
            if wrong != "second_input":
                m, router = _moe(run, h0, blocks, li, c, wrong, given)
            b0 = run["ffn"](a0, h0, sub("mlp", s0),
                            m if wrong == "early_skip" else None)
            a1 = run["mla"](b0, blocks["ln1"]["scale"][s1], sub("mla", s1))
            h1 = run["norm"](a1, blocks["ln2"]["scale"][s1])
            if wrong == "second_input":
                m, router = _moe(run, h1, blocks, li, c, wrong, given)
            x = run["ffn"](a1, h1, sub("mlp", s1),
                           None if wrong == "early_skip" else m)
            routers.append(router)
        return (run["head"](x if last is None else x[-last:],
                            params["ln_f"]["scale"],
                            params["lm_head"]["kernel"]),
                jnp.stack(routers))


def logits(params, ids, c, wrong=None, last=None):
    """[S] token ids -> [S, vocab] float32.  ``wrong``: one of ``WRONG``,
    see above.  ``last``: only that many last rows go through the head."""
    out, routers = _forward(params, ids, c, wrong, last)
    margins = routers[:, 0]
    sys.stdout.write(json.dumps({
        "note": "reference_router", "tokens": int(margins.shape[1]),
        "layers": int(margins.shape[0]), "wrong": wrong,
        # a twelfth and a thirteenth within bfloat16's rounding of each other
        "near_ties": int((margins < 2.0 ** -8).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def following(params, ids, c, routing, wrong=None, last=None):
    """``logits`` with the router's choice given: ``routing [expert
    layers, S, 12]``, the experts each token took (the router's numbering,
    the zero-compute ones 512..767).  → (logits, the largest shortfall of
    a taken expert over tokens and layers, as a share of the twelfth
    biased score)."""
    out, routers = _forward(params, ids, c, wrong, last, routing)
    return out, float(routers[:, 1].max())
