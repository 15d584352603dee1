"""Plain reference for ``deepseek-v2-d5``.

The architecture as published (deepseek-ai/DeepSeek-V2 ``config.json``,
``model_type`` ``deepseek_v2``; what the config's keys do not settle
stands under ``assumed`` in the configuration's file), written out in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``:
no kernel, no cache, no batching, no sort, no sharing of code with
``deepspeed_tpu.models``, ``.inference``, ``.ops`` or ``.parallel``.  It
READS the system's seeded bf16 parameter tree (``dense_blocks``: the
leading dense layer's ``mla``, ``mlp``, ``ln1``, ``ln2``; ``blocks``: a
row an expert layer of ``mla``, ``gate``, the held ``experts``,
``shared`` and the norms) and upcasts a group of heads, a dense MLP or
one expert at a time, cut out of the stacks where they lie.

  x     = embed[ids]
  layer (two norms, eps 1e-6):
          a  = x + MLA(rms(x));  h = rms(a)
          x' = a + F(h)          F: the dense MLP (12288) in layer 0,
                                 the expert layer in every later one
  MLA:    c_q = rms_q(h W_qa) [1536]; q = c_q W_qb [128, 192] cut into
          q_n [128] and q_r [64];  [c | k_r] = h W_kva [512 | 64];
          c = rms_c(c);  [k_n | v] = c W_kvb [128, 128 | 128], EXPANDED
          for every token and head;  no multipliers on the latents;
          rotary over adjacent pairs of q_r of every head and of the ONE
          k_r all heads share, at YaRN's frequencies;
          softmax(s (q_n . k_n + q_r . k_r)) over a full masked score
          matrix (a group of heads and a block of its rows at a time),
          o = p v;  (o flattened to 16384) W_o.  No gate, no bias.
  YaRN:   dim 64, base 10000, factor 40, L0 4096:
          d(r) = dim ln(L0 / (2 pi r)) / (2 ln base);
          low = max(floor(d(beta_fast)), 0), high = min(ceil(d(beta_slow)),
          dim - 1) (10 and 23);  ramp_i = clip((i - low) / (high - low), 0,
          1), i = 0..31;  f_i = base^(-2i/dim);
          inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i);
          m(t) = 0.1 t ln(factor) + 1;  cos and sin times
          m(mscale) / m(mscale_all_dim) (1.0);
          s = 192^-0.5 m(mscale_all_dim)^2 (0.072169 x 1.58963).
  FFN:    (silu(h W_g) * (h W_i)) W_o.
  MoE:    p = softmax_f32(h W_r) over ALL ``router_outputs`` (160); a
          group's score is the LARGEST p of its 20; the ``topk_group``
          (3) best of ``n_group`` (8) groups stay open; S = the 6 largest
          p among their experts; w_e = 16 p_e (NOT renormalised, no bias);
          y = sum over e in S AND HELD HERE of w_e E_e(h)  +  S(h),
          S the shared MLP of width 3072 (two shared experts), added as
          it is.  ``experts_held`` [first, count]: the experts whose
          weights the tree holds (whole groups: a group is a device's
          experts); the others' terms belong to other chips and are not
          added, nothing stands in.
  logits = rms_f(x) W_head  (untied; the vocabulary's slice)

Departures from the publication: none in the mathematics of what is
kept.  Of the memory (the engine's weights and pool hold 14.2 GB of the
chip's 16.9 beside it, and a sequence here is as long as the traffic's,
16.6k tokens, so one float32 stream ``[S, 5120]`` is 341 MB): the
attention ``HEADS`` heads at a time, each group's weights upcast alone
and its score matrix ``QUERY_ROWS`` rows at a time; the MLPs and the
experts over blocks of ``TOKENS`` tokens, the experts upcast ONE at a
time inside one loop a layer; the attention, the MLP and the expert layer
each a program of its own that WRITES ITS RESULT OVER THE STREAM IT WAS
GIVEN (donated), and the host waits for a layer's end before it launches
the next: a launch reserves its result when it is queued, so forty
experts launched ahead of the chip, each with a stream of its own to
write, held 2.4 GB at 6.4k tokens (my chip runs, PR 56).

``wrong`` computes the forward with one thing done wrongly, for the
readings that show what the cell's tolerance refuses (PERF.md):
``no_yarn_scale`` (s without m(mscale_all_dim)^2), ``no_yarn_freqs``
(the plain frequencies f_i at every pair), ``group_by_top2_sum`` (a
group scored by the sum of its two best, DeepSeek-V3's rule),
``renormalised_weights`` (w divided by the six's sum),
``unscaled_weights`` (w without its 16), ``int8`` (every layer matrix
rounded to int8 with one scale a row: the nearest precision below the
one the configuration states).

``following`` computes the same forward with the router's CHOICE given
(the experts the system took, by token and layer, in the router's own
numbering 0..159) and everything else its own, and says how far that
choice lies from its own: the largest amount by which a taken expert's
group's score falls short of this reference's last open group's, and the
expert's score short of the sixth inside the open set nearest this
reference's own that holds the groups the taken experts span (a third and
a fourth group within rounding swap as a sixth and a seventh expert do,
and the sixth inside the swapped set is another number), as a share of
this reference's own sixth (softmax scores over 160 outputs lie about
6e-3).  A choice over more than ``topk_group`` groups, or by another
group rule, leaves a spanned group far below the last open one.  Its
line ``reference_groups`` says, beside that, what the statistic's FIRST
form reads (a taken expert held to the sixth inside this reference's OWN
open groups), at which layer and token, and how far apart this
reference's last open and first closed group lie there, as a share of
the open one's score; then how many token-layers' choices span a group
this reference closed, and the widest such gap among them: the readings
that say whether a large first-form value is a swap of two groups within
the arithmetic's noise or a choice by another rule.
"""

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_yarn_scale", "no_yarn_freqs", "group_by_top2_sum",
         "renormalised_weights", "unscaled_weights", "int8")
TOKENS = 512                # the MLPs and experts run over blocks of this
QUERY_ROWS = 128            # rows of the score matrix computed at a time
HEADS = 8                   # heads whose keys and values exist at a time


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _yarn(c, wrong):
    """→ (inv_freq [dim / 2], the table's multiplier, the softmax
    scale)."""
    n, dim = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    base, rs = c["rope_theta"], dict(c["rope_scaling"])
    factor, l0 = rs["factor"], rs["original_max_position_embeddings"]
    i = jnp.arange(dim // 2, dtype=F32)
    f = base ** (-2.0 * i / dim)

    def d(r):
        return dim * math.log(l0 / (2 * math.pi * r)) / (2 * math.log(base))

    low = max(math.floor(d(rs["beta_fast"])), 0)
    high = min(math.ceil(d(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = f if wrong == "no_yarn_freqs" else f / factor * ramp + f * (1 - ramp)

    def m(t):
        return 0.1 * t * math.log(factor) + 1.0

    scale = (n + dim) ** -0.5
    if wrong != "no_yarn_scale":
        scale = scale * m(rs["mscale_all_dim"]) ** 2
    return inv, m(rs["mscale"]) / m(rs["mscale_all_dim"]), scale


def _rotary_pairs(x, inv, mult):
    """Adjacent pairs (x0, x1), (x2, x3), ... of the last axis rotated by
    the position (axis 0) times ``inv``."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (inv.shape[0],))
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _int8(w):
    """``w`` rounded to int8 with one scale a row of its last axis."""
    scale = jnp.maximum(jnp.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(w / scale) * scale


def _prepared(a, wrong):
    """A stored weight as the reference computes with it: upcast where it
    is used, and for the ``int8`` control rounded first, whole."""
    return _int8(a.astype(F32)) if wrong == "int8" and a.ndim >= 2 else a


def _blocks(fn, *xs):
    """``fn`` over arrays ``[S, ...]`` a block of ``TOKENS`` rows at a
    time; ``S`` is whole blocks (``_forward`` pads the sequence's end)."""
    nb = xs[0].shape[0] // TOKENS
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape((nb, TOKENS) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape((nb * TOKENS,) + o.shape[2:]),
                        out)


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"].astype(F32)) * (h @ p["wi"].astype(F32))
            ) @ p["wo"].astype(F32)


def _mla(x, norm, m, li, c, wrong):
    """``x + MLA(rms(x))`` over ``x [S, d]``, layer ``li`` of the stack
    ``m``."""
    m = jax.tree.map(lambda a: _prepared(a[li], wrong), m)
    S, d = x.shape
    H = c["num_attention_heads"]
    n, r, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, q_rank = c["kv_lora_rank"], c["q_lora_rank"]
    eps = c["rms_norm_eps"]
    inv, mult, scale = _yarn(c, wrong)
    h = _rms(x, norm, eps)
    c_q = _rms(h @ m["wq_a"].astype(F32), m["q_norm"], eps)
    kva = h @ m["w_kva"].astype(F32)
    lat = _rms(kva[:, :rank], m["c_norm"], eps)
    k_r = _rotary_pairs(kva[:, rank:], inv, mult)                 # [S, r]
    g = math.gcd(H, HEADS)
    j = jnp.arange(S)[None, :]
    nb = S // QUERY_ROWS                # whole: TOKENS is a multiple
    at = jnp.arange(S).reshape(nb, -1)

    def heads(y, ws):
        wq_b, w_kvb, wo = (w.astype(F32) for w in ws)
        q = jnp.einsum("sc,cgx->sgx", c_q, wq_b)
        q_n, q_r = q[..., :n], _rotary_pairs(q[..., n:], inv, mult)
        kv = jnp.einsum("sc,cgx->sgx", lat, w_kvb)
        k_n, v = kv[..., :n], kv[..., n:]      # expanded for every token

        def rows(qi):
            qn, qr, i = qi
            s = jnp.einsum("qgn,tgn->gqt", qn, k_n) \
                + jnp.einsum("qgr,tr->gqt", qr, k_r)
            s = jnp.where((j <= i[:, None])[None], s * scale, -jnp.inf)
            return jnp.einsum("gqt,tgv->qgv", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(rows, (q_n.reshape(nb, QUERY_ROWS, g, n),
                               q_r.reshape(nb, QUERY_ROWS, g, r), at))
        return y + o.reshape(S, g * vd) @ wo, None

    y, _ = jax.lax.scan(heads, x, (
        m["wq_b"].reshape(q_rank, H // g, g, n + r).transpose(1, 0, 2, 3),
        m["w_kvb"].reshape(rank, H // g, g, n + vd).transpose(1, 0, 2, 3),
        m["wo"].reshape(H // g, g * vd, d)))
    return y


def _ffn(a, norm, mp, li, c, wrong):
    """``a + FFN(rms(a))``: the dense MLP of leading layer ``li``."""
    mp = jax.tree.map(lambda w: _prepared(w[li], wrong), mp)
    return _blocks(lambda ab: ab + _swiglu(
        _rms(ab, norm, c["rms_norm_eps"]), mp), a)


def _route(h, gate, c, wrong, given):
    """→ (w [S, outputs] the taken experts' weights, zeros elsewhere;
    [margin, shortfall, the shortfall's first form, the gap between the
    last open and the first closed group, that gap where the given
    choice spans a group this reference closed] [5, S])."""
    k, G, keep = c["num_experts_per_tok"], c["n_group"], c["topk_group"]
    p = jax.nn.softmax(h @ gate["kernel"].astype(F32), axis=-1)
    E = p.shape[-1]
    by_group = jnp.sort(p.reshape(-1, G, E // G), axis=-1)
    of_group = by_group[..., -2:].sum(-1) if wrong == "group_by_top2_sum" \
        else by_group[..., -1]
    ranked_g = jnp.sort(of_group, axis=-1)
    last_open = ranked_g[:, -keep]
    gap = (last_open - ranked_g[:, -keep - 1]) / last_open
    open_ = of_group >= last_open[:, None]                         # [S, G]
    inside = jnp.where(jnp.repeat(open_, E // G, axis=1), p, -jnp.inf)
    ranked = jnp.sort(inside, axis=-1)
    sixth = ranked[:, -k]
    margin = (sixth - ranked[:, -k - 1]) / sixth
    swapped = jnp.zeros_like(gap)
    if given is None:
        chosen, among = inside >= sixth[:, None], sixth
    else:
        chosen = (given[:, :, None] == jnp.arange(E)).any(1)
        # a third and a fourth group within rounding of each other swap
        # as a sixth and a seventh expert do, and the sixth INSIDE the
        # swapped group set is another: the taken experts are held to
        # the sixth of the open set nearest this reference's own that
        # holds every group they span (those groups first, then its own
        # best), and each spanned group to the last open group's score
        spanned = chosen.reshape(-1, G, E // G).any(-1)
        swapped = jnp.where((spanned & ~open_).any(-1), gap, 0.0)
        first = jnp.where(spanned, jnp.inf, of_group)
        nearest = first >= jnp.sort(first, axis=-1)[:, -keep][:, None]
        among = jnp.sort(jnp.where(jnp.repeat(nearest, E // G, axis=1), p,
                                   -jnp.inf), axis=-1)[:, -k]
    # a taken expert's shortfall: of its score below that sixth, and of
    # its group's score below the last open group's
    behind = jnp.repeat(jnp.maximum(last_open[:, None] - of_group, 0.0),
                        E // G, axis=1)

    def short(held_to):
        lack = jnp.maximum(held_to[:, None] - p, 0.0) + behind
        return jnp.where(chosen, lack, 0.0).max(-1) / sixth

    w = jnp.where(chosen, p, 0.0)
    if c["norm_topk_prob"] or wrong == "renormalised_weights":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if wrong != "unscaled_weights":
        w = w * c["routed_scaling_factor"]
    return w, jnp.stack([margin, short(among), short(sixth), gap, swapped])


def _moe(a, norm, gate, shared, experts, li, c, wrong, given):
    """``a + MoE(rms(a))`` of expert layer ``li`` → (x', the router's five
    readings ``[5, S]``), a block of ``TOKENS`` rows at a time: the shared
    MLP, every token's, then only the held experts' terms, expert ``e``
    cut out of the stack where it lies and upcast alone, one after
    another."""
    first, count = _held(c)
    gate, shared = (jax.tree.map(lambda w: _prepared(w[li], wrong), t)
                    for t in (gate, shared))

    def block(ab, given_b=None):
        h = _rms(ab, norm, c["rms_norm_eps"])
        w, router = _route(h, gate, c, wrong, given_b)

        def one(e, y):
            p = jax.tree.map(lambda t: _prepared(jax.lax.dynamic_slice(
                t, (li, e) + (0,) * (t.ndim - 2), (1, 1) + t.shape[2:])[0, 0],
                wrong), experts)
            w_e = jax.lax.dynamic_index_in_dim(w, first + e, axis=1)
            return y + w_e * _swiglu(h, p)

        return jax.lax.fori_loop(0, count, one, ab + _swiglu(h, shared)), \
            router.T

    y, router = _blocks(block, a, *(() if given is None else (given,)))
    return y, router.T


_KEYS = ("rms_norm_eps", "rope_theta", "rope_scaling",
         "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "q_lora_rank", "num_experts_per_tok",
         "routed_scaling_factor", "n_group", "topk_group", "norm_topk_prob",
         "n_routed_experts", "experts_held")


def _key(c):
    return tuple(tuple(sorted(v.items())) if isinstance(v, dict)
                 else tuple(v) if isinstance(v, list) else v
                 for v in map(c.get, _KEYS))


def _held(c):
    return tuple(c.get("experts_held") or (0, c["n_routed_experts"]))


@functools.lru_cache(maxsize=None)
def _programs(keys, wrong):
    """One compiled program a part and a sequence length: the attention,
    the dense MLP, an expert layer, the head.  The three that add to the
    stream write over the one they were given."""
    c = dict(zip(_KEYS, keys))
    eps = c["rms_norm_eps"]
    return dict(
        mla=jax.jit(lambda x, n, m, li: _mla(x, n, m, li, c, wrong),
                    donate_argnums=0),
        ffn=jax.jit(lambda a, n, mp, li: _ffn(a, n, mp, li, c, wrong),
                    donate_argnums=0),
        moe=jax.jit(lambda a, n, gate, shared, experts, li, given=None:
                    _moe(a, n, gate, shared, experts, li, c, wrong, given),
                    donate_argnums=0),
        head=jax.jit(lambda x, s, w: _rms(x, s, eps) @ w.astype(F32)))


def _forward(params, ids, c, wrong=None, last=None, routing=None):
    """→ (logits, [expert layers, 5, S]).  ``routing [expert layers, S,
    6]``: the choice to follow."""
    assert wrong is None or wrong in WRONG, wrong
    run = _programs(_key(c), wrong)
    lead = c["first_k_dense_replace"]
    S = len(ids)
    # whole blocks of TOKENS: tokens (id 0) behind the sequence's end,
    # which no position of it sees
    ids = jnp.pad(jnp.asarray(ids), (0, -S % TOKENS))
    routers = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][ids].astype(F32)
        for layer in range(c["num_hidden_layers"]):
            blocks = params["dense_blocks" if layer < lead else "blocks"]
            li = jnp.int32(layer if layer < lead else layer - lead)
            # a layer is cut out of the stacks inside the programs: cut
            # out here it would stand beside them, a copy a launch
            a = run["mla"](x, blocks["ln1"]["scale"][li], blocks["mla"], li)
            ln2 = blocks["ln2"]["scale"][li]
            if layer < lead:
                x = run["ffn"](a, ln2, blocks["mlp"], li)
            else:
                given = None if routing is None else jnp.pad(
                    jnp.asarray(routing[layer - lead]),
                    ((0, -S % TOKENS), (0, 0)))
                x, router = run["moe"](a, ln2, blocks["gate"],
                                       blocks["shared"], blocks["experts"],
                                       li, given)
                routers.append(router[:, :S])
            # a launch reserves its result when it is queued: no layer is
            # launched before the one below it has ended
            x = jax.block_until_ready(x)
        return (run["head"](x[S - (last or S):S], params["ln_f"]["scale"],
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
        # a sixth and a seventh within bfloat16's rounding of each other
        "near_ties": int((margins < 2.0 ** -8).sum()),
        "smallest_margin": float(margins.min())}) + "\n")
    sys.stdout.flush()
    return out


def following(params, ids, c, routing, wrong=None, last=None):
    """``logits`` with the router's choice given: ``routing [expert
    layers, S, 6]``, the experts each token took (the router's numbering).
    → (logits, the largest shortfall of a taken expert over tokens and
    layers, as a share of the sixth score)."""
    out, routers = _forward(params, ids, c, wrong, last, routing)
    short, own, gap, swapped = (routers[:, i] for i in (1, 2, 3, 4))
    layer, token = divmod(int(own.argmax()), own.shape[1])
    sys.stdout.write(json.dumps({
        "note": "reference_groups", "tokens": int(own.shape[1]),
        "wrong": wrong, "shortfall": float(short.max()),
        # held to the sixth inside this reference's OWN open groups
        "first_form": float(own[layer, token]), "layer": layer,
        "token": token, "shortfall_there": float(short[layer, token]),
        # its last open group over its first closed one, there
        "group_gap_there": float(gap[layer, token]),
        # the token-layers whose given choice spans a group this
        # reference closed, and the widest gap any of them crossed
        "swaps": int((swapped > 0).sum()),
        "widest_gap_swapped": float(swapped.max())}) + "\n")
    sys.stdout.flush()
    return out, float(short.max())
