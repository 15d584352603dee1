"""Plain reference for ``falcon-h1-34b-d6``.

The architecture as published (tiiuae/Falcon-H1-34B-Instruct
``config.json``; what its keys do not say is from ``model_type:
falcon_h1``'s public modelling code and listed under ``assumed`` in the
configuration's file), written out in ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunks, no sharing of code with ``deepspeed_tpu``.  It
READS the system's seeded bf16 parameter tree and upcasts one matrix at
a time, and computes the head in blocks of the vocabulary, so that it
fits beside 10.5 GB of weights and the engine's pools.

``N(.)`` is an RMSNorm with a learned scale, eps ``rms_norm_eps``.

  x0     = embed[ids] * embedding_multiplier
  block:   h = N_in(x)
           a = x + ssm_out_multiplier * Mamba(ssm_in_multiplier * h)
                 + attention_out_multiplier * Attn(attention_in_multiplier * h)
           x' = a + MLP(N_ff(a))
           BOTH mixers read the same normed input and are summed.
  Attn:    q = h Wq (20 heads of 128), k = (h Wk) * key_multiplier,
           v = h Wv (4 heads, each shared by 5 query heads); rotary over
           the whole head (rotate-half pairing, base rope_theta); causal
           softmax(q k^T / sqrt(128)) v as a full masked score matrix;
           Wo [2560 -> 5120].  No biases.
  Mamba:   p = (u W_in) * m, m the constant vector that holds
           ssm_multipliers over the columns of z, x, B, C, dt in that
           order; p cut in order into z [4096], xBC [4096 + 2*2*256],
           dt [32].
           xBC_t <- silu(b_c + sum_{j=0..3} w_c[:, j] * xBC_{t-3+j})
           (depthwise, causal, zeros before the first token): a sum of
           four shifted products.  Cut into x_t [32, 128], B_t, C_t
           [2, 256].  dt_t = softplus(dt_t + dt_bias), A = -exp(A_log).
           Per head i, group g = i // 16, TOKEN BY TOKEN (a lax.scan
           over t of exactly this line, no chunks):
               S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t[g]
               y_t = S_t C_t[g] + D_i x_t          S_0 = 0, S: [128, 256]
           y <- y * silu(z); an RMSNorm over each group's 2048 channels
           apart with a learned [4096] scale; y W_out [4096 -> 5120].
  MLP:     ((h W_up) * silu((h W_gate) * mlp_multipliers[0])) W_down
           * mlp_multipliers[1]
  logits = N_f(x) W_head * lm_head_multiplier                  (untied)

Departures from the publication: none in the mathematics.  The key
multiplier stands on the keys and the column multipliers on ``p``, where
the modelling code has them; nothing is folded into a weight.

``wrong`` computes the forward with one thing done wrongly, for the
comparison's tolerance to be fitted against (``WRONG``): the mixer left
out (``no_mixer``); the recurrent state zeroed before token ``at``
(``state_reset``: a state lost between prefill and decode); the
convolution reading zeros before token ``at`` (``tail_cut``: a tail not
carried over a chunk's boundary); the state started from what
``before`` leaves instead of zeros (``stale_state``: a slot's old state
kept by the next sequence; the convolution's tail likewise); the column
multipliers left out (``no_col_scales``); the key multiplier left out
(``no_key_scale``); the gated norm over all 4096 channels at once
(``norm_ungrouped``); every layer matrix rounded to int8 with one scale
a row (``int8``).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("no_mixer", "state_reset", "tail_cut", "stale_state",
         "no_col_scales", "no_key_scale", "norm_ungrouped", "int8")


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    S, _, D = x.shape
    inv = 1.0 / (F32(base) ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(w):
    """``w`` rounded to int8 with one scale a row of its last axis."""
    scale = jnp.maximum(jnp.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(w / scale) * scale


def _mm(x, w, rounded=False, block=1 << 24):
    """``x @ w`` with ``w`` upcast where it is used, a block of at most
    ``block`` elements of its columns at a time: one part of one matrix
    in float32 at a time.  ``w``'s leading axes beyond x's last are
    flattened to match."""
    k = x.shape[-1]
    if rounded:          # whole: a scale a row of the matrix's last axis
        return x @ _int8(w.astype(F32)).reshape(k, -1)
    w = w.reshape(k, -1)
    n = w.shape[1]
    parts = -(-w.size // block)
    while n % parts:
        parts += 1

    def part(i):
        return x @ jax.lax.dynamic_slice(
            w, (0, i * (n // parts)), (k, n // parts)).astype(F32)

    if parts == 1:
        return x @ w.astype(F32)
    out = jax.lax.map(part, jnp.arange(parts))           # [parts, S, n/parts]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], n)


def _attention(h, a, c, wrong, mm):
    S = h.shape[0]
    H, D = a["wq"].shape[-2:]
    Hkv = a["wk"].shape[-2]
    q = mm(h, a["wq"]).reshape(S, H, D)
    k = mm(h, a["wk"]).reshape(S, Hkv, D)
    if wrong != "no_key_scale":
        k = k * F32(c["key_multiplier"])
    v = mm(h, a["wv"]).reshape(S, Hkv, D)
    q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    # grouped-query attention: query head j reads kv head j // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v)
    return mm(o.reshape(S, H * D), a["wo"].reshape(H * D, -1))


def _recurrence(x, b, c, dt, a, d_skip, s0, reset_at):
    """The recurrence, token by token.  x: [S, H, P]; b, c: [S, H, N]
    (each head's group's); dt: [S, H]; s0: [H, P, N]; ``reset_at``: the
    token before which the state is zeroed (-1: never)."""

    def token(s, xs):
        t, x_t, b_t, c_t, dt_t = xs
        s = jnp.where(t == reset_at, 0.0, s)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, c_t) + d_skip[:, None] * x_t
        return s, y

    S = x.shape[0]
    s_last, y = jax.lax.scan(token, s0,
                             (jnp.arange(S), x, b, c, dt))
    return y, s_last


def _mamba(u, m, c, wrong, mm, carried, at):
    """→ (the mixer's output [S, d], (the state it leaves, its last
    three raw convolution inputs)).  ``carried``: such a pair to start
    from: zeros, or for the ``stale_state`` control what another
    sequence left.  ``at``: a traced token index."""
    m = {k: v if k in ("w_in", "w_out") else v.astype(F32)
         for k, v in m.items()}
    S = u.shape[0]
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    G, N, W = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"]
    d = c["mamba_d_ssm"]
    p = mm(u, m["w_in"])
    if wrong != "no_col_scales":
        sz, sx, sb, sc, sdt = c["ssm_multipliers"]
        p = p * jnp.concatenate([
            jnp.full((d,), sz, F32), jnp.full((d,), sx, F32),
            jnp.full((G * N,), sb, F32), jnp.full((G * N,), sc, F32),
            jnp.full((H,), sdt, F32)])
    z, xbc, dt = p[:, :d], p[:, d:2 * d + 2 * G * N], p[:, 2 * d + 2 * G * N:]
    # the convolution: a sum of four shifted products, zeros before the
    # sequence's first token
    padded = jnp.concatenate([carried[1], xbc])
    if wrong == "tail_cut":
        # what a chunk starting at ``at`` would read with no tail
        rows = jnp.arange(S)[:, None] - jnp.arange(W - 1, -1, -1)[None, :]
        cut = (jnp.arange(S)[:, None] >= at) & (rows < at)   # [S, W]
    acc = m["conv_b"]
    for j in range(W):
        term = padded[j:j + S]
        if wrong == "tail_cut":
            term = jnp.where(cut[:, j:j + 1], 0.0, term)
        acc = acc + m["conv_w"][:, j] * term
    xbc_c = jax.nn.silu(acc)
    x = xbc_c[:, :d].reshape(S, H, P)
    b = xbc_c[:, d:d + G * N].reshape(S, G, N)
    cc = xbc_c[:, d + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    a = -jnp.exp(m["A_log"])
    # head i reads group i // (H / G)
    y, s_last = _recurrence(
        x, jnp.repeat(b, H // G, axis=1), jnp.repeat(cc, H // G, axis=1),
        dt, a, m["D"], carried[0],
        at if wrong == "state_reset" else jnp.int32(-1))
    y = y.reshape(S, d) * jax.nn.silu(z)
    groups = 1 if wrong == "norm_ungrouped" else G
    y = y.reshape(S, groups, d // groups)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + c["rms_norm_eps"])
    y = y.reshape(S, d) * m["norm"]
    return mm(y, m["w_out"]), (s_last, padded[-(W - 1):])


def _layer(x, blocks, i, c, wrong, carried, at):
    # layer ``i`` cut out of the stacked weights HERE, inside the
    # program, where each cut joins the block of columns that reads it:
    # cut outside, a layer's 0.86 GB would be copied for every call
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), blocks)
    mm = functools.partial(_mm, rounded=wrong == "int8")
    eps = c["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"].astype(F32), eps)
    left = carried
    a = x + F32(c["attention_out_multiplier"]) * _attention(
        h * F32(c["attention_in_multiplier"]), lp["attn"], c, wrong, mm)
    if wrong != "no_mixer":
        y, left = _mamba(h * F32(c["ssm_in_multiplier"]), lp["ssm"], c,
                         wrong, mm, carried, at)
        a = a + F32(c["ssm_out_multiplier"]) * y
    h = _rms(a, lp["ln2"]["scale"].astype(F32), eps)
    gate_m, down_m = c["mlp_multipliers"]
    mp = lp["mlp"]
    u = mm(h, mp["wi"]) * jax.nn.silu(mm(h, mp["wg"]) * F32(gate_m))
    return a + mm(u, mp["wo"]) * F32(down_m), left


def _head(x, scale, kernel, c, blocks=32):
    """The head in ``blocks`` blocks of the vocabulary, each cut out of
    the kernel and upcast where it is used."""
    h = _rms(x, scale.astype(F32), c["rms_norm_eps"])
    d, V = kernel.shape
    while V % blocks:
        blocks -= 1
    out = jax.lax.map(
        lambda i: h @ jax.lax.dynamic_slice(
            kernel, (0, i * (V // blocks)), (d, V // blocks)).astype(F32),
        jnp.arange(blocks))                          # [blocks, S, V/blocks]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V) \
        * F32(c["lm_head_multiplier"])


KEYS = ("num_hidden_layers", "rms_norm_eps", "rope_theta", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
        "mlp_multipliers", "embedding_multiplier", "lm_head_multiplier",
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "mamba_d_conv", "mamba_d_ssm")


@functools.lru_cache(maxsize=None)
def _programs(key, wrong):
    """One compiled program a layer and one for the head, for the
    configuration's numbers ``key`` and the control ``wrong``."""
    c = {k: list(v) if isinstance(v, tuple) else v for k, v in key}
    return (jax.jit(lambda x, blocks, i, carried, at: _layer(
        x, blocks, i, c, wrong, carried, at)),
            jax.jit(lambda x, s, w: _head(x, s, w, c)))


def _forward(params, ids, c, wrong=None, last=None, at=None, before=None):
    assert wrong is None or wrong in WRONG, wrong
    layer, head = _programs(tuple(
        (k, tuple(c[k]) if isinstance(c[k], list) else c[k])
        for k in KEYS), wrong)
    n = c["num_hidden_layers"]
    zeros = (jnp.zeros((c["mamba_n_heads"], c["mamba_d_head"],
                        c["mamba_d_state"]), F32),
             jnp.zeros((c["mamba_d_conv"] - 1, c["mamba_d_ssm"]
                        + 2 * c["mamba_n_groups"] * c["mamba_d_state"]), F32))
    carried = [zeros] * n
    if wrong == "stale_state":
        # what the sequence ``before`` leaves in every layer
        carried = _forward(params, before, c, last=1)[1]
    x = params["embed"]["table"][jnp.asarray(ids)].astype(F32) \
        * F32(c["embedding_multiplier"])
    left = []
    at = jnp.int32(-1 if at is None else at)
    for i in range(n):
        x, lf = layer(x, params["blocks"], jnp.int32(i), carried[i], at)
        left.append(lf)
    x = x if last is None else x[-last:]
    return head(x, params["ln_f"]["scale"], params["lm_head"]["kernel"]), \
        left


def logits(params, ids, c, wrong=None, last=None, at=None, before=None):
    """[S] token ids -> [S, vocab] float32.  ``wrong``: one of ``WRONG``,
    see above (``at``: the token ``state_reset`` and ``tail_cut`` act
    before; ``before``: the sequence whose state ``stale_state`` starts
    from).  ``last``: only that many last rows."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, c, wrong, last, at, before)[0]
