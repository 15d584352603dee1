"""Delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692): the computations a served ``"kda"`` layer
needs.

Per head, with ``S`` a ``[K (key), V (value)]`` state that is zero before
a sequence's first token, ``alpha_t = exp(g_t)`` a decay per KEY CHANNEL
and ``beta_t`` one number::

    S^  = Diag(alpha_t) S_{t-1}
    S_t = S^ + beta_t k_t (v_t - S^^T k_t)^T
    o_t = S_t^T q_t

in front of it a depthwise causal convolution and SiLU over the q, k and
v channels together (``ops/ssm.py`` ``conv_rows`` / ``conv_tails``, scope
``kda_conv``), an L2 norm of q and k, and behind it an RMSNorm over each
head's values under a sigmoid gate.  What this file computes:

* :func:`gates` / :func:`gated_norm` (scope ``kda_gate``): the bounded
  decay ``g = bound * sigmoid(exp(A_log) * (a + dt_bias))`` in
  ``(bound, 0)``, ``beta``, and the output's gated norm;
* :func:`state_update` (scope ``kda_update``): one token for every slot
  of the state pool that a step advances by one token, dense over the
  pool as ``ops/ssm.state_update`` is, in XLA (two reads of the rows
  and a write), and :func:`state_update_in_place`, the same as one pass
  by a Pallas kernel over the stack in place (the call's scaffolding is
  ``ops/ssm.update_rows_in_place``; a TPU, the rows on one device);
* :func:`chunk_rule` (scope ``kda_chunk``): the chunked form over a list
  of chunks of ``Q`` tokens, each of one run: inside a chunk the WY form
  of the delta rule (``u = (I + A)^-1 beta (v - ...)``), between chunks
  the state carried in float32.  A decay per channel cannot be pulled
  out of a product of two rows as one factor: ``exp(G_i - G_j)`` is cut
  at a reference row as ``exp(G_i - r) exp(r - G_j)``, and with ``g``
  bounded below by ``bound`` a block of ``block`` rows keeps both
  factors inside float32 (16 rows at -5: at most ``e^80``).

``mixer_forward`` is the whole mixer over whole sequences from a zero
state (``models/transformer.apply``).  All but that kernel is XLA.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .ssm import head_columns, heads_per_step, update_rows_in_place

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class KDADims(NamedTuple):
    """The mixer's sizes (``TransformerConfig.kda_dims``)."""
    heads: int
    key_dim: int
    value_dim: int
    conv: int
    chunk: int
    block: int          # rows whose decays one reference row holds
    bound: float        # the gate's lower bound (negative)

    @property
    def conv_channels(self) -> int:
        """Channels the convolution runs over: q, k and v together."""
        return self.heads * (2 * self.key_dim + self.value_dim)


def split_qkv(x, dims: KDADims, eps: float):
    """[..., conv_channels] after the convolution → q, k ``[..., H, K]``
    (L2-normed over K, q times ``K^-0.5``) and v ``[..., H, V]``, f32."""
    H, K, V = dims.heads, dims.key_dim, dims.value_dim
    lead = x.shape[:-1]
    x = x.astype(F32)
    q = x[..., :H * K].reshape(lead + (H, K))
    k = x[..., H * K:2 * H * K].reshape(lead + (H, K))
    v = x[..., 2 * H * K:].reshape(lead + (H, V))

    def l2(t):
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + eps)

    return l2(q) * (K ** -0.5), l2(k), v


def gates(a_raw, b_raw, mp, dims: KDADims):
    """a_raw ``[..., H * K]`` (``h W_f``), b_raw ``[..., H]`` (``h W_b``)
    → (g ``[..., H, K]`` float32 in ``(bound, 0)``, beta ``[..., H]``)."""
    H, K = dims.heads, dims.key_dim
    a = (a_raw.astype(F32) + mp["dt_bias"].astype(F32)).reshape(
        a_raw.shape[:-1] + (H, K))
    g = dims.bound * jax.nn.sigmoid(
        jnp.exp(mp["A_log"].astype(F32))[:, None] * a)
    return g, jax.nn.sigmoid(b_raw.astype(F32))


def gated_norm(o, gate_raw, scale, eps: float):
    """RMSNorm over each head's values with one learned ``[V]`` scale,
    times ``sigmoid(h W_g)`` element-wise.  o: [..., H, V] float32;
    gate_raw: [..., H * V] → [..., H * V] float32."""
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)
    return o.reshape(gate_raw.shape) * jax.nn.sigmoid(gate_raw.astype(F32))


def state_update(state, q, k, v, g, beta, active, replay, fresh):
    """One token for every slot, dense over the pool.

    state: [S, H, K, V] stored type; q, k, g: [S, H, K]; v: [S, H, V];
    beta: [S, H]; active: [S], the slot holds a one-token run this step;
    replay: [S], that row was computed before (its state is already in
    the slot: read it, do not advance it); fresh: [S], the run starts at
    position 0.  → (o [S, H, V] float32, new state in the stored type)."""
    s32 = jnp.where(fresh[:, None, None, None], 0.0, state.astype(F32))
    alpha = jnp.exp(g)
    # both reads of the OLD state in one pass: what the decayed state
    # holds for k (the delta rule's prediction) and for q
    pred = jnp.einsum("shkv,shk->shv", s32, alpha * k, precision=HI)
    read = jnp.einsum("shkv,shk->shv", s32, alpha * q, precision=HI)
    delta = beta[..., None] * (v - pred)
    o = read + (k * q).sum(-1, keepdims=True) * delta
    # a replayed row's state is S_t already: o_t = S_t^T q_t
    o = jnp.where(replay[:, None, None],
                  jnp.einsum("shkv,shk->shv", s32, q, precision=HI), o)
    new = alpha[..., None] * s32 + k[..., None] * delta[..., None, :]
    advance = active & ~replay
    return o, jnp.where(advance[:, None, None, None],
                        new.astype(state.dtype), state)


def _kda_head(h, old, ins, outs, *, hb: int):
    """The delta rule's head ``h`` of a block: ``old [K, V]`` float32 →
    ``Diag(alpha) old + k (outer) delta``, both reads of the old state
    (the prediction for ``k``, the output's for ``q``) up the tile's
    rows, and the output into row ``h`` of the block's."""
    cols, rows = ins
    ak, aq, alpha, k = (cols[:, i * hb + h:i * hb + h + 1] for i in range(4))
    v, beta, kq = (rows[i, h:h + 1, :] for i in range(3))
    delta = beta * (v - jnp.sum(old * ak, axis=0, keepdims=True))
    outs[0][h:h + 1, :] = jnp.sum(old * aq, axis=0, keepdims=True) \
        + kq * delta
    return alpha * old + k * delta


def state_update_in_place(stack, li, q, k, v, g, beta, active, replay, fresh,
                          hb=None):
    """``state_update`` of layer ``li``'s rows ``stack[li, :S]`` by the
    Pallas kernel, in place on ``stack [L, S+1, H, K, V]``: a row is
    read once and written once (XLA's reads it for the two products,
    then again for the update).  The other arguments as
    ``state_update``'s.  → (o [S, H, V] float32, the stack)."""
    S, H, V = v.shape
    K = k.shape[-1]
    hb = hb or heads_per_step(stack)
    alpha = jnp.exp(g)
    on = replay[:, None, None]
    # a replayed row's state is S_t already: o_t = S_t^T q_t, which is
    # the read for q with no decay on it and no delta behind it
    cols = head_columns(
        [alpha * k, jnp.where(on, q, alpha * q), alpha, k], hb)
    kq = jnp.where(on, 0.0, (k * q).sum(-1, keepdims=True))
    rows = jnp.stack([v, jnp.broadcast_to(beta[..., None], v.shape),
                      jnp.broadcast_to(kq, v.shape)], 1)       # [S, 3, H, V]
    stack, o = update_rows_in_place(
        functools.partial(_kda_head, hb=hb), stack, li, active & ~replay,
        fresh,
        ins=[(cols, (None, None, K, 4 * hb), lambda s, j: (s, j, 0, 0)),
             (rows, (None, 3, hb, V), lambda s, j: (s, 0, j, 0))],
        outs=[(jax.ShapeDtypeStruct((S, H, V), F32), (None, hb, V),
               lambda s, j: (s, j, 0))],
        hb=hb, name="kda_state_update")
    return o, stack


def _decayed_products(x, y, G, block: int, strict: bool):
    """``M[i, j] = sum_c x_i[c] y_j[c] exp(G_i[c] - G_j[c])`` for
    ``j <= i`` (``j < i`` with ``strict``), else 0.

    x, y, G: [..., Q, K] float32, ``G`` the running sum of the log
    decays down the chunk.  Rows are cut into blocks of ``block``; block
    ``I`` takes as reference the row before its first, ``r_I``: its own
    rows carry ``exp(G_i - r_I)`` in ``(e^(bound * block), 1]`` and the
    rows ``j`` it reads ``exp(r_I - G_j)``, at most ``e^(-bound *
    block)`` inside the block and at most 1 before it."""
    Q, K = x.shape[-2:]
    n = Q // block
    lead = x.shape[:-2]
    Gb = G.reshape(lead + (n, block, K))
    # the row before each block's first (zeros before the chunk's first)
    ref = jnp.concatenate([jnp.zeros(lead + (1, K), F32),
                           Gb[..., :-1, -1, :]], axis=-2)       # [..., n, K]
    xs = x.reshape(lead + (n, block, K)) * jnp.exp(Gb - ref[..., None, :])
    # every block reads all Q rows under its own reference; the rows
    # behind the block (it never reads them) are taken out here
    up = ref[..., :, None, :] - G[..., None, :, :]           # [..., n, Q, K]
    behind = (jnp.arange(Q)[None, :]
              >= (jnp.arange(n)[:, None] + 1) * block)[..., None]
    ys = y[..., None, :, :] * jnp.where(behind, 0.0,
                                        jnp.exp(jnp.where(behind, 0.0, up)))
    m = jnp.einsum("...nik,...njk->...nij", xs, ys, precision=HI)
    m = m.reshape(lead + (Q, Q))
    i, j = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    return jnp.where((j < i) if strict else (j <= i), m, 0.0)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a [..., Q, Q]``:
    ``a`` is nilpotent, so the inverse is the finite product
    ``(I - a)(I + a^2)(I + a^4)...`` (log2(Q) squarings, all matmuls)."""
    Q = a.shape[-1]
    eye = jnp.eye(Q, dtype=a.dtype)
    inv, p, n = eye - a, a, 1
    while 2 * n < Q:
        p = jnp.matmul(p, p, precision=HI)
        inv = jnp.matmul(inv, eye + p, precision=HI)
        n *= 2
    return inv


def chunk_rule(q, k, v, g, beta, first, init, dims: KDADims
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The chunked form over ``NC`` chunks of ``Q`` tokens.

    q, k, g: [NC, Q, H, K]; v: [NC, Q, H, V]; beta: [NC, Q, H] (float32;
    a row that is not there has ``g = 0`` and ``beta = 0``: it neither
    moves the state nor is moved by it); first: [NC] bool, the chunk
    starts from ``init[c]`` [NC, H, K, V] (float32), else from the state
    the chunk before it left.  → (o [NC, Q, H, V] float32, the state each
    chunk leaves [NC, H, K, V] float32)."""
    Q = q.shape[1]
    # heads in front of the chunk's rows: [NC, H, Q, .]
    q, k, v, g = (jnp.swapaxes(t.astype(F32), 1, 2) for t in (q, k, v, g))
    beta = jnp.swapaxes(beta.astype(F32), 1, 2)[..., None]   # [NC, H, Q, 1]
    # the running sum of log decays down a chunk, as a product with a
    # triangle of ones (a cumsum is a slow windowed reduction on the TPU)
    tri = jnp.tril(jnp.ones((Q, Q), F32))
    G = jnp.einsum("qr,chrk->chqk", tri, g, precision=HI)
    # (I + A) u = beta (v - (k exp(G)) S_0),  A_ij = beta_i M(k, k)_ij
    inv = _unit_lower_inverse(
        beta * _decayed_products(k, k, G, dims.block, strict=True))
    w_v = jnp.matmul(inv, beta * v, precision=HI)            # [NC, H, Q, V]
    w_k = jnp.matmul(inv, beta * k * jnp.exp(G), precision=HI)
    qk = _decayed_products(q, k, G, dims.block, strict=False)
    q_in = q * jnp.exp(G)                       # what row q reads of S_0
    total = G[:, :, -1]                                      # [NC, H, K]
    k_out = k * jnp.exp(total[:, :, None, :] - G)    # a row's k at the end

    def carry(prev, xs):
        f, s0, wv, wk, a, qi, tot, ko = xs
        s_in = jnp.where(f, s0, prev)                        # [H, K, V]
        u = wv - jnp.matmul(wk, s_in, precision=HI)          # [H, Q, V]
        o = jnp.matmul(qi, s_in, precision=HI) \
            + jnp.matmul(a, u, precision=HI)
        s_out = jnp.exp(tot)[..., None] * s_in + jnp.einsum(
            "hqk,hqv->hkv", ko, u, precision=HI)
        return s_out, (o, s_out)

    _, (o, left) = jax.lax.scan(
        carry, jnp.zeros(init.shape[1:], F32),
        (first, init.astype(F32), w_v, w_k, qk, q_in, total, k_out))
    return jnp.swapaxes(o, 1, 2), left


def mixer_forward(mp, u, dims: KDADims, eps: float):
    """The whole mixer over whole sequences from a zero state.
    u: [B, S, dm] → [B, S, dm], in ``u``'s type."""
    dtype = u.dtype
    Bsz, S, _ = u.shape
    H, K, V = dims.heads, dims.key_dim, dims.value_dim
    with jax.named_scope("kda_conv"):
        xc = u @ mp["w_qkv"].astype(dtype)
        W = dims.conv
        padded = jnp.pad(xc, ((0, 0), (W - 1, 0), (0, 0))).astype(F32)
        acc = 0.0
        for j in range(W):          # zeros stand before the first token
            acc = acc + mp["conv_w"][:, j].astype(F32) * padded[:, j:j + S]
        q, k, v = split_qkv(jax.nn.silu(acc).astype(dtype), dims, eps)
    with jax.named_scope("kda_gate"):
        g, beta = gates(u @ mp["w_f"].astype(dtype),
                        u @ mp["w_b"].astype(dtype), mp, dims)
    with jax.named_scope("kda_chunk"):
        Q = dims.chunk
        nc = -(-S // Q)
        pad = nc * Q - S

        def chunks(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape((Bsz * nc, Q) + t.shape[2:])

        first = (jnp.arange(Bsz * nc) % nc) == 0
        o, _ = chunk_rule(chunks(q), chunks(k), chunks(v), chunks(g),
                          chunks(beta), first,
                          jnp.zeros((Bsz * nc, H, K, V), F32), dims)
        o = o.reshape(Bsz, nc * Q, H, V)[:, :S]
    with jax.named_scope("kda_gate"):
        o = gated_norm(o, u @ mp["w_g"].astype(dtype), mp["norm"], eps)
    return o.astype(dtype) @ mp["w_o"].astype(dtype)
