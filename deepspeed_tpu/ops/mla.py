"""Latent attention (DeepSeek-V2's MLA; the query in one product, or
through a latent of its own as DeepSeek-V3's): what a served ``"mla"``
layer needs.

A token leaves ONE vector in the cache, ``[c | k_r]``: the normed
key-value latent ``c`` (``kv_rank`` values) and the rotated shared key
``k_r`` (``rope_dim`` values).  Every head's keys and values are linear
in ``c`` (``[k_n | v] = c W_kvb``), so the scores and the output can be
taken over the cached vectors themselves with ``W_kvb`` folded into the
query and the output::

    s_h(t, j) = (q_n W_kb_h^T) . c_j + q_r . k_r_j     (over all 576)
    o_h(t)    = (sum_j p_h(t, j) c_j) W_vb_h            (the first 512)

which is attention of all the query heads over one shared key of
``kv_rank + rope_dim`` and one shared value of ``kv_rank``.

* :func:`rope_interleaved`: the rotary embedding over adjacent pairs;
* :func:`latent_write` (scope ``latent_write``): a step's rows into the
  latent pool by block table;
* :func:`latent_attend` (scope ``latent_attn``): groups of query rows,
  each group of one sequence, over that sequence's cached rows read by
  block table, a few blocks at a time under an online softmax.  The
  serving forward calls it twice: the step's one-token rows, one group a
  slot, and the chunks of its longer runs;
* :func:`attention_forward`: the layer over whole sequences with keys
  and values expanded (``models/transformer.apply``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


class MLADims(NamedTuple):
    """The layer's sizes (``TransformerConfig.mla_dims``)."""
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    # the query latent's size (``q = N_q(h W_qa) W_qb``); 0: ``h W_q``
    q_rank: int = 0
    # constant multipliers on the normed query latent and on the normed
    # key-value latent (1.0 multiplies nothing)
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def row(self) -> int:
        """What a token leaves in the cache: ``[c | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5


def rope_interleaved(x, cos, sin, positions):
    """Rotate adjacent pairs ``(x0, x1), (x2, x3), ...`` of the last
    axis.  x: [T, ..., R]; cos, sin: [max, R / 2]; positions: [T]."""
    c, s = cos[positions], sin[positions]                      # [T, R/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (c.shape[-1],)
    c, s = c.reshape(shape), s.reshape(shape)
    x32 = x.astype(F32).reshape(x.shape[:-1] + (-1, 2))
    a, b = x32[..., 0], x32[..., 1]
    out = jnp.stack([a * c - b * s, b * c + a * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _widened(x, width: int):
    """``x``'s last axis filled with zeros up to ``width``: the pool's
    rows are whole vectors of 128 lanes (``KVCacheConfig.latent_row``)."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, width - x.shape[-1]),))


def latent_write(pool, rows, blk, off):
    """``rows [T, row]`` into ``pool [blocks, bs, >= row]`` at block
    ``blk`` [T] (a row that pads the step: the layer's trash block) and
    offset ``off`` [T]."""
    return pool.at[blk, off].set(
        _widened(rows.astype(pool.dtype), pool.shape[-1]))


def latent_attend(pool, q, qpos, tables, dims: MLADims, blocks: int):
    """Query groups over their sequences' cached rows.

    pool: [rows, bs, >= row] (zeros behind a row's values); q: [G, R, H,
    row] folded queries (the stored type); qpos: [G, R] i32, a query's
    position (-1: the row is not
    there); tables: [G, nb] i32, rows of ``pool`` that hold the group's
    sequence, block by block (a block the sequence does not have: any
    row, it is masked).  ``blocks``: blocks read in one pass of the loop,
    which stops behind the last block any query reads.
    → [G, R, H, kv_rank] float32 (a row that is not there: garbage)."""
    G, R, H, _ = q.shape
    q = _widened(q, pool.shape[-1])
    bs = pool.shape[1]
    span = blocks * bs
    nb = tables.shape[1]
    passes = -(-nb // blocks)
    tables = jnp.pad(tables, ((0, 0), (0, passes * blocks - nb)))
    need = (jnp.max(qpos) + span) // span          # passes that hold a key

    def one(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, j * blocks, blocks, 1)
        ctx = pool[ids].reshape(G, span, -1)                # [G, span, row]
        s = jnp.einsum("grhd,gcd->grhc", q, ctx,
                       preferred_element_type=F32) * dims.scale
        cols = j * span + jnp.arange(span)
        s = jnp.where((cols[None, None, :] <= qpos[:, :, None])[:, :, None],
                      s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        w = jnp.exp(m - m_new)
        pv = jnp.einsum("grhc,gcd->grhd", p.astype(ctx.dtype),
                        ctx[..., :dims.kv_rank], preferred_element_type=F32)
        return m_new, l * w + p.sum(-1), acc * w[..., None] + pv

    init = (jnp.full((G, R, H), -1e30, F32), jnp.zeros((G, R, H), F32),
            jnp.zeros((G, R, H, dims.kv_rank), F32))
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(need, passes), one, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _normed(x, scale, eps: float, times: float):
    """A latent's RMSNorm in float32, then its constant multiplier."""
    x = x.astype(F32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)
    return x if times == 1.0 else x * times


def w_kvb(ap, dims: MLADims):
    """``W_kvb`` as ``[kv_rank, H, nope + value]`` (a model with a query
    latent keeps it as the matrix ``[kv_rank, H * (nope + value)]``)."""
    return ap["w_kvb"].reshape(dims.kv_rank, dims.heads, -1)


def project(ap, h, cos, sin, positions, dims: MLADims, eps: float, mm):
    """A layer's projections of ``h [T, dm]`` → (q_n [T, H, nope], q_r
    [T, H, rope] rotated, the cache row ``[c | k_r]`` [T, row]: the
    latent after its norm, the shared key after its rotation).
    ``mm(x, w)``: the caller's matrix product."""
    if dims.q_rank:
        c_q = _normed(mm(h, ap["wq_a"]), ap["q_norm"], eps, dims.q_scale)
        q = mm(c_q.astype(h.dtype), ap["wq_b"]).reshape(
            h.shape[0], dims.heads, -1)
    else:
        q = mm(h, ap["wq"])                             # [T, H, nope + rope]
    q_n, q_r = q[..., :dims.nope_dim], q[..., dims.nope_dim:]
    kva = mm(h, ap["w_kva"])                                 # [T, row]
    c = _normed(kva[..., :dims.kv_rank], ap["c_norm"], eps, dims.kv_scale)
    k_r = rope_interleaved(kva[..., dims.kv_rank:], cos, sin, positions)
    q_r = rope_interleaved(q_r, cos, sin, positions)
    return q_n, q_r, jnp.concatenate([c.astype(h.dtype), k_r], axis=-1)


def fold_query(ap, q_n, q_r, dims: MLADims):
    """``[q_n W_kb^T | q_r]``: the query as it meets a cache row."""
    w_kb = w_kvb(ap, dims)[..., :dims.nope_dim].astype(q_n.dtype)  # [c,H,n]
    return jnp.concatenate(
        [jnp.einsum("thn,chn->thc", q_n, w_kb), q_r], axis=-1)


def unfold_output(ap, o, dims: MLADims, dtype):
    """``o [T, H, kv_rank]`` over the latents → the heads' values."""
    w_vb = w_kvb(ap, dims)[..., dims.nope_dim:].astype(dtype)   # [c, H, v]
    return jnp.einsum("thc,chv->thv", o.astype(dtype), w_vb)


def attention_forward(ap, h, cos, sin, dims: MLADims, eps: float):
    """The layer's attention over whole sequences, keys and values
    expanded for every token.  h: [B, S, dm] → [B, S, H, value_dim]."""
    B, S, _ = h.shape
    dt = h.dtype
    pos = jnp.tile(jnp.arange(S), B)

    def mm(x, w):
        return jnp.tensordot(x, w.astype(dt), 1)

    q_n, q_r, row = project(ap, h.reshape(B * S, -1), cos, sin, pos, dims,
                            eps, mm)
    c, k_r = row[..., :dims.kv_rank], row[..., dims.kv_rank:]
    kv = jnp.einsum("tc,chx->thx", c, w_kvb(ap, dims).astype(dt))
    k_n, v = kv[..., :dims.nope_dim], kv[..., dims.nope_dim:]

    def seqs(t):
        return t.reshape((B, S) + t.shape[1:])

    s = (jnp.einsum("bqhn,bkhn->bhqk", seqs(q_n), seqs(k_n))
         + jnp.einsum("bqhr,bkr->bhqk", seqs(q_r), seqs(k_r))
         ).astype(F32) * dims.scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhv->bqhv", p, seqs(v))
