"""Mamba-2 mixer: the three computations a served hybrid block needs.

A block of kind ``"hybrid"`` (``models/transformer.py``) holds a
Mamba-2 mixer beside its attention.  Per head ``i`` of ``H`` (group
``g = i // (H / G)``), with ``dt_t = softplus(dt_t + dt_bias)`` and
``A = -exp(A_log)``::

    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t[g]     S: [P, N]
    y_t = S_t C_t[g] + D_i x_t

in front of it a depthwise causal convolution of width ``W`` over the
``x``, ``B`` and ``C`` channels together.  What this file computes:

* :func:`conv_rows` / :func:`conv_tails` (scope ``ssm_conv``): the
  convolution over a step's flat rows, each row reaching back into its
  own run and, past the run's first row, into the sequence's carried
  tail of raw inputs, and the tails the step leaves;
* :func:`state_update` (scope ``ssm_update``): the one-token update of
  every sequence that a step advances by one token, dense over the
  state pool's slots (a slot holds at most one run a step, so nothing is
  gathered or scattered), in XLA: three passes over the rows, the read
  for ``C . S`` and the update's read and write;
* :func:`state_update_in_place` (the same scope): that update as ONE
  pass, a Pallas kernel over the stack of all layers' rows in place
  (:func:`update_rows_in_place`, which ``ops/kda.py``'s kernel shares);
  the serving forward takes it on a TPU where the rows lie on one
  device, and XLA's elsewhere;
* :func:`chunk_scan` (scope ``ssm_scan``): the chunked form (SSD) over a
  list of chunks of ``Q`` tokens, each of one run; a chunk continues the
  one before it or starts from a given state; in XLA, the decay tile of
  every chunk an array in memory;
* :func:`chunk_scan_in_place` (the same scope): the chunked form over
  the chunks a step's table holds as ONE Pallas kernel: a run's rows by
  its own DMAs, the decay tile in VMEM, the run's state read from and
  left in its slot's row of the stack, a chunk that is not there a grid
  step that does nothing; the serving forward takes it where it takes
  :func:`state_update_in_place`.

``mixer_forward`` is the whole mixer over whole sequences from a zero
state (``models/transformer.apply``); the serving forward
(``inference/model.py``) composes the three itself around the engine's
state pool.  The convolution is XLA: see PERF.md section 6 (PR 42,
PR 46, PR 55) for what the chip said.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _start, _wait

F32 = jnp.float32


class SSMDims(NamedTuple):
    """The mixer's sizes (``TransformerConfig.ssm_dims``)."""
    d_ssm: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int
    chunk: int

    @property
    def conv_channels(self) -> int:
        """Channels the convolution runs over: x, B and C together."""
        return self.d_ssm + 2 * self.groups * self.state

    @property
    def in_proj(self) -> int:
        """Columns of ``w_in``: z, xBC, dt in that order."""
        return self.d_ssm + self.conv_channels + self.heads


def split_in_proj(p, dims: SSMDims, col_scales):
    """``p``: [..., in_proj] → (z, xBC, dt).  ``col_scales``: the five
    multipliers over the columns of z, x, B, C, dt."""
    p = p * column_scales(dims, col_scales).astype(p.dtype)
    d, c = dims.d_ssm, dims.conv_channels
    return p[..., :d], p[..., d:d + c], p[..., d + c:]


def column_scales(dims: SSMDims, col_scales) -> jnp.ndarray:
    """The constant vector over ``w_in``'s columns that holds the five
    multipliers (z, x, B, C, dt)."""
    gn = dims.groups * dims.state
    widths = (dims.d_ssm, dims.d_ssm, gn, gn, dims.heads)
    return jnp.concatenate([jnp.full((w,), s, F32)
                            for w, s in zip(widths, col_scales)])


def split_xbc(xbc, dims: SSMDims):
    """[..., conv_channels] → x [..., H, P], B, C [..., G, N]."""
    d, gn = dims.d_ssm, dims.groups * dims.state
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(lead + (dims.heads, dims.head_dim))
    b = xbc[..., d:d + gn].reshape(lead + (dims.groups, dims.state))
    c = xbc[..., d + gn:].reshape(lead + (dims.groups, dims.state))
    return x, b, c


def _grouped(t, dims: SSMDims, axis: int):
    """Split the head axis ``axis`` of ``t`` into (group, head of the
    group): head i reads B and C of group i // (H / G), so with the
    heads laid out by group an einsum shares them without a copy."""
    axis %= t.ndim
    return t.reshape(t.shape[:axis]
                     + (dims.groups, dims.heads // dims.groups)
                     + t.shape[axis + 1:])


def _taps(rows, first, offset, width: int):
    """For each of a convolution's ``width`` taps, oldest first: how far
    back it reaches, whether that lies inside the row's run, and else
    where in the slot's tail it lies.  rows, first, offset: [R] (a
    row's flat index, its run's first row, where in the tail the input
    before the run sits)."""
    for j in range(width):
        back = width - 1 - j
        src = rows - back
        # the input ``back`` tokens back lies (first - src) rows before
        # the run: that many entries before ``offset`` in the tail
        yield j, back, src >= first, jnp.clip(
            offset - (first - src) + 1, 0, width - 1)


def conv_rows(xbc, tail, seq_slot, first, offset, fresh, conv_w, conv_b):
    """The causal convolution over a step's flat rows: each row reaches
    back into its own run and, before the run's first row, into its
    slot's tail.

    xbc: [T, C] raw inputs; tail: [S, W, C], a slot's last ``W`` raw
    inputs (the last entry the newest); seq_slot: [T]; first: [T], the
    flat index of the first row of the row's run; offset: [T], where in
    the tail the input one before the run's first row sits (``W - 1``,
    or ``W - 2`` for a replayed row, whose own input is the tail's
    newest); fresh: [T], the run starts at position 0 (what lies before
    it is zeros whatever the slot held).
    → silu(b + sum_j w[:, j] * input W-1-j tokens back) [T, C] float32."""
    T, W = xbc.shape[0], tail.shape[1]
    acc = jnp.broadcast_to(conv_b.astype(F32), xbc.shape)
    # ONE gather of each row's whole tail; a tap then chooses among its
    # W entries with selects (a gather a tap was most of this scope)
    mine = jnp.where(fresh[:, None, None], 0, tail[seq_slot])   # [T, W, C]
    for j, back, inside, at in _taps(jnp.arange(T), first, offset, W):
        reach = mine[:, 0]
        for i in range(1, W):
            reach = jnp.where((at == i)[:, None], mine[:, i], reach)
        own = jnp.pad(xbc, ((back, 0), (0, 0)))[:T]      # the row ``back`` up
        acc = acc + conv_w[:, j].astype(F32) \
            * jnp.where(inside[:, None], own, reach).astype(F32)
    return jax.nn.silu(acc)


def conv_tails(xbc, tail, last, first, offset, fresh, has_run):
    """The tails the step leaves: for a slot with a run, the ``W`` raw
    inputs that end at the run's last row ``last`` [S] (out of the run
    and, where it is shorter than ``W``, out of the old tail); the other
    slots keep theirs.  first, offset, fresh, has_run: [S], as
    ``conv_rows``' per row.  → [S, W, C]."""
    S, W = tail.shape[:2]
    slots = jnp.arange(S)
    out = []
    for j, back, inside, at in _taps(last, first, offset, W):
        reach = jnp.where(fresh[:, None], 0, tail[slots, at])
        out.append(jnp.where(inside[:, None],
                             xbc[jnp.maximum(last - back, 0)], reach))
    return jnp.where(has_run[:, None, None],
                     jnp.stack(out, axis=1).astype(tail.dtype), tail)


def state_update(state, x, b, c, dt, a, d_skip, active, replay, fresh,
                 dims: SSMDims):
    """One token for every slot, dense over the pool.

    state: [S, H, P, N] stored type; x: [S, H, P]; b, c: [S, G, N];
    dt: [S, H] (after softplus); a: [H] (negative); active: [S], the
    slot holds a one-token run this step; replay: [S], that row was
    computed before (its state is already in the slot: read it, do not
    advance it); fresh: [S], the run starts at position 0.
    → (y [S, H, P] float32, new state in the stored type)."""
    s32 = _grouped(state.astype(F32), dims, 1)            # [S, G, K, P, N]
    s32 = jnp.where(fresh[:, None, None, None, None], 0.0, s32)
    b32, c32 = b.astype(F32), c.astype(F32)
    x32 = _grouped(x.astype(F32), dims, 1)                  # [S, G, K, P]
    dt = _grouped(dt.astype(F32), dims, 1)                  # [S, G, K]
    decay = jnp.exp(dt * _grouped(a, dims, 0))
    dtx = dt[..., None] * x32
    # y off the OLD state: y_t = C.S_t = decay (C.S_{t-1}) + dt x (B.C)
    read = jnp.einsum("sgkpn,sgn->sgkp", s32, c32)
    step = decay[..., None] * read \
        + dtx * jnp.sum(b32 * c32, -1)[:, :, None, None]
    y = jnp.where(replay[:, None, None, None], read, step) \
        + _grouped(d_skip.astype(F32), dims, 0)[..., None] * x32
    new = decay[..., None, None] * s32 \
        + dtx[..., None] * b32[:, :, None, None, :]
    advance = active & ~replay
    out = jnp.where(advance[:, None, None, None],
                    new.reshape(state.shape).astype(state.dtype), state)
    return y.reshape(x.shape), out


# ------------------------------------------------- the update, a kernel
# What a grid step of the update kernels holds of a slot's state: its
# heads' tiles, in and out and each double-buffered, so four times this
STATE_TILE_BYTES = 2 * 1024 * 1024


def heads_per_step(stack) -> int:
    """Heads of a slot's state ``stack [L, S+1, H, ., .]`` that one grid
    step of an update kernel takes: all of them where they fit
    ``STATE_TILE_BYTES``, else the largest halving of them that does and
    still fills the rows' sublanes (a multiple of 8)."""
    H = stack.shape[2]
    head = stack.shape[3] * stack.shape[4] * stack.dtype.itemsize
    while H * head > STATE_TILE_BYTES and H % 16 == 0:
        H //= 2
    return H


def head_columns(vectors, hb: int):
    """``vectors``: a list of ``[S, H, A]`` float32 → ``[S, H / hb, A,
    len * hb]``: for a block of ``hb`` heads the vectors as COLUMNS,
    vector ``i`` of the block's head ``h`` in column ``i * hb + h``.  A
    kernel multiplies a state tile ``[A, B]`` down its rows by a column
    ``[A, 1]`` it cuts out with a static lane index; the transposes are
    XLA's, over vectors a state's ``1 / B`` in size."""
    S, H, A = vectors[0].shape
    t = jnp.stack(vectors, 1).reshape(S, len(vectors), H // hb, hb, A)
    return t.transpose(0, 2, 4, 1, 3).reshape(S, H // hb, A, -1)


def _rows_kernel(li, advance, fresh, tile, *refs, head, n_in: int, hb: int):
    """One slot's block of ``hb`` heads: every head's tile loaded once in
    the stored type, advanced in float32 by ``head(h, old, ins, outs) →
    new`` (which takes its reads of the old state from the same tile and
    leaves them in ``outs``), stored once."""
    s = pl.program_id(0)
    ins, (out, *outs) = refs[:n_in], refs[n_in:]
    zero, move = fresh[s] != 0, advance[s] != 0
    for h in range(hb):
        kept = tile[h]
        new = head(h, jnp.where(zero, 0.0, kept.astype(F32)), ins, outs)
        # a slot that is not advanced keeps its bits
        out[h] = jnp.where(move, new.astype(out.dtype), kept)


def update_rows_in_place(head, stack, li, advance, fresh, ins, outs, *,
                         hb: int, name: str):
    """The one-token update of layer ``li``'s state rows as ONE pass
    over them, in place on the stack: what ``ops/ssm.py`` and
    ``ops/kda.py``'s kernels share.

    stack: ``[L, S+1, H, A, B]`` in the stored type, aliased to the
    first result: the grid walks the ``S`` slots of layer ``li`` (a
    scalar, prefetched into the block index map) and each slot's blocks
    of ``hb`` heads, so the trash row ``S`` and every other layer are
    never visited and keep what they hold.  advance, fresh: ``[S]``, the
    slot's row is advanced (else it is stored back as it was loaded),
    and starts from zeros.  ins: ``(array [S, ...], block, index map
    (s, j) → block index)`` per input; outs likewise with a
    ``ShapeDtypeStruct``; a block's ``None`` dimensions are squeezed.
    ``head``: see ``_rows_kernel``.  → (stack, *outs)."""
    H, A, B = stack.shape[2:]
    S = advance.shape[0]

    def spec(block, index):
        return pl.BlockSpec(block, lambda s, j, *_: index(s, j))

    tile = pl.BlockSpec((None, None, hb, A, B),
                        lambda s, j, li, *_: (li[0], s, j, 0, 0))
    scalars = (jnp.asarray(li, jnp.int32).reshape(1),
               advance.astype(jnp.int32), fresh.astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_rows_kernel, head=head, n_in=len(ins), hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S, H // hb),
            in_specs=[tile] + [spec(b, i) for _, b, i in ins],
            out_specs=[tile] + [spec(b, i) for _, b, i in outs],
        ),
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype)]
        + [o for o, _, _ in outs],
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name=name,
    )(*scalars, stack, *[a for a, _, _ in ins])


def _ssm_head(h, old, ins, outs, *, per_group: int):
    """Mamba-2's head ``h`` of a block: ``old [P, N]`` float32 →
    ``decay * old + (dt x) (outer) B``, and ``old . C`` down the state's
    lanes into column ``h`` of the block's reads."""
    dtx, decay, bc = ins
    g = h // per_group
    outs[0][:, h:h + 1] = jnp.sum(old * bc[1, g], axis=1, keepdims=True)
    return decay[h:h + 1, :] * old + dtx[:, h:h + 1] * bc[0, g]


def state_update_in_place(stack, li, x, b, c, dt, a, d_skip, active, replay,
                          fresh, dims: SSMDims, hb=None):
    """``state_update`` of layer ``li``'s rows ``stack[li, :S]`` by the
    Pallas kernel, in place on ``stack [L, S+1, H, P, N]``: a row is
    read once and written once (XLA's reads it for ``C . S``, then again
    for the update).  The other arguments as ``state_update``'s.
    → (y [S, H, P] float32, the stack)."""
    S, H, P = x.shape
    N, per_group = dims.state, dims.heads // dims.groups
    hb = hb or heads_per_step(stack)
    dt, x32 = dt.astype(F32), x.astype(F32)
    b32, c32 = b.astype(F32), c.astype(F32)
    decay = jnp.exp(dt * a)                                     # [S, H]
    dtx = dt[..., None] * x32                                   # [S, H, P]
    # a block's groups: whole ones, or the one its heads lie in
    gb = max(1, hb // per_group)
    stack, read = update_rows_in_place(
        functools.partial(_ssm_head, per_group=per_group),
        stack, li, active & ~replay, fresh,
        ins=[(head_columns([dtx], hb), (None, None, P, hb),
              lambda s, j: (s, j, 0, 0)),
             (jnp.broadcast_to(decay[..., None], (S, H, N)), (None, hb, N),
              lambda s, j: (s, j, 0)),
             (jnp.stack([b32, c32], 1)[:, :, :, None], (None, 2, gb, 1, N),
              lambda s, j: (s, 0, j * hb // (per_group * gb), 0, 0))],
        outs=[(jax.ShapeDtypeStruct((S, H // hb, P, hb), F32),
               (None, None, P, hb), lambda s, j: (s, j, 0, 0))],
        hb=hb, name="ssm_state_update")
    read = read.transpose(0, 1, 3, 2).reshape(S, H, P)
    # y off the OLD state, as ``state_update``
    bc = jnp.repeat(jnp.sum(b32 * c32, -1), per_group, axis=1)   # [S, H]
    step = decay[..., None] * read + dtx * bc[..., None]
    y = jnp.where(replay[:, None, None], read, step) \
        + d_skip.astype(F32)[:, None] * x32
    return y, stack


def chunk_scan(x, b, c, dt, a, d_skip, first, init, dims: SSMDims
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The chunked form over ``NC`` chunks of ``Q`` tokens.

    x: [NC, Q, H, P]; b, c: [NC, Q, G, N]; dt: [NC, Q, H] (after
    softplus; 0 marks a row that is not there: it neither moves the
    state nor is moved by it); a: [H]; first: [NC] bool, the chunk
    starts from ``init[c]`` [NC, H, P, N] (float32), else from the state
    the chunk before it left.  → (y [NC, Q, H, P] float32, the state
    each chunk leaves [NC, H, P, N] float32)."""
    NC, Q = x.shape[:2]
    x32 = _grouped(x.astype(F32), dims, 2)                  # [NC, Q, G, K, P]
    b32, c32 = b.astype(F32), c.astype(F32)                 # [NC, Q, G, N]
    dt = _grouped(dt.astype(F32), dims, 2)                  # [NC, Q, G, K]
    # the running sum of log decays down a chunk, as a product with a
    # triangle of ones (a cumsum is a slow windowed reduction on the
    # TPU); in full float32: exp() of it is what every row is scaled by
    tri = jnp.tril(jnp.ones((Q, Q), F32))
    la = jnp.einsum("qr,crgk->cqgk", tri, dt * _grouped(a, dims, 0),
                    precision=jax.lax.Precision.HIGHEST)
    dtx = dt[..., None] * x32
    # inside a chunk: row q reads row r <= q under exp(la_q - la_r)
    diff = la[:, :, None] - la[:, None, :]                  # [NC, Q, R, G, K]
    keep = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[
        None, :, :, None, None]
    decay = jnp.exp(jnp.where(keep, diff, -jnp.inf))
    scores = jnp.einsum("cqgn,crgn->cqrg", c32, b32)[..., None] * decay
    y = jnp.einsum("cqrgk,crgkp->cqgkp", scores, dtx)
    # what a chunk's own rows leave at its end
    to_end = jnp.exp(la[:, -1:] - la)                       # [NC, Q, G, K]
    local = jnp.einsum("cqgkp,cqgn->cgkpn", dtx * to_end[..., None], b32)
    total = jnp.exp(la[:, -1])                              # [NC, G, K]
    into = jnp.exp(la)                                      # [NC, Q, G, K]

    def carry(prev, xs):
        f, s0, loc, tot, c_g, dec = xs
        s_in = jnp.where(f, s0, prev)
        off = jnp.einsum("qgn,gkpn->qgkp", c_g, s_in) * dec[..., None]
        s_out = tot[..., None, None] * s_in + loc
        return s_out, (off, s_out)

    init = _grouped(init.astype(F32), dims, 1)
    _, (off, left) = jax.lax.scan(
        carry, jnp.zeros(init.shape[1:], F32),
        (first, init, local, total, c32, into))
    y = y + off + _grouped(d_skip.astype(F32), dims, 0)[..., None] * x32
    return (y.reshape(x.shape),
            left.reshape((NC, dims.heads) + left.shape[3:]))


# ------------------------------------------ the chunked form, a kernel
def slab_heads(dims: SSMDims) -> int:
    """Heads the chunk kernel takes side by side on a slab's lanes: as
    many as fill 128 where a head is narrower, halved until a group's
    heads divide so (a slab's heads share their B and C)."""
    side = 128 // dims.head_dim if 128 % dims.head_dim == 0 else 1
    while (dims.heads // dims.groups) % side:
        side //= 2
    return side


def _column(t, h):
    """Column ``h`` (traced) of ``t [F, lanes]`` → ``[F, 1]``: the
    lanes rolled until it is the first (a dynamic lane index is not a
    thing a load can take)."""
    lanes = t.shape[1]
    return pltpu.roll(t, jax.lax.rem(lanes - h, jnp.int32(lanes)), 1)[:, :1]


def _chunk_kernel(tab, li, fresh, a_ref, dt_hbm, xbc_hbm, stack_in,
                  stack_out, y_hbm, dbuf, xbuf, bbuf, cbuf, sbuf, s_ref, ybuf,
                  la_ref, lat_ref, cb_ref, at, sem, *, dims: SSMDims,
                  side: int):
    """Chunk ``c`` of the step's table for a block of heads: the rows of
    its run that lie in the frames (aligned windows of ``F`` rows) it is
    the first of the run to touch, a frame at a time, as ``chunk_scan``
    computes a chunk whose other rows have ``dt = 0``.

    tab: ``[NC * 5]`` (``RecBatch.chunks``), li: ``[1]``, fresh:
    ``[NC]``, prefetched.  ``*_hbm``: the step's rows where XLA left
    them: dt ``[T, lanes]`` (the heads on whole lane tiles, zeros past
    them) and the convolution's ``[T, x | B | C]``; the
    stack ``[L, S+1, slabs, side * P, N]`` in and (the same buffer) out;
    y ``[T, H * P]`` float32, of which only the frames that hold a run's
    rows are written.  A slab: ``side`` heads side by side on the lanes.
    ``at``: SMEM, the run's first row and the frames the input and the
    output buffers hold."""
    jb, c = pl.program_id(0), pl.program_id(1)
    sl, F, LW = xbuf.shape
    H, P, G, N = dims.heads, dims.head_dim, dims.groups, dims.state
    per_group = H // G
    start, n, slot = tab[5 * c], tab[5 * c + 1], tab[5 * c + 2]
    first, last = tab[5 * c + 3] != 0, tab[5 * c + 4] != 0
    blk = pl.ds(jb * sl, sl)
    f32 = lambda t: t.astype(F32)

    def frame_of(f):
        return pl.ds(pl.multiple_of(f * F, F), F)

    def lanes_of(j):
        """Slab ``j`` of this block of heads among a row's channels."""
        return pl.ds(pl.multiple_of((jb * sl + j) * LW, LW), LW)

    def each_slab(do, copy):
        jax.lax.fori_loop(0, sl, lambda j, _: do(copy(j)), None)

    def inputs(do, f):
        """``do`` (start or wait) the copies of frame ``f``'s rows: dt,
        B and C by group, x a slab at a time (the relayout is the DMA's:
        a slab is then an index, not a lane offset)."""
        rows = frame_of(f)
        do(pltpu.make_async_copy(dt_hbm.at[rows], dbuf, sem.at[0]))
        for g in range(G):
            for k, buf in enumerate((bbuf, cbuf)):
                do(pltpu.make_async_copy(
                    xbc_hbm.at[rows, pl.ds(H * P + (k * G + g) * N, N)],
                    buf.at[g], sem.at[0]))
        each_slab(do, lambda j: pltpu.make_async_copy(
            xbc_hbm.at[rows, lanes_of(j)], xbuf.at[j], sem.at[0]))

    def flush():
        """The output frame the buffer holds, to where it lies."""
        for do in (_start, _wait):
            each_slab(do, lambda j: pltpu.make_async_copy(
                ybuf.at[j], y_hbm.at[frame_of(at[2]), lanes_of(j)],
                sem.at[1]))

    @pl.when(c == 0)
    def _():
        at[1] = -1
        at[2] = -1

    def piece(f, lo, hi):
        """The run's rows ``lo .. hi`` of frame ``f`` (counted from the
        frame's first): the state in ``s_ref`` read, advanced, left."""
        @pl.when(at[1] != f)
        def _():
            inputs(_start, f)
            inputs(_wait, f)
            at[1] = f

        @pl.when(at[2] != f)
        def _():
            pl.when(at[2] >= 0)(flush)
            at[2] = f

        q = jax.lax.broadcasted_iota(jnp.int32, (F, 1), 0)
        there = (q >= lo) & (q < hi)                            # [F, 1]
        causal = q >= jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
        # the running log decay down the frame, as ``chunk_scan`` takes
        # it: a product with a triangle of ones in full float32
        la = jnp.dot(causal.astype(F32),
                     jnp.where(there, dbuf[...], 0.0) * a_ref[...],
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=F32)               # [F, lanes]
        la_ref[...] = la
        lat_ref[...] = la.T
        for g in range(G):      # C B^T once a group: its heads share it
            cb_ref[g] = jax.lax.dot_general(
                cbuf[g], bbuf[g], (((1,), (1,)), ((), ())),
                preferred_element_type=F32)

        def slab(j, _):
            h0 = (jb * sl + j) * side
            g = jax.lax.div(h0, jnp.int32(per_group))
            xs, s_in = f32(xbuf[j]), s_ref[j]                  # [F, LW]
            # what the state the frame starts from adds to every row
            off = jax.lax.dot_general(
                f32(cbuf[g]), s_in, (((1,), (1,)), ((), ())),
                preferred_element_type=F32)                     # [F, LW]
            ys, ws, tots = [], [], []
            for i in range(side):
                col = _column(la_ref[...], h0 + i)              # [F, 1]
                row = lat_ref[pl.ds(h0 + i, 1), :]              # [1, F]
                # the frame's last, down a head's rows of the state
                # (spread over the sublanes before the roll: a [1, 1]
                # does not broadcast both ways)
                ends = _column(jnp.broadcast_to(
                    la_ref[F - 1:, :], (P, la_ref.shape[1])), h0 + i)
                dtx = jnp.where(there, xs[:, i * P:(i + 1) * P]
                                * _column(dbuf[...], h0 + i), 0.0)   # [F, P]
                # inside the frame: row q reads row r <= q under
                # exp(la_q - la_r), a tile that never leaves VMEM
                decay = jnp.exp(jnp.where(causal, col - row, -jnp.inf))
                ys.append(jnp.dot(cb_ref[g] * decay, dtx,
                                  preferred_element_type=F32)
                          + off[:, i * P:(i + 1) * P] * jnp.exp(col))
                ws.append(dtx * jnp.exp(ends[:1] - col))
                tots.append(jnp.exp(ends))
            cat = (lambda p, axis: p[0] if side == 1
                   else jnp.concatenate(p, axis=axis))
            # what the frame's own rows leave at its end
            local = jnp.dot(cat(ws, 1).T, f32(bbuf[g]),
                            preferred_element_type=F32)         # [LW, N]
            s_ref[j] = cat(tots, 0) * s_in + local
            # (a row of another run, or of none, keeps what it holds)
            ybuf[j] = jnp.where(there, cat(ys, 1), ybuf[j])

        jax.lax.fori_loop(0, sl, slab, None)

    @pl.when(n > 0)
    def _():
        @pl.when(first)
        def _():
            at[0] = start
            cp = pltpu.make_async_copy(stack_in.at[li[0], slot, blk], sbuf,
                                       sem.at[2])
            cp.start()
            cp.wait()
            zero = fresh[c] != 0        # whatever the slot held

            def take(j, _):
                s_ref[j] = jnp.where(zero, 0.0, f32(sbuf[j]))

            jax.lax.fori_loop(0, sl, take, None)

        # the run ends where its last chunk does
        e = jax.lax.while_loop(lambda i: tab[5 * i + 4] == 0,
                               lambda i: i + 1, c)
        run = (at[0], tab[5 * e] + tab[5 * e + 1])
        f0 = jax.lax.div(start, jnp.int32(F))
        f1 = jax.lax.div(start + n - 1, jnp.int32(F))

        def frame(k, _):
            # the chunk before this one took the frame it ended in
            new = jax.lax.select(
                k == 0, first | (jax.lax.rem(start, jnp.int32(F)) == 0),
                f1 != f0)
            f = f0 + k
            pl.when(new)(lambda: piece(
                f, jax.lax.max(run[0] - f * F, jnp.int32(0)),
                jax.lax.min(run[1] - f * F, jnp.int32(F))))

        jax.lax.fori_loop(0, 2, frame, None)

        @pl.when(last)
        def _():
            def give(j, _):
                sbuf[j] = s_ref[j].astype(sbuf.dtype)   # rounded once

            jax.lax.fori_loop(0, sl, give, None)
            cp = pltpu.make_async_copy(
                sbuf, stack_out.at[li[0], slot, blk], sem.at[2])
            cp.start()
            cp.wait()

    @pl.when((c == pl.num_programs(1) - 1) & (at[2] >= 0))
    def _():
        flush()


def chunk_scan_in_place(stack, li, xbc, dt, a, d_skip, chunks, fresh,
                        dims: SSMDims, hb=None):
    """The chunked form over the chunks a step holds as ONE Pallas
    kernel, a run's state read from and left in its slot's row of layer
    ``li`` of ``stack [L, S+1, H, P, N]`` in place.

    xbc: [T, conv_channels], the convolution's x, B and C of the step's
    flat rows as it leaves them (``split_xbc``); dt: [T, H] (after
    softplus); chunks: [NC, 5] (``RecBatch.chunks``: first row, rows,
    slot, first of its run, last of its run); fresh: [NC], the chunk's
    run starts from zeros whatever its slot holds.  A chunk of no rows
    costs a grid step that does nothing: no DMA, no product.  The
    mathematics are ``chunk_scan``'s term for term; no array of ``Q x Q
    x heads`` exists outside VMEM, and XLA lays nothing out for the
    kernel but a ``dt`` of fewer heads than a lane tile, padded to one.
    → (y [T, H, P] float32, zeros in the rows no chunk holds; the
    stack)."""
    T, (H, P) = xbc.shape[0], (dims.heads, dims.head_dim)
    G, N = dims.groups, dims.state
    side = slab_heads(dims)
    hb = hb or heads_per_step(stack)
    LW, sl = side * P, hb // side
    F = min(dims.chunk, T)
    lanes = -(-H // 128) * 128
    pad = -T % F    # (no rung of a served step: a frame divides those)
    if pad:
        xbc = jnp.pad(xbc, ((0, pad), (0, 0)))
    # (a copy's rows are whole lane tiles: Falcon-H1's 32 heads are
    # padded to 128, granite's 128 are as they are)
    if pad or lanes - H:
        dt = jnp.pad(dt, ((0, pad), (0, lanes - H)))
    scalars = (chunks.astype(jnp.int32).reshape(-1),
               jnp.asarray(li, jnp.int32).reshape(1),
               fresh.astype(jnp.int32))
    slabs = stack.reshape(stack.shape[:2] + (H // side, LW, N))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    slabs, y = pl.pallas_call(
        functools.partial(_chunk_kernel, dims=dims, side=side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(H // hb, chunks.shape[0]),
            in_specs=[pl.BlockSpec((1, lanes), lambda *_: (0, 0))]
            + [hbm] * 3,
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((F, lanes), F32),             # dt
                pltpu.VMEM((sl, F, LW), xbc.dtype),      # x by slab
                pltpu.VMEM((G, F, N), xbc.dtype),        # B
                pltpu.VMEM((G, F, N), xbc.dtype),        # C
                pltpu.VMEM((sl, LW, N), stack.dtype),    # a row, as stored
                pltpu.VMEM((sl, LW, N), F32),            # the run's state
                pltpu.VMEM((sl, F, LW), F32),            # y by slab
                pltpu.VMEM((F, lanes), F32),             # la, and across
                pltpu.VMEM((lanes, F), F32),
                pltpu.VMEM((G, F, F), F32),              # C B^T
                pltpu.SMEM((3,), jnp.int32),
                pltpu.SemaphoreType.DMA((3,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(slabs.shape, slabs.dtype),
                   jax.ShapeDtypeStruct((T + pad, H * P), F32)],
        input_output_aliases={len(scalars) + 3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name="ssm_chunk_scan",
    )(*scalars, jnp.pad(a.astype(F32), (0, lanes - H))[None],
      dt.astype(F32), xbc, slabs)
    start, n = chunks[:, 0], chunks[:, 1]
    r = jnp.arange(T)[:, None]
    held = ((r >= start) & (r < start + n)).any(1)[:, None, None]
    y = y[:T].reshape(T, H, P) + d_skip.astype(F32)[:, None] \
        * xbc[:T, :H * P].reshape(T, H, P).astype(F32)
    return jnp.where(held, y, 0.0), slabs.reshape(stack.shape)


def gated_norm(y, z, scale, dims: SSMDims, eps: float):
    """``y * silu(z)``, then an RMSNorm over each group's channels
    apart with one learned [d_ssm] scale.  y, z: [..., d_ssm]."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    lead = g.shape[:-1]
    g = g.reshape(lead + (dims.groups, dims.d_ssm // dims.groups))
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(lead + (dims.d_ssm,)) * scale.astype(F32)


def discretise(dt_raw, mp):
    """(dt [..., H] after softplus, A [H] negative) in float32."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_bias"].astype(F32))
    return dt, -jnp.exp(mp["A_log"].astype(F32))


def mixer_forward(mp, u, dims: SSMDims, col_scales, eps: float):
    """The whole mixer over whole sequences from a zero state.
    u: [B, S, dm] → [B, S, dm], in ``u``'s type."""
    dtype = u.dtype
    Bsz, S, _ = u.shape
    with jax.named_scope("ssm_in"):
        z, xbc, dt_raw = split_in_proj(u @ mp["w_in"].astype(dtype), dims,
                                       col_scales)
    with jax.named_scope("ssm_conv"):
        W = dims.conv
        padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0))).astype(F32)
        acc = mp["conv_b"].astype(F32)
        for j in range(W):          # zeros stand before the first token
            acc = acc + mp["conv_w"][:, j].astype(F32) * padded[:, j:j + S]
        x, b, c = split_xbc(jax.nn.silu(acc).astype(dtype), dims)
    with jax.named_scope("ssm_scan"):
        dt, a = discretise(dt_raw, mp)
        Q = dims.chunk
        nc = -(-S // Q)
        pad = nc * Q - S

        def chunks(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape((Bsz * nc, Q) + t.shape[2:])

        first = (jnp.arange(Bsz * nc) % nc) == 0
        init = jnp.zeros((Bsz * nc, dims.heads, dims.head_dim, dims.state),
                         F32)
        y, _ = chunk_scan(chunks(x), chunks(b), chunks(c), chunks(dt), a,
                          mp["D"], first, init, dims)
        y = y.reshape(Bsz, nc * Q, dims.d_ssm)[:, :S]
    with jax.named_scope("ssm_out"):
        y = gated_norm(y, z, mp["norm"], dims, eps).astype(dtype)
        return y @ mp["w_out"].astype(dtype)
