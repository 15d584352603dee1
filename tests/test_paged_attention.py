"""Pallas paged-attention kernel vs the XLA gather formulation
(reference analog: inference/v2/kernels/ragged_ops blocked_flash tests)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.model import (_paged_attention,
                                           _paged_attention_pallas)
from deepspeed_tpu.inference.ragged.state import RaggedBatch


def _mixed_batch(T=16, max_seqs=4, nblocks=12, bs=8, Hkv=2, D=16, seed=0):
    """Three live sequences at different positions + budget padding."""
    r = np.random.RandomState(seed)
    # seq 0: decode at pos 19 (3 blocks); seq 1: prefill chunk pos 4..11
    # (2 blocks); seq 2: decode at pos 0 (1 block)
    tables = np.full((max_seqs, nblocks), -1, np.int32)
    tables[0, :3] = [5, 2, 9]
    tables[1, :2] = [1, 7]
    tables[2, :1] = [4]
    tok_pos = [(0, 19)] + [(1, p) for p in range(4, 12)] + [(2, 0)]
    T_used = len(tok_pos)
    positions = np.zeros(T, np.int32)
    seq_slot = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    for i, (s, p) in enumerate(tok_pos):
        seq_slot[i], positions[i], valid[i] = s, p, True
    kv = jnp.asarray(r.randn(nblocks + 1, bs, 2, Hkv, D), jnp.float32)
    batch = RaggedBatch(
        token_ids=jnp.zeros(T, jnp.int32),
        positions=jnp.asarray(positions),
        seq_slot=jnp.asarray(seq_slot),
        token_valid=jnp.asarray(valid),
        block_tables=jnp.asarray(tables),
        context_lens=jnp.zeros(max_seqs, jnp.int32),
        logits_idx=jnp.full(max_seqs, -1, jnp.int32),
        n_tokens=T_used, n_seqs=3)
    return kv, batch, bs


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("H", [4, 2])
    def test_matches_xla_gather(self, H):
        kv, batch, bs = _mixed_batch()
        Hkv, D = kv.shape[3], kv.shape[4]
        q = jnp.asarray(np.random.RandomState(1).randn(
            batch.token_ids.shape[0], H, D), jnp.float32)
        scale = 1.0 / np.sqrt(D)
        ref = _paged_attention(kv, q, batch, bs, 4, scale)
        out = _paged_attention_pallas(kv, q, batch, bs, 4, scale)
        valid = np.asarray(batch.token_valid)
        np.testing.assert_allclose(np.asarray(out)[valid],
                                   np.asarray(ref)[valid],
                                   atol=1e-5, rtol=1e-5)

    def test_under_jit_with_bf16(self):
        kv, batch, bs = _mixed_batch()
        kv = kv.astype(jnp.bfloat16)
        D = kv.shape[4]
        q = jnp.asarray(np.random.RandomState(2).randn(
            batch.token_ids.shape[0], 4, D), jnp.bfloat16)
        scale = 1.0 / np.sqrt(D)
        f_ref = jax.jit(lambda kv, q: _paged_attention(kv, q, batch, bs, 4,
                                                       scale))
        f_pal = jax.jit(lambda kv, q: _paged_attention_pallas(
            kv, q, batch, bs, 4, scale))
        valid = np.asarray(batch.token_valid)
        np.testing.assert_allclose(
            np.asarray(f_pal(kv, q)).astype(np.float32)[valid],
            np.asarray(f_ref(kv, q)).astype(np.float32)[valid],
            atol=2e-2, rtol=2e-2)


class TestAliasedBlockTables:
    """Prefix-cache aliasing at the attention level: two sequences'
    block tables referencing the SAME physical block must read identical
    KV from it — attention is a pure gather by block id, so aliasing is
    invisible to the kernel.  Checked against a de-aliased reference
    where the shared content is duplicated into a private block."""

    def _aliased_batch(self, bs=8, Hkv=2, D=16, nblocks=12):
        r = np.random.RandomState(5)
        kv = np.asarray(r.randn(nblocks + 1, bs, 2, Hkv, D), np.float32)
        # both sequences share physical block 4 for positions 0..7, then
        # diverge; the de-aliased reference gives seq 1 a private copy
        # (block 9) with identical content
        kv[9] = kv[4]
        tables = np.full((4, nblocks), -1, np.int32)
        tables[0, :2] = [4, 2]
        tables[1, :2] = [4, 7]
        dealiased = tables.copy()
        dealiased[1, 0] = 9
        # one decode token per sequence, deep enough to read the shared
        # block AND the private tail
        tok_pos = [(0, 12), (1, 14)]
        T = 4
        positions = np.zeros(T, np.int32)
        seq_slot = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        for i, (s, p) in enumerate(tok_pos):
            seq_slot[i], positions[i], valid[i] = s, p, True

        def batch(tab):
            return RaggedBatch(
                token_ids=jnp.zeros(T, jnp.int32),
                positions=jnp.asarray(positions),
                seq_slot=jnp.asarray(seq_slot),
                token_valid=jnp.asarray(valid),
                block_tables=jnp.asarray(tab),
                context_lens=jnp.zeros(4, jnp.int32),
                logits_idx=jnp.full(4, -1, jnp.int32),
                n_tokens=2, n_seqs=2)
        return jnp.asarray(kv), batch(tables), batch(dealiased), bs, valid

    @pytest.mark.parametrize("impl", [_paged_attention,
                                      _paged_attention_pallas])
    def test_shared_block_reads_identical_kv(self, impl):
        kv, aliased, dealiased, bs, valid = self._aliased_batch()
        D = kv.shape[4]
        q = jnp.asarray(np.random.RandomState(6).randn(
            aliased.token_ids.shape[0], 4, D), jnp.float32)
        scale = 1.0 / np.sqrt(D)
        out_alias = impl(kv, q, aliased, bs, 4, scale)
        out_ref = impl(kv, q, dealiased, bs, 4, scale)
        np.testing.assert_allclose(np.asarray(out_alias)[valid],
                                   np.asarray(out_ref)[valid],
                                   atol=1e-6, rtol=1e-6)


# --------------------------------------------------------------- tiles
# The kernel's grid walks query tiles (ops/paged_attention.py): every
# batch below is built by ``StateManager.build_batch`` itself, so the
# runs are what the scheduler really stages.

BS_T, NBLK_T, HKV_T, D_T = 8, 96, 2, 16


def _built_batch(runs, T, max_seqs=8):
    """``runs``: [(uid, tokens already in the cache, new tokens)] ->
    (RaggedBatch of the new tokens, {uid: slot}).  The history goes
    through ``build_batch`` first, as earlier steps would have put it
    there (one request at a time, so its rows never count)."""
    from deepspeed_tpu.inference.ragged.state import (KVCacheConfig,
                                                      StateManager)

    sm = StateManager(KVCacheConfig(num_layers=1, num_kv_heads=HKV_T,
                                    head_dim=D_T, block_size=BS_T,
                                    num_blocks=NBLK_T, dtype=jnp.float32),
                      max_seqs=max_seqs)
    for uid, seen, _ in runs:
        if seen:
            sm.build_batch([(uid, [1] * seen)], seen)
    batch = sm.build_batch([(uid, [2] * n) for uid, _, n in runs if n], T)
    return batch, dict(sm._slots)


# name -> (runs, token budget); bs = 8, so 128-row tiles cross 16 blocks.
# (Two budgets, 32 and 192, for the ten of them: cases of one shape
# share a compiled program.)
TILE_BATCHES = {
    # runs of one at different depths, then budget padding
    "decode-only": ([(1, 19, 1), (2, 0, 1), (3, 70, 1), (4, 8, 1)], 32),
    # 150 rows = a whole 128-row tile and 22 of the next; starts at
    # position 5, in the middle of a block
    "chunk-unaligned": ([(1, 5, 150)], 192),
    # a verify window (k + 1 = 4 rows) between two decode tokens
    "verify-window": ([(1, 30, 1), (2, 19, 4), (3, 3, 1)], 32),
    # two chunks and three decode tokens in one step
    "two-chunks": ([(1, 40, 1), (2, 3, 140), (3, 9, 1), (4, 61, 30),
                    (5, 0, 1)], 192),
    # a run of exactly the short height and one just over it
    "eight-and-nine": ([(1, 11, 8), (2, 2, 9)], 32),
    # all padding but one token
    "one-token": ([(1, 12, 1)], 32),
    # bs = 8 and groups of 16 blocks: a group of the short call holds
    # 128 keys, a table of 32 blocks two groups.  Contexts that end in
    # the first and a middle block of their second group, at the edge
    # between the two (the first group's last key, the second's first)
    # and inside the first group; the deepest tile first
    "group-edges": ([(1, 250, 1), (2, 135, 1), (3, 127, 1), (4, 128, 1),
                     (5, 10, 1), (6, 67, 1)], 32),
    # verify windows of 2 to 8 rows whose positions cross a group's edge
    # (125..130 and 127..128), one that ends on it (120..127), and a
    # decode token; the last tile of the list is the shallowest
    "verify-across-groups": ([(1, 125, 6), (2, 120, 8), (3, 127, 2),
                              (4, 57, 7), (5, 180, 1), (6, 2, 3)], 32),
    # the last tile of the list is the deepest, behind single-group ones
    "deep-last": ([(1, 5, 1), (2, 30, 2), (3, 250, 1)], 32),
    # tiles of one block and deep tiles in turn (what is fetched ahead
    # across tiles, and into which of the two buffers: at groups of
    # four blocks 7, 5, 4 and 3 groups, odd and even counts between the
    # shallow ones); contexts that end on a block's last key (7, 199)
    # and on a group's (127)
    "deep-and-shallow-in-turn": ([(1, 3, 1), (2, 199, 1), (3, 7, 1),
                                  (4, 135, 1), (5, 0, 1), (6, 127, 1),
                                  (7, 64, 1)], 32),
}


def _random_pool(seed, layers=None, quant=False, hkv=HKV_T, d=D_T):
    r = np.random.RandomState(seed)
    shape = (NBLK_T + 1, BS_T, 2, hkv, d)
    if layers:
        shape = (layers,) + shape
    if quant:
        return (jnp.asarray(r.randint(-127, 128, shape), jnp.int8),
                jnp.asarray(r.uniform(0.01, 0.03,
                                      shape[:-4] + (hkv, 2 * BS_T)),
                            jnp.float32))
    return jnp.asarray(r.randn(*shape), jnp.float32)


# One compile for the cases that differ in data only: the interpreted
# kernel traced eagerly is lowered anew at every call (2.5-4.5 s), under
# ``jit`` once a shape.  ``most``: the ``GROUP_MAX`` a test has patched
# in, which the trace reads and the cache's key has to hold.
@functools.partial(jax.jit, static_argnames=("nb", "scale", "layer",
                                             "window", "most"))
def _on_kernel(kv, q, batch, nb, scale, slopes=None, layer=None, window=None,
               most=None):
    return _paged_attention_pallas(kv, q, batch, BS_T, nb, scale,
                                   slopes=slopes, layer=layer, window=window)


@functools.partial(jax.jit, static_argnames=("nb", "scale", "layer",
                                             "window"))
def _on_xla(kv, q, batch, nb, scale, slopes=None, layer=None, window=None):
    return _paged_attention(kv, q, batch, BS_T, nb, scale, slopes=slopes,
                            layer=layer, window=window)


def _check_tiles(kv, batch, H, nb, layer=None, slopes=None, tol=1e-5,
                 dtype=jnp.float32):
    T = batch.token_ids.shape[0]
    D = jax.tree.leaves(kv)[0].shape[-1]
    q = jnp.asarray(np.random.RandomState(11).randn(T, H, D), dtype)
    scale = float(1.0 / np.sqrt(D))
    ref = _on_xla(kv, q, batch, nb, scale, slopes=slopes, layer=layer)
    out = _on_kernel(kv, q, batch, nb, scale, slopes=slopes, layer=layer)
    valid = np.asarray(batch.token_valid)
    out, ref = (np.asarray(a.astype(jnp.float32)) for a in (out, ref))
    assert valid.any()
    np.testing.assert_allclose(out[valid], ref[valid], atol=tol, rtol=tol)
    # budget padding belongs to no tile: nothing is written there
    assert not out[~valid].any()


def _poisoned(kv, batch, window=None, layer=None):
    """``kv`` with NaN in every pool row that no query of ``batch`` has
    to read (the scales of a quantized pool: codes cannot hold one); of
    a stacked pool viewed ``[L * rows, ...]`` every other layer's rows
    too (``layer``: ``(base, rows)``)."""
    data = jax.tree.leaves(kv)[0]
    base = 0 if layer is None else layer[0]
    need = np.zeros(data.shape[0], bool)
    tables = np.asarray(batch.block_tables)
    valid = np.asarray(batch.token_valid)
    for slot, pos in zip(np.asarray(batch.seq_slot)[valid],
                         np.asarray(batch.positions)[valid]):
        first = 0 if window is None else max(pos - (window - 1), 0) // BS_T
        need[base + tables[slot, first:pos // BS_T + 1]] = True
    bad = jnp.asarray(~need).reshape((-1,) + (1,) * (data.ndim - 1))
    if isinstance(kv, tuple):
        return kv[0], jnp.where(bad[..., 0, 0], jnp.nan, kv[1])
    return jnp.where(bad, jnp.nan, kv)


class TestQueryTiles:
    # five query heads a kv head (falcon-h1): the first ratio that is
    # not a power of two, 40 folded rows a short tile and 640 a long one
    @pytest.mark.parametrize("rep", [1, 4, 5, 8])
    @pytest.mark.parametrize("name", sorted(TILE_BATCHES))
    def test_matches_xla_on_built_batches(self, name, rep):
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        _check_tiles(_random_pool(3), batch, HKV_T * rep, nb=32)

    @pytest.mark.parametrize("name", ["two-chunks", "verify-window",
                                      "group-edges",
                                      "verify-across-groups"])
    def test_alibi(self, name):
        from deepspeed_tpu.models import layers as L
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        _check_tiles(_random_pool(4), batch, 8, nb=32,
                     slopes=L.alibi_slopes(8))

    @pytest.mark.parametrize("name", ["two-chunks", "decode-only",
                                      "group-edges",
                                      "verify-across-groups"])
    def test_int8_kv(self, name):
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        _check_tiles(_random_pool(5, quant=True), batch, 8, nb=32, tol=1e-4)

    @pytest.mark.parametrize("name", ["group-edges", "verify-across-groups",
                                      "two-chunks"])
    def test_head_size_64(self, name):
        """gpt2's head: the blocks' lanes are half full and a head's
        keys are gathered a key at a time, not through the words."""
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        kv = _random_pool(9, hkv=3, d=64)
        _check_tiles(kv, batch, 3, nb=32)

    @pytest.mark.parametrize("rep", [1, 8])
    @pytest.mark.parametrize("store", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("name", ["group-edges", "verify-across-groups",
                                      "chunk-unaligned"])
    def test_lane_full_heads_are_read_through_the_words(self, name, store,
                                                        rep):
        """At ``D = 128`` with a whole number of 32-bit sublanes a key
        (four heads: two in bf16, one in int8) a head's keys come by
        strided loads over the block's words, its bits shifted out: the
        same numbers as the XLA formulation on the same pool."""
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        kv = _random_pool(10, quant=store == "int8", hkv=4, d=128)
        dtype, tol = jnp.float32, 1e-4
        if store == "bf16":
            kv, dtype, tol = kv.astype(jnp.bfloat16), jnp.bfloat16, 2e-2
        _check_tiles(kv, batch, 4 * rep, nb=32, tol=tol, dtype=dtype)

    @pytest.mark.parametrize("window,most", [(None, 4), (64, 16), (20, 8)])
    @pytest.mark.parametrize("name", sorted(TILE_BATCHES))
    def test_host_counts_the_trips_and_copies_the_kernel_makes(
            self, name, window, most, monkeypatch):
        """``group_steps`` on the host against what the short call does
        on the device for the same runs: a trip of a tile's loop is a
        group that holds a needed block (``ceil(span / k)`` of them, ``k``
        from ``kv_group``: its own, and smaller ones, so that a table of
        32 blocks is up to eight groups deep), and each needed block is
        one copy started and one waited for, no more."""
        from deepspeed_tpu.inference.model import _query_tiles
        pa = importlib.import_module("deepspeed_tpu.ops.paged_attention")

        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        nb = 32
        kv = _random_pool(8)
        tiles = _query_tiles(kv, batch, BS_T, nb)
        assert pa.GROUP_MAX == 16
        monkeypatch.setattr(pa, "GROUP_MAX", most)
        k = pa.kv_group(pa.SHORT, 4, HKV_T, D_T, BS_T, jnp.float32, nb)
        assert k == most
        n = int(tiles.short.count)
        pos, length = (np.asarray(a)[:n]
                       for a in (tiles.short.pos, tiles.short.length))
        first, last = pa._tile_span(np.arange(n), pos, length, BS_T, window)
        span = np.asarray(last) - np.asarray(first) + 1
        short = [(seen, m) for _, seen, m in runs if 0 < m <= pa.SHORT]
        steps, blocks = pa.group_steps(short, BS_T, k, window)
        assert (steps, blocks) == (int((-(-span // k)).sum()),
                                   int(span.sum()))
        if window and name not in ("deep-and-shallow-in-turn",
                                   "verify-across-groups", "two-chunks"):
            return
        # the walk itself, counted where the kernel makes it (the short
        # call alone: the long tiles' list emptied): every full layer's,
        # and three of the batches behind each window
        made = {"walks": 0, "copies": 0}
        walk = pa.each_group_block

        def count(key):
            jax.debug.callback(
                lambda: made.__setitem__(key, made[key] + 1))

        def counted(do, *args):
            count("walks")
            walk(lambda cp: (count("copies"), do(cp)), *args)

        monkeypatch.setattr(pa, "each_group_block", counted)
        q = jnp.asarray(np.random.RandomState(3).randn(T, 4 * HKV_T, D_T),
                        jnp.float32)
        pa.paged_attention(kv, q, tiles._replace(long=jax.tree.map(
            jnp.zeros_like, tiles.long)), 0.25, window=window)
        jax.effects_barrier()
        # every group and every block once started and once waited for
        assert made == {"walks": 2 * steps, "copies": 2 * blocks}

    @pytest.mark.parametrize("store", ["bf16", "int8"])
    @pytest.mark.parametrize("window,most", [(None, 4), (20, 16)])
    @pytest.mark.parametrize("name", ["deep-and-shallow-in-turn",
                                      "verify-across-groups", "two-chunks"])
    def test_a_block_no_tile_needs_is_never_read(self, name, window, most,
                                                 store, monkeypatch):
        """Every pool row outside the tiles' needed blocks holds NaN
        (an int8 cache's scales do): the trash row, the blocks behind a
        tile's last position, a window layer's blocks before its first.
        The kernel's output is the reference's over the clean pool, at
        its own groups and at groups of four blocks (a full layer's
        tiles up to eight groups deep)."""
        monkeypatch.setattr(importlib.import_module(
            "deepspeed_tpu.ops.paged_attention"), "GROUP_MAX", most)
        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        nb, H = 32, 4 * HKV_T
        kv = _random_pool(14, quant=store == "int8")
        dtype, tol = jnp.float32, 1e-4
        if store == "bf16":
            kv, dtype, tol = kv.astype(jnp.bfloat16), jnp.bfloat16, 2e-2
        q = jnp.asarray(np.random.RandomState(15).randn(T, H, D_T), dtype)
        scale = float(1.0 / np.sqrt(D_T))
        want = _on_xla(kv, q, batch, nb, scale, window=window)
        got = _on_kernel(_poisoned(kv, batch, window), q, batch, nb, scale,
                         window=window, most=most)
        valid = np.asarray(batch.token_valid)
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        np.testing.assert_allclose(got[valid], want[valid], atol=tol,
                                   rtol=tol)
        assert not got[~valid].any()

    def test_row_after_a_tile_is_not_overwritten(self):
        """Each height alone writes its own tiles' rows and no other:
        the long call leaves the decode rows that follow a chunk's last
        row untouched (zero), the short call leaves the chunk's."""
        from deepspeed_tpu.inference.model import _query_tiles
        from deepspeed_tpu.ops.paged_attention import paged_attention

        runs, T = TILE_BATCHES["two-chunks"]
        batch, _ = _built_batch(runs, T)
        kv = _random_pool(7)
        q = jnp.asarray(np.random.RandomState(13).randn(T, 8, D_T),
                        jnp.float32)
        scale = 1.0 / np.sqrt(D_T)
        ref = np.asarray(_paged_attention(kv, q, batch, BS_T, 32, scale))
        tiles = _query_tiles(kv, batch, BS_T, 32)
        rows = np.arange(T)
        long_rows = ((rows >= 1) & (rows < 141)) | ((rows >= 142)
                                                    & (rows < 172))
        short_rows = np.isin(rows, [0, 141, 172])
        for off, mine in (("short", long_rows), ("long", short_rows)):
            only = tiles._replace(**{off: jax.tree.map(
                jnp.zeros_like, getattr(tiles, off))})
            out = np.asarray(paged_attention(kv, q, only, scale))
            np.testing.assert_allclose(out[mine], ref[mine], atol=1e-5,
                                       rtol=1e-5)
            assert not out[~mine].any()

    @pytest.mark.parametrize("name", sorted(TILE_BATCHES))
    def test_tile_lists_and_host_count_agree(self, name):
        """``query_tiles`` on the device and ``tile_counts`` on the
        host see the same step; every tile is one slot's consecutive
        rows and positions."""
        from deepspeed_tpu.inference.model import _query_tiles
        from deepspeed_tpu.ops.paged_attention import (LONG, SHORT,
                                                        tile_counts)

        runs, T = TILE_BATCHES[name]
        batch, slots = _built_batch(runs, T)
        tiles = _query_tiles(_random_pool(8), batch, BS_T, 32)
        n_short, n_long, long_rows = tile_counts([n for _, _, n in runs])
        assert int(tiles.short.count) == n_short
        assert int(tiles.long.count) == n_long
        lengths = np.asarray(tiles.long.length)
        assert lengths[:n_long].sum() == long_rows
        assert not lengths[n_long:].any()
        tables = np.asarray(batch.block_tables)
        seen = np.zeros(T, int)
        for tl, height in ((tiles.short, SHORT), (tiles.long, LONG)):
            for k in range(int(tl.count)):
                row, pos, n = (int(np.asarray(a)[k])
                               for a in (tl.row, tl.pos, tl.length))
                assert 1 <= n <= height
                sl = slice(row, row + n)
                slot = np.asarray(batch.seq_slot)[sl]
                assert (slot == slot[0]).all()
                np.testing.assert_array_equal(
                    np.asarray(batch.positions)[sl], np.arange(pos, pos + n))
                want = tables[slot[0], :32]
                np.testing.assert_array_equal(
                    np.asarray(tl.tables)[k],
                    np.where(want < 0, NBLK_T, want))
                seen[sl] += 1
        # every real row in exactly one tile, padding in none
        np.testing.assert_array_equal(
            seen, np.asarray(batch.token_valid).astype(int))
