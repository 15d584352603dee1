"""Ragged inference state: sequence descriptors + paged KV cache + batch
metadata.

TPU-native re-design of the reference's ragged subsystem
(``inference/v2/ragged/``): ``DSSequenceDescriptor``
(sequence_descriptor.py, 280 LoC), ``BlockedKVCache`` (kv_cache.py, 208),
``DSStateManager`` (ragged_manager.py), ``RaggedBatchWrapper``
(ragged_wrapper.py, 292 — pinned host-staged batch metadata).

Differences forced/afforded by XLA:
* the KV cache is one jnp array [L, num_blocks, block_size, 2, Hkv, D]
  updated functionally with scatter (donated across steps — in-place in
  practice);
* batch metadata is a fixed-shape numpy struct (XLA needs static shapes —
  the reference's pinned "fast host buffer" maps to plain numpy staged
  via device_put, its variable batch to padding up to the token budget).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .allocator import BlockedAllocator

# Sentinel token value in a pending queue meaning "the value is the
# previous pipelined step's on-device sample for this sequence's slot" —
# the host schedules position/blocks for it without ever reading the
# token back (engine.py substitutes it inside the jitted step from the
# prior step's [max_seqs] sample array).  Real token ids are >= 0.
FEEDBACK_TOKEN = -1

# root parent digest of every per-sequence block hash chain
_CHAIN_ROOT = b"kv-prefix-chain-v1"

# revive rounds a single queued request may trigger before match_prefix
# stops probing the tier for it: reviving allocates destination blocks,
# and in a tiny pool that allocation can evict (and re-demote) the very
# ancestors the chain needs — the cap turns that churn into a bounded
# cost and falls through to a plain resident match / re-prefill
_MAX_REVIVE_ATTEMPTS = 2


class RestageEntry(NamedTuple):
    """One queued tier->HBM block restage: the engine resolves ``op``
    (tier.ReviveOp) at its pre-dispatch drain, uploads the verified
    payload into block ``dst`` and registers ``digest`` — or frees
    ``dst`` when verification fails (the caller re-prefills)."""
    uid: int
    digest: bytes
    parent: bytes
    tokens: Tuple[int, ...]
    dst: int
    op: object


def chain_hash(parent: bytes, tokens) -> bytes:
    """Rolling content hash of one FULL KV block: digest of
    ``(parent_hash, block_tokens)``.  128-bit blake2b — the index maps
    digest -> physical block and a collision would silently alias wrong
    KV, so a real cryptographic digest (not Python's ``hash``) is the
    cheap insurance; hashing a 64-token block is ~1 µs."""
    toks = np.asarray(tokens, np.int64).tobytes()
    return hashlib.blake2b(parent + toks, digest_size=16).digest()


def iter_prefix_chain_digests(tokens, block_size: int,
                              max_blocks: Optional[int] = None):
    """Lazily yield the chain digest of each FULL block-aligned prefix
    of ``tokens`` — a GENERATOR so consumers that stop at the first
    index miss (``match_prefix`` on a cache-miss admission) hash one
    block, not the whole prompt."""
    n = len(tokens) // block_size
    if max_blocks is not None:
        n = min(n, max_blocks)
    parent = _CHAIN_ROOT
    for k in range(n):
        parent = chain_hash(parent, tokens[k * block_size:
                                           (k + 1) * block_size])
        yield parent


def prefix_chain_digests(tokens, block_size: int,
                         max_blocks: Optional[int] = None) -> List[bytes]:
    """Chain digests of every FULL block-aligned prefix of ``tokens`` —
    the engine-independent form of the prefix-cache key.  Entry ``k`` is
    the digest a :class:`StateManager` index holds iff the first
    ``(k+1) * block_size`` tokens of this stream are resident, so a
    fleet router can score cache affinity for a prompt against any
    replica's digest set without touching that replica's engine
    (docs/SERVING.md "Fleet: routing, failover, migration").
    ``match_prefix`` consumes the same digests (lazily, via
    :func:`iter_prefix_chain_digests`), so router-side scoring and
    engine-side matching can never disagree on the key."""
    return list(iter_prefix_chain_digests(tokens, block_size,
                                          max_blocks))


@dataclasses.dataclass
class RecurrentConfig:
    """The second kind of per-request cache state: a model with
    recurrent layers (``TransformerConfig.has_ssm``) keeps, for every
    sequence and layer, a state of FIXED size that is advanced and not
    appended to.  It lives in the sequence's slot (``StateManager.slot``,
    the row of its block table), so it needs no allocator: ``[L,
    max_seqs + 1, heads, head_dim, state]`` and the convolution's tail
    ``[L, max_seqs + 1, conv, channels]``, the last row taking the
    writes of rows that are not there (the pool's trash block's part).
    Stored in ``dtype``, advanced in float32."""
    heads: int
    head_dim: int
    state: int
    conv: int                 # tail entries kept: the convolution's width
    channels: int             # channels the convolution runs over
    chunk: int                # the chunked form's chunk, in tokens
    dtype: object = jnp.bfloat16
    # runs of more than one token a step may hold (the scheduler's
    # bound): with it a step's chunks are at most ceil(T / chunk) + this
    scan_runs: int = 4
    # the layers that hold a state, where not every layer does (a layer
    # holds ONE kind of cache: its state rows lie at its rank among the
    # layers of its kind); None: all of ``KVCacheConfig.num_layers``
    layers: Optional[int] = None

    # the state's own stored type where it is not ``dtype`` (the
    # tail's, which holds raw inputs as they were computed): a state
    # that a decoding sequence advances thousands of times is rounded
    # once a token in its stored type
    state_dtype: object = None

    def n_chunks(self, token_budget: int) -> int:
        return -(-token_budget // self.chunk) + self.scan_runs

    def bytes_per_seq(self, num_layers: int) -> int:
        """A sequence's state and tail over ``num_layers`` layers."""
        state = jnp.dtype(self.state_dtype or self.dtype).itemsize
        return num_layers * (
            state * self.heads * self.head_dim * self.state
            + jnp.dtype(self.dtype).itemsize * self.conv * self.channels)


@dataclasses.dataclass
class RunCut:
    """How a step's runs of several tokens are cut for a latent layer of
    a model with no recurrent layer beside it (``RecurrentConfig`` says
    the same of a model that has one): chunks of at most ``chunk`` rows,
    at most ``scan_runs`` such runs a step (``RecBatch``)."""
    chunk: int
    scan_runs: int = 4

    def n_chunks(self, token_budget: int) -> int:
        return -(-token_budget // self.chunk) + self.scan_runs


@dataclasses.dataclass
class KVCacheConfig:
    num_layers: int
    num_kv_heads: int
    head_dim: int
    block_size: int = 64
    num_blocks: int = 128
    dtype: object = jnp.bfloat16
    # "none" | "int8" | "fp8": store the paged cache quantized with one
    # scale per written (token, k|v, head) vector — halves the KV HBM
    # stream that dominates long-context decode (reference analog:
    # ZeRO-Inference KV quantization, deepspeed/inference/quantization/)
    quant: str = "none"
    # a model with recurrent layers: the state rows beside the blocks
    recurrent: Optional[RecurrentConfig] = None
    # latent attention: a token leaves ONE row of this many values in a
    # block (``[L, blocks + 1, block_size, latent_row]``) in place of
    # the keys and values of ``num_kv_heads`` heads; ``num_layers`` then
    # counts the latent layers
    latent_dim: int = 0
    # a latent-only model: how its layers read a step's runs
    run_cut: Optional[RunCut] = None
    # the pool is read by the Pallas kernel, whose DMAs move whole memory
    # tiles (``ops/paged_attention.py``): a block's ``(Hkv, D)`` slab is
    # allocated filled up to them (``slab``: gpt2's 12 x 64 as 16 x 128;
    # the heads and lanes a model lacks stay zero), so that the kernel
    # reads a block where it lies.  The engine says so where it runs the
    # kernel; the XLA formulations read any shape
    tiled: bool = False
    # the kv heads split over this many chips (a tensor mesh): each
    # chip's own heads are filled up, ``head_groups`` slabs side by side
    head_groups: int = 1

    @property
    def runs(self):
        """What cuts a step's runs into ``RecBatch``: the recurrent
        layers' configuration, a latent-only model's ``run_cut``, or
        None for a model whose layers read rows alone."""
        return self.recurrent or self.run_cut

    @property
    def max_context(self) -> int:
        return self.num_blocks * self.block_size

    @property
    def latent_row(self) -> int:
        """The width of a latent pool's rows: ``latent_dim`` and zeros up
        to whole vectors of 128 lanes.  The chip's tiles pad a row to
        that anyway; of a pool whose rows are not whole tiles its
        compiler keeps the block axis innermost instead and transposes
        the whole pool into every step and out of it (two copies of
        0.9 GB a step at 12288 blocks of 64 rows of 576)."""
        return -(-self.latent_dim // 128) * 128

    def __post_init__(self):
        if self.latent_dim and self.quant != "none":
            raise ValueError("kv_quant: a latent pool is not quantized")
        if self.quant not in ("none", "int8", "fp8"):
            raise ValueError(
                f"kv_quant={self.quant!r}: the paged cache supports "
                "'int8' or 'fp8' (per-vector scales); weight_quant is "
                "the option that also takes 'int4'")

    @property
    def store_dtype(self):
        """What the pool's blocks hold: the codes' type when quantized."""
        return {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}.get(
            self.quant, self.dtype)

    @property
    def slab(self):
        """(heads, lanes) a block of the pool holds a key or a value in:
        the model's, or with ``tiled`` each chip's share of them filled
        up to whole memory tiles."""
        if not self.tiled:
            return self.num_kv_heads, self.head_dim
        from ...ops.paged_attention import slab
        heads, lanes = slab(self.num_kv_heads // self.head_groups,
                            self.head_dim, self.store_dtype)
        return self.head_groups * heads, lanes

    def kv_zeros(self):
        """A pristine cache: a single array ``[L, blocks + 1, bs, 2,
        heads, lanes]`` (``slab``), or (data, scales) when quantized (a
        plain tuple — a pytree, so jit/donate/device_put treat it like
        the array everywhere the engine is agnostic).  The scales are
        ``[L, blocks + 1, heads, 2 * bs]`` f32: a head a row, its keys'
        scales of the block and then its values', which is a whole
        memory tile a block at 8 kv heads and blocks of 64 (a layout
        with the heads innermost is 16 lanes of 128, and the TPU's
        compiler then keeps the block axis innermost and relays a
        layer's worth for every kernel call)."""
        shape = (self.num_layers, self.num_blocks + 1, self.block_size, 2
                 ) + self.slab
        if self.latent_dim:
            shape = shape[:3] + (self.latent_row,)
        if self.quant == "none":
            return jnp.zeros(shape, self.dtype)
        return (jnp.zeros(shape, self.store_dtype),
                jnp.zeros(shape[:2] + (shape[4], 2 * shape[2]),
                          jnp.float32))

    def cache_zeros(self, max_seqs: int):
        """What a serving step carries and donates: the paged cache, and
        for a model with recurrent layers a dict of it (``"kv"``), the
        state rows (``"ssm"``) and the convolution tails (``"conv"``)."""
        kv = self.kv_zeros()
        rc = self.recurrent
        if rc is None:
            return kv
        rows = (rc.layers or self.num_layers, max_seqs + 1)
        return {"kv": kv,
                "ssm": jnp.zeros(rows + (rc.heads, rc.head_dim, rc.state),
                                 rc.state_dtype or rc.dtype),
                "conv": jnp.zeros(rows + (rc.conv, rc.channels), rc.dtype)}


@dataclasses.dataclass
class SequenceDescriptor:
    """(reference: DSSequenceDescriptor sequence_descriptor.py)."""
    uid: int
    seen_tokens: int = 0                       # tokens already in KV
    blocks: List[int] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)  # generated
    # --- prefix-cache state --------------------------------------------
    cached_tokens: int = 0        # tokens served from the prefix cache
    # token ids in KV order while every value is host-known; a deferred
    # on-device token (FEEDBACK_TOKEN) that no launch is named for
    # breaks the chain — blocks past the break are never content-hashed
    chain: List[int] = dataclasses.field(default_factory=list)
    chain_broken: bool = False
    # per-full-block rolling hashes (parallel to ``blocks``' prefix);
    # pre-seeded by a prefix match, extended as chain blocks fill
    hashes: List[bytes] = dataclasses.field(default_factory=list)
    # speculative-decode state: number of DRAFTED tokens in the most
    # recent scheduled step whose acceptance has not resolved yet.
    # While nonzero, the last ``draft_len`` chain tokens / KV rows are
    # provisional: prefix-cache registration is deferred (a shared
    # block must never contain tokens that may roll back) and
    # :meth:`StateManager.resolve_draft` either commits them or rewinds
    # the write cursor.
    draft_len: int = 0
    # chain positions whose token is still on the device only, as
    # ``(sid, index)``: a row fed from step ``sid``'s sample array
    # (``build_batch(deferred_from=...)``) whose value that step's
    # collect fills in (:meth:`StateManager.resolve_feedback`).  While
    # any is open the sequence is not resumable and no block at or past
    # the first one is content-hashed.
    deferred: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)
    # a model with recurrent layers: rows taken back (``rewind``) whose
    # token the slot's state has ALREADY been advanced by.  A state is
    # advanced, not overwritten, so the row scheduled next is marked as
    # a replay: it reads the state it produced and leaves it as it is
    state_ahead: int = 0

    def blocks_needed(self, new_tokens: int, block_size: int) -> int:
        total = self.seen_tokens + new_tokens
        needed = -(-total // block_size)       # ceil
        return max(0, needed - len(self.blocks))

    @property
    def resumable(self) -> bool:
        """The host knows every KV row's token id in order: the chain
        is intact and no unresolved draft window holds provisional
        rows.  This is THE eligibility predicate shared by
        preemption-by-eviction, failure-recovery re-queueing, and
        ``engine.snapshot()`` — a resumable sequence can be released
        and re-prefilled token-identically; a non-resumable one holds
        device-side tokens the host never saw (a deferred feedback
        marker) and can only be closed."""
        return (not self.chain_broken and self.draft_len == 0
                and not self.deferred
                and len(self.chain) == self.seen_tokens)


class RecBatch(NamedTuple):
    """A step's runs as a model with recurrent layers needs them (a run:
    the consecutive rows one sequence has in the step; a slot holds at
    most one).  Host arithmetic over the schedule."""
    run_len: jnp.ndarray         # [max_seqs] i32, 0: no run in the slot
    replay: jnp.ndarray          # [max_seqs] bool, the one-token run was
                                 # computed before (``state_ahead``)
    chunks: jnp.ndarray          # [NC, 5] i32: the runs of several tokens
                                 # cut into chunks of at most ``chunk``
                                 # rows: first flat row, rows, slot
                                 # (max_seqs: no chunk), first of its
                                 # run, last of its run


class RaggedBatch(NamedTuple):
    """Fixed-shape device view of one engine step (the RaggedBatchWrapper
    analog).  All arrays are padded to (token_budget, max_seqs)."""
    token_ids: jnp.ndarray       # [T] i32
    positions: jnp.ndarray       # [T] i32, position within its sequence
    seq_slot: jnp.ndarray        # [T] i32, row into block_tables
    token_valid: jnp.ndarray     # [T] bool, False for budget padding
    block_tables: jnp.ndarray    # [max_seqs, max_blocks] i32; -1 pad
                                 # (wraps to the trash row on gather)
    context_lens: jnp.ndarray    # [max_seqs] i32, ctx len AFTER this step
    logits_idx: jnp.ndarray      # [max_seqs] i32, flat idx of each seq's
                                 # last token this step (-1 if none)
    n_tokens: int                # real token count (static python int)
    n_seqs: int
    feedback_src: Optional[jnp.ndarray] = None
                                 # [T] i32: slot whose previous-step
                                 # on-device sample supplies this token's
                                 # id (-1 = token_ids holds the value)
    seq_uids: Optional[jnp.ndarray] = None
                                 # [max_seqs] u32: uid occupying each
                                 # slot (masked to 32 bits; 0 when
                                 # empty).  Feeds the schedule-invariant
                                 # per-(uid, position) sampling keys —
                                 # see sampler.sample_rows
    verify_idx: Optional[jnp.ndarray] = None
                                 # [max_seqs, n_verify] i32: flat token
                                 # indices of each slot's speculative
                                 # verify window (-1 pad).  Column j of
                                 # a drafting row is the fed token
                                 # (j=0) / j-th draft; column 0 of a
                                 # non-drafting row is its logits_idx.
                                 # Present only on verify-step batches
                                 # (None keeps the legacy single-sample
                                 # program byte-identical)
    rec: Optional[RecBatch] = None
                                 # a model with recurrent layers only


STEP_ROWS_ALIGN = 128


def step_rows(max_seqs: int, n_verify: int, token_budget: int
              ) -> Tuple[int, ...]:
    """The row counts a served step is compiled at, smallest first: a
    step runs at the smallest that holds the tokens it was scheduled.
    The bottom rung holds every step without a prompt chunk
    (``max_seqs`` rows of ``n_verify`` tokens, rounded up to the MXU's
    128 rows); ``token_budget``, the scheduler's cap on a step, is the
    top one.  An engine whose bottom rung reaches its budget has the
    budget alone.  (Rungs between the two, doubling, were measured and
    left out: PERF.md section 6, PR 43.  A program more costs every
    set-up its trace, and the paged kernel's is seconds of Python.)"""
    rows = -(-max_seqs * max(1, n_verify) // STEP_ROWS_ALIGN) \
        * STEP_ROWS_ALIGN
    return (rows, token_budget) if rows < token_budget else (token_budget,)


class BatchStager:
    """Two alternating host-side staging buffer sets for RaggedBatch
    metadata (the reference's pinned "fast host buffer",
    ragged_wrapper.py).  The served loop builds step N+1's metadata
    while step N executes on device; alternating buffers guarantee the
    host never rewrites a set whose ``device_put`` transfer for the
    previous step may still be draining.  Two sets suffice for the one
    step ``InferenceEngine.step`` keeps in flight."""

    def __init__(self, token_budget: int, max_seqs: int, max_blocks: int,
                 n_verify: int = 1, n_chunks: int = 0):
        self.shape_key = (token_budget, max_seqs, max_blocks)
        # widest speculative verify window this engine may stage
        # (spec_max_draft + 1); batches slice the columns they use
        self.n_verify = max(1, n_verify)
        self._bufs = [self._alloc(token_budget, max_seqs, max_blocks,
                                  self.n_verify)
                      for _ in range(2)]
        if n_chunks:         # a model with recurrent layers (RecBatch)
            for b in self._bufs:
                b.update(self._alloc_rec(max_seqs, n_chunks))
        self._i = 0

    @staticmethod
    def _alloc(T: int, S: int, nb: int, nv: int) -> Dict[str, np.ndarray]:
        return {
            "token_ids": np.zeros(T, np.int32),
            "positions": np.zeros(T, np.int32),
            "seq_slot": np.zeros(T, np.int32),
            "block_tables": np.full((S, nb), -1, np.int32),
            "context_lens": np.zeros(S, np.int32),
            "logits_idx": np.full(S, -1, np.int32),
            "feedback_src": np.full(T, -1, np.int32),
            "seq_uids": np.zeros(S, np.uint32),
            "verify_idx": np.full((S, nv), -1, np.int32),
        }

    @staticmethod
    def _alloc_rec(S: int, n_chunks: int) -> Dict[str, np.ndarray]:
        return {
            "run_len": np.zeros(S, np.int32),
            "replay": np.zeros(S, bool),
            "chunks": np.tile(np.array([0, 0, S, 1, 0], np.int32),
                              (n_chunks, 1)),
        }

    def next_buffers(self) -> Dict[str, np.ndarray]:
        """The next staging set, reset to its fill values."""
        b = self._bufs[self._i]
        self._i = (self._i + 1) % len(self._bufs)
        b["token_ids"].fill(0)
        b["positions"].fill(0)
        b["seq_slot"].fill(0)
        b["block_tables"].fill(-1)
        b["context_lens"].fill(0)
        b["logits_idx"].fill(-1)
        b["feedback_src"].fill(-1)
        b["seq_uids"].fill(0)
        b["verify_idx"].fill(-1)
        if "chunks" in b:
            b["run_len"].fill(0)
            b["replay"].fill(False)
            b["chunks"][:] = (0, 0, len(b["run_len"]), 1, 0)
        return b


class StateManager:
    """Owns allocator + sequence table + the paged KV cache + the
    prefix-cache hash index (reference: DSStateManager ragged_manager.py).

    With ``prefix_cache=True``, every FULL block whose token chain is
    host-known is registered in a ``digest -> physical block`` index as
    it fills; :meth:`match_prefix` aliases an incoming prompt's longest
    cached block-aligned prefix into the new sequence's block table
    (refcounted, read-only) so prefill starts at the first uncached
    token.  Unreferenced cached blocks rest on the allocator's LRU
    cached-free pool until evicted for a fresh allocation."""

    def __init__(self, cfg: KVCacheConfig, max_seqs: int = 16,
                 max_blocks_per_seq: Optional[int] = None,
                 prefix_cache: bool = False):
        self.cfg = cfg
        self.max_seqs = max_seqs
        self.max_blocks_per_seq = max_blocks_per_seq or cfg.num_blocks
        self.prefix_cache = prefix_cache
        self.allocator = BlockedAllocator(cfg.num_blocks,
                                          on_evict=self._on_evict)
        # tpulint: ledger=allocator — every live descriptor owns blocks
        self.seqs: Dict[int, SequenceDescriptor] = {}
        self._slots: Dict[int, int] = {}       # uid -> batch row
        self._free_slots = list(range(max_seqs))
        # prefix-cache index: chain digest -> physical block (1:1), plus
        # the reverse map the eviction callback uses
        self._hash_index: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        # indexed blocks the allocator has reclaimed for new content
        self.prefix_evictions = 0
        # copy-on-write copies queued by match_prefix: (uid, src, dst).
        # The ENGINE drains these with a device block copy before the
        # next step dispatch (the scheduler itself never touches the
        # device); release() drops a flushed sequence's entries
        self.cow_pending: List[Tuple[int, int, int]] = []
        # fired with the uid AFTER a sequence's blocks/slot are
        # released — the engine closes the request's lifecycle record
        # here so no exit path (flush, preemption, deadline, direct
        # release) can leak an open record
        self.on_release: Optional[callable] = None
        # (digest, block) index entries registered since the last
        # build_batch began: a registration promises the block HOLDS
        # the hashed content, but the device write that honors it rides
        # the same step — if that step FAILS, the engine must
        # unregister exactly these entries (docs/SERVING.md "Failure
        # domains & recovery") or a later prefix match would alias
        # never-written KV
        self.round_registered: List[Tuple[bytes, int]] = []
        # tiered KV (tier.py, attached by the engine when kv_tier
        # resolves on; None = discard-on-evict, the pre-tier behavior).
        # Demotions and restages are QUEUES the engine drains around its
        # pre-dispatch device work — the scheduler itself never touches
        # the device or the disk
        self.tier = None
        # (parent_digest, chain_digest, block_tokens, block) — content
        # evicted from the index this round, payload still on device
        # until the next dispatched step overwrites the block
        self.tier_pending_demote: List[
            Tuple[bytes, bytes, Tuple[int, ...], int]] = []
        self.tier_pending_restage: List[RestageEntry] = []
        # uid -> outstanding restage ops; a uid in here is deferred by
        # the scheduler (admitted next round, once its chain re-indexes)
        self._restaging_uids: Dict[int, int] = {}
        self._revive_attempts: Dict[int, int] = {}
        # block -> (parent_digest, block_tokens): what _on_evict needs
        # to demote a block's content under its chain key; tracks
        # _block_hash keys exactly
        self._block_meta: Dict[int, Tuple[bytes, Tuple[int, ...]]] = {}
        # paged KV: [L, blocks+1, block_size, 2, Hkv, D] — the extra row is
        # the trash block that padding tokens' KV writes are routed to
        # (plus per-vector scales when cfg.quant != "none"); with
        # recurrent layers, beside it the state rows by slot
        self.kv = cfg.cache_zeros(max_seqs)

    # ---- sequence lifecycle ---------------------------------------------
    def get_or_create(self, uid: int) -> SequenceDescriptor:
        if uid not in self.seqs:
            if not self._free_slots:
                raise RuntimeError("No free sequence slots")
            self.seqs[uid] = SequenceDescriptor(uid=uid)
            self._slots[uid] = self._free_slots.pop(0)
        return self.seqs[uid]

    def slot(self, uid: int) -> int:
        return self._slots[uid]

    def release(self, uid: int) -> None:
        """(reference: flush engine_v2.py:242).  Blocks drop one
        reference each: a block whose content is index-registered and
        whose refcount hits zero retires to the cached-free LRU pool
        (matchable until evicted); the rest go back to the free list."""
        self._revive_attempts.pop(uid, None)
        seq = self.seqs.pop(uid, None)
        if seq is None:
            return
        if self.cow_pending:
            # a queued-but-undrained COW copy must die with its owner:
            # its dst block is freed right here and may be reallocated
            # before the engine would have executed the copy
            self.cow_pending = [c for c in self.cow_pending if c[0] != uid]
        if seq.blocks:
            # retire TAIL blocks into the cached-free LRU first: a chain
            # block is only matchable when every ancestor is still
            # indexed, so eviction (oldest-released first) must consume
            # chains leaf-first — a surviving cached prefix stays useful
            self.allocator.free(list(reversed(seq.blocks)))
        self._free_slots.append(self._slots.pop(uid))
        if self.on_release is not None:
            self.on_release(uid)

    # ---- prefix cache ----------------------------------------------------
    def _on_evict(self, block: int) -> None:
        """Allocator reclaimed a cached-free block: drop its index entry
        (nothing may match content about to be overwritten).  With a
        tier attached the content is queued for demotion instead of
        dying — the engine reads the block off the device BEFORE the
        step that overwrites it dispatches (same pre-dispatch ordering
        COW drains rely on)."""
        h = self._block_hash.pop(block, None)
        meta = self._block_meta.pop(block, None)
        if h is not None:
            self.prefix_evictions += 1
            self._hash_index.pop(h, None)
            if self.tier is not None and meta is not None:
                self.tier_pending_demote.append(
                    (meta[0], h, meta[1], block))

    def match_prefix(self, uid: int, tokens: List[int],
                     max_pool_take: Optional[int] = None) -> int:
        """Alias the longest cached block-aligned prefix of ``tokens``
        into a NEW sequence ``uid`` and return the number of prompt
        tokens served from the cache (0 = no match; the caller drops the
        matched tokens from its pending queue, so prefill starts at the
        first uncached token).

        ``max_pool_take`` caps how many blocks the match may REMOVE from
        the allocatable pool (reviving a cached-free block and the COW
        copy below each count; aliasing a live block is free) — the
        scheduler passes its unreserved headroom so a mid-round match
        can never consume blocks already promised to an earlier admit.

        At least one token is always left for the prefill step (the
        forward must run to produce the first logits).  When the cached
        chain covers the whole prompt, the last matched block therefore
        becomes a shared *partial* block from this sequence's view — it
        is copy-on-write'd: a fresh block is allocated, a device copy
        (queued on ``cow_pending``) duplicates the content, and the
        sequence's table points at the private copy while the original
        stays in the index for future matchers."""
        bs = self.cfg.block_size
        if (not self.prefix_cache or uid in self.seqs
                or not self._free_slots or len(tokens) <= bs):
            return 0
        if max_pool_take is None:
            max_pool_take = self.allocator.free_blocks
        hashes: List[bytes] = []
        blocks: List[int] = []
        takes = 0
        revive_run: List[bytes] = []
        # lazy digests: a cache-miss admission hashes ONE block and
        # stops, instead of pre-hashing the whole prompt
        digest_iter = iter_prefix_chain_digests(tokens, bs,
                                                self.max_blocks_per_seq)
        for h in digest_iter:
            b = self._hash_index.get(h)
            if b is None:
                if (self.tier is not None
                        and uid not in self._restaging_uids
                        and self._revive_attempts.get(uid, 0)
                        < _MAX_REVIVE_ATTEMPTS
                        and h in self.tier):
                    # the resident run ends in the tier: gather the
                    # contiguous spilled continuation, bounded by the
                    # pool headroom its destination blocks will consume.
                    # max_pool_take is the scheduler's UNRESERVED
                    # headroom — at <= 0 a restage dst would steal a
                    # block already promised to this round's admitted
                    # batch, so the revive waits for a later round
                    budget = min(max_pool_take,
                                 self.allocator.free_blocks)
                    if budget <= 0:
                        break
                    revive_run.append(h)
                    for h2 in digest_iter:
                        if len(revive_run) >= budget \
                                or h2 not in self.tier:
                            break
                        revive_run.append(h2)
                break
            t = 1 if self.allocator.refcount(b) == 0 else 0
            if takes + t > max_pool_take:
                break
            takes += t
            hashes.append(h)
            blocks.append(b)
        if revive_run and self._begin_restage(uid, revive_run):
            # the whole match ABORTS (no refs were taken): the caller
            # re-queues the request and the engine's restage drain
            # re-indexes the chain, so next round's match covers both
            # the resident run and the revived continuation
            return 0
        if not blocks:
            return 0
        for b in blocks:
            self.allocator.ref(b)
        matched = len(blocks) * bs
        if matched >= len(tokens):
            # full cover: re-schedule the last token so the step has
            # output; it re-writes position matched-1 inside the last
            # matched block -> copy-on-write (the rewrite is
            # content-equivalent but must not touch a shared block)
            matched = len(tokens) - 1
            if takes < max_pool_take and self.allocator.free_blocks >= 1:
                src = blocks[-1]
                [dst] = self.allocator.allocate(1)
                self.cow_pending.append((uid, src, dst))
                self.allocator.free([src])     # swap our alias for the copy
                blocks[-1] = dst
            else:
                # no room for the private copy: drop back to a
                # block-aligned match instead
                self.allocator.free([blocks.pop()])
                hashes.pop()
                matched = len(blocks) * bs
                if not blocks:
                    return 0
        seq = self.get_or_create(uid)
        seq.blocks = list(blocks)
        seq.seen_tokens = matched
        seq.cached_tokens = matched
        seq.chain = list(tokens[:matched])
        seq.hashes = hashes
        self._revive_attempts.pop(uid, None)
        return matched

    def _register_chain_blocks(self, seq: SequenceDescriptor) -> None:
        """Content-hash and index any chain blocks that just became full
        (called from build_batch after the chain is extended — so a block
        is matchable from the very step that fills it; device ordering
        makes the write land before any aliasing step's read)."""
        bs = self.cfg.block_size
        # a block holding a token the host has not read yet waits
        known = seq.deferred[0][1] if seq.deferred else len(seq.chain)
        while len(seq.hashes) < known // bs:
            k = len(seq.hashes)
            parent = seq.hashes[-1] if seq.hashes else _CHAIN_ROOT
            h = chain_hash(parent, seq.chain[k * bs:(k + 1) * bs])
            seq.hashes.append(h)
            if h not in self._hash_index:
                b = seq.blocks[k]
                self._hash_index[h] = b
                self._block_hash[b] = h
                self._block_meta[b] = (
                    parent, tuple(seq.chain[k * bs:(k + 1) * bs]))
                self.allocator.mark_cached(b)
                self.round_registered.append((h, b))

    def unregister_blocks(self, entries: List[Tuple[bytes, int]]) -> None:
        """Withdraw specific ``(digest, block)`` index registrations —
        the failure-recovery path for registrations whose backing KV
        write died with a failed step.  Unregistering is always SAFE
        (worst case a future match misses); only entries still mapping
        the same block are touched, so stale lists from older rounds
        are harmless."""
        for h, b in entries:
            if self._hash_index.get(h) != b:
                continue
            del self._hash_index[h]
            self._block_hash.pop(b, None)
            self._block_meta.pop(b, None)
            self.allocator.unmark_cached(b)

    def reset_prefix_cache(self) -> None:
        """Drop every index entry; cached-free blocks become plain free.
        (Used when cache CONTENT is invalidated, e.g. a failed step
        lost the donated pool and the engine starts from a zero one.)"""
        for b in list(self._block_hash):
            self.allocator.unmark_cached(b)
        self._block_hash.clear()
        self._hash_index.clear()
        self._block_meta.clear()
        self.cow_pending.clear()
        # invalidated content must not be demoted or restaged either:
        # dump the demote queue and free every pending restage's
        # destination (its tier entry was consumed — acceptable loss on
        # a content reset, which only happens before real traffic)
        self.tier_pending_demote.clear()
        for ent in self.tier_pending_restage:
            self.allocator.free([ent.dst])
        self.tier_pending_restage.clear()
        self._restaging_uids.clear()

    def prefix_digests(self) -> frozenset:
        """Hex digests resident in the prefix-cache index right now —
        the router's live cache-affinity key.  The same set
        ``engine.snapshot()["prefix_index"]`` freezes at snapshot time;
        score a prompt against it with :func:`prefix_chain_digests`."""
        return frozenset(h.hex() for h in self._hash_index)

    def pool_stats(self) -> Dict[str, int]:
        """Allocator-truth pool occupancy — the numbers the engine's
        ``serving_kv_*`` pull-gauges export (docs/OBSERVABILITY.md
        "Device & compiler telemetry").  Computed from the SAME state
        ``BlockedAllocator.assert_invariants`` checks, so the scheduler
        fuzz can cross-check gauge == truth after every op; pure host
        ints, safe to read at any phase boundary."""
        al = self.allocator
        return {
            "free": len(al._free),
            "cached_free": al.cached_free_blocks,
            "referenced": al.referenced_blocks,
            "total": al.total_blocks,
            "peak_referenced": al.peak_referenced_blocks,
            "prefix_index_entries": len(self._hash_index),
            "live_seqs": len(self.seqs),
            "free_slots": len(self._free_slots),
        }

    def take_cow_copies(self) -> List[Tuple[int, int]]:
        """Hand the queued (src, dst) copy-on-write block copies to the
        engine (which executes them on device BEFORE the next step) and
        clear the queue."""
        out = [(s, d) for _, s, d in self.cow_pending]
        self.cow_pending.clear()
        return out

    # ---- tier plumbing (tier.py; docs/KV_TIERING.md) ---------------------
    def _begin_restage(self, uid: int, run: List[bytes]) -> bool:
        """Start restaging a contiguous run of tiered chain digests for
        a deferred request: consume each tier entry (NVMe reads are
        queued inside ``begin_revive`` so they overlap the scheduler
        round) and allocate its destination block, held at refcount 1
        until the engine's drain commits or aborts the upload."""
        if not run or self.allocator.free_blocks < len(run):
            return False
        if len(self._revive_attempts) > 1024:
            # bounded: uids cancelled while still queued never release()
            self._revive_attempts.pop(next(iter(self._revive_attempts)))
        self._revive_attempts[uid] = \
            self._revive_attempts.get(uid, 0) + 1
        started = 0
        for h in run:
            op = self.tier.begin_revive(h)
            if op is None:
                break
            [dst] = self.allocator.allocate(1)
            self.tier_pending_restage.append(
                RestageEntry(uid, h, op.parent, op.tokens, dst, op))
            started += 1
        if not started:
            return False
        self._restaging_uids[uid] = \
            self._restaging_uids.get(uid, 0) + started
        return True

    def restaging(self, uid: int) -> bool:
        """Whether ``uid`` has restage ops in flight — the scheduler
        defers (keeps queued, schedules nothing for) such a request."""
        return uid in self._restaging_uids

    def commit_restage(self, ent: RestageEntry) -> None:
        """The engine verified and uploaded ``ent``'s payload into
        ``ent.dst``: register the digest and retire the block to the
        cached-free pool (matchable, evictable — restaged content IS
        cache content).  Joins ``round_registered`` so a failed step
        unwinds the registration like any other."""
        b, h = ent.dst, ent.digest
        if h not in self._hash_index:
            self._hash_index[h] = b
            self._block_hash[b] = h
            self._block_meta[b] = (ent.parent, tuple(ent.tokens))
            self.allocator.mark_cached(b)
            self.round_registered.append((h, b))
        # a racing prefill may have re-registered the digest while the
        # restage was in flight — our copy is then redundant and the
        # free below retires it straight to the plain free list
        self.allocator.free([b])
        self._restage_done(ent.uid)

    def abort_restage(self, ent: RestageEntry) -> None:
        """Verification failed (or the payload died with its spill
        file): free the destination unregistered — the request falls
        back to a plain re-prefill, which rebuilds the chain."""
        self.allocator.free([ent.dst])
        self._restage_done(ent.uid)

    def _restage_done(self, uid: int) -> None:
        n = self._restaging_uids.get(uid, 0) - 1
        if n <= 0:
            self._restaging_uids.pop(uid, None)
        else:
            self._restaging_uids[uid] = n

    def take_tier_demotes(self) -> List[Tuple[bytes, bytes,
                                              Tuple[int, ...], int]]:
        """Hand the queued (parent, digest, tokens, block) demotions to
        the engine, which reads each block off the device BEFORE the
        step that overwrites it dispatches."""
        out = self.tier_pending_demote
        self.tier_pending_demote = []
        return out

    def stage_chain_demotes(self, uid: int) -> int:
        """Queue a device→tier COPY for every still-indexed full block
        of ``uid``'s chain and return how many were queued — the
        prefill→decode handoff's KV export (docs/SERVING.md
        "Disaggregated pools & elasticity").  Unlike the eviction path
        (``_on_evict``) the blocks stay indexed and cached-free on this
        replica: the tier entry is a copy ``export_tier_chain`` can
        ship, not a move.  Blocks already tiered (or never registered —
        the partial tail, cache-off runs) are skipped; the destination
        re-prefills whatever the exported run doesn't cover."""
        seq = self.seqs.get(uid)
        if seq is None or self.tier is None:
            return 0
        n = 0
        for b in seq.blocks:
            h = self._block_hash.get(b)
            meta = self._block_meta.get(b)
            if h is None or meta is None or h in self.tier:
                continue
            self.tier_pending_demote.append((meta[0], h, meta[1], b))
            n += 1
        return n

    def take_tier_restage(self) -> List[RestageEntry]:
        out = self.tier_pending_restage
        self.tier_pending_restage = []
        return out

    # ---- scheduling query ------------------------------------------------
    @property
    def max_context_tokens(self) -> int:
        return self.max_blocks_per_seq * self.cfg.block_size

    def context_remaining(self, uid: int) -> int:
        seq = self.seqs.get(uid)
        seen = seq.seen_tokens if seq else 0
        return self.max_context_tokens - seen

    def can_schedule(self, uid: int, new_tokens: int) -> bool:
        """(reference: can_schedule engine_v2.py:184)."""
        seq = self.seqs.get(uid) or SequenceDescriptor(uid=uid)
        need = seq.blocks_needed(new_tokens, self.cfg.block_size)
        slot_ok = uid in self._slots or bool(self._free_slots)
        return (need <= self.allocator.free_blocks and slot_ok
                and new_tokens <= self.context_remaining(uid))

    def resolve_draft(self, uid: int, accepted: int) -> int:
        """Resolve a speculative verify step for ``uid``: commit the
        ``accepted`` leading draft tokens and REWIND the write cursor
        over the rejected tail (the engine's accept-longest-matching-
        prefix check decides ``accepted``; docs/SERVING.md "Speculative
        decoding").

        The rejected rows' KV stays physically in place but becomes
        dead weight the very next scheduled token overwrites: rollback
        is just ``seen_tokens``/chain truncation, no device work.  The
        trailing blocks allocated for the rejected rows are kept — they
        are private by construction (registration was deferred while
        the draft was unresolved, so no other sequence can alias them)
        and the growing sequence refills them.  Prefix-cache
        registration of chain blocks completed by the window happens
        HERE, post-rollback, so the index only ever maps hashes to
        committed content.

        Returns the number of rejected tokens rolled back (0 when the
        sequence died mid-flight or carried no unresolved draft —
        idempotent by construction)."""
        seq = self.seqs.get(uid)
        if seq is None or not seq.draft_len:
            return 0
        k = seq.draft_len
        seq.draft_len = 0
        if not 0 <= accepted <= k:
            raise ValueError(f"accepted={accepted} outside 0..{k}")
        rejected = k - accepted
        if rejected:
            seq.seen_tokens -= rejected
            if not seq.chain_broken:
                del seq.chain[-rejected:]
        if self.prefix_cache and not seq.chain_broken:
            self._register_chain_blocks(seq)
        return rejected

    def resolve_feedback(self, uid: int, sid: int, token: int) -> None:
        """Step ``sid``'s collect read ``uid``'s sample: write it into
        the chain position a later batch fed from the device (no-op
        when none was).  The block it may complete is hashed by the
        sequence's next ``build_batch``, in that step's ledger."""
        seq = self.seqs.get(uid)
        if seq is not None and seq.deferred and seq.deferred[0][0] == sid:
            seq.chain[seq.deferred.pop(0)[1]] = int(token)

    def rewind(self, uid: int, n_tokens: int = 1) -> None:
        """Take back ``uid``'s last ``n_tokens`` scheduled rows (a row
        launched ahead whose result is thrown away, or whose fed token
        never arrived): the write cursor and the chain move back, the
        rows' KV is overwritten by whatever is scheduled next, blocks
        already allocated for them stay with the sequence (as in
        :meth:`resolve_draft`).  A recurrent state is not overwritten,
        it is advanced: the sequence notes how far its state is ahead
        (``state_ahead``), and the row fed again is a replay that reads
        the state it produced and leaves it where one pass would."""
        seq = self.seqs[uid]
        seq.seen_tokens -= n_tokens
        if self.cfg.recurrent is not None:
            # a recurrent state was advanced by those rows and cannot be
            # overwritten: the row scheduled next replays (build_batch)
            seq.state_ahead += n_tokens
        if not seq.chain_broken:
            del seq.chain[-n_tokens:]
            seq.deferred = [d for d in seq.deferred
                            if d[1] < len(seq.chain)]

    # ---- batch building --------------------------------------------------
    def build_batch(self, requests: List[tuple], token_budget: int,
                    stager: Optional[BatchStager] = None,
                    draft_lens: Optional[Dict[int, int]] = None,
                    n_verify: int = 1,
                    deferred_from: Optional[Dict[int, int]] = None
                    ) -> RaggedBatch:
        """requests: [(uid, list_of_new_token_ids)]; allocates KV blocks and
        produces the padded device metadata.  A token id of
        :data:`FEEDBACK_TOKEN` (single-token decode continuations only)
        marks a deferred on-device token: the host stages id 0 and
        records the sequence's slot in ``feedback_src`` so the jitted
        step substitutes the previous step's sample.  With ``stager``,
        metadata is written into its alternating pre-allocated buffers
        instead of fresh arrays.

        ``draft_lens``: per-uid count of trailing SPECULATIVE tokens in
        that request's token list (a decode verify window ``[fed token,
        draft_1..draft_k]``).  The window's KV rows are written like any
        chunked prefill, but the sequence is marked draft-pending:
        prefix-cache registration defers and the engine's collect calls
        :meth:`resolve_draft` to commit or rewind.  ``n_verify > 1``
        emits ``verify_idx`` ([max_seqs, n_verify]) so the compiled step
        samples every window position (-1 pads; non-drafting rows use
        column 0 = their last token).

        ``deferred_from``: uid -> the step whose collect will read this
        batch's :data:`FEEDBACK_TOKEN` row for that uid.  Such a row
        keeps its place in the chain (``SequenceDescriptor.deferred``)
        instead of breaking it: the host learns the token one step
        late, in order."""
        T = token_budget
        rc = self.cfg.runs
        n_chunks = 0
        # fresh registration ledger for this round (see round_registered)
        self.round_registered = []
        bufs = self._host_buffers(T, n_verify, stager)
        token_ids = bufs["token_ids"]
        positions = bufs["positions"]
        seq_slot = bufs["seq_slot"]
        block_tables = bufs["block_tables"]
        context_lens = bufs["context_lens"]
        logits_idx = bufs["logits_idx"]
        feedback_src = bufs["feedback_src"]
        seq_uids = bufs["seq_uids"]
        verify_idx = bufs["verify_idx"]

        # keep existing sequences' tables valid even if not in this batch
        for uid, seq in self.seqs.items():
            s = self._slots[uid]
            block_tables[s, :len(seq.blocks)] = seq.blocks
            context_lens[s] = seq.seen_tokens
            seq_uids[s] = np.uint32(uid & 0xFFFFFFFF)

        cursor = 0
        n_seqs = 0
        for uid, new_tokens in requests:
            n = len(new_tokens)
            if n == 0:
                continue
            k_draft = draft_lens.get(uid, 0) if draft_lens else 0
            if k_draft and (k_draft >= n or n_verify <= k_draft):
                raise ValueError(
                    f"uid {uid}: {k_draft} drafts need a {k_draft + 1}-"
                    f"token window and n_verify > {k_draft}")
            if cursor + n > T:
                raise ValueError(f"token budget {T} exceeded")
            seq = self.get_or_create(uid)
            if seq.draft_len:
                raise ValueError(
                    f"uid {uid}: unresolved draft window "
                    f"({seq.draft_len} tokens) — resolve_draft must run "
                    "before more tokens are scheduled")
            if n > self.context_remaining(uid):
                raise ValueError(
                    f"uid {uid}: {n} new tokens exceed remaining context "
                    f"({self.context_remaining(uid)} of "
                    f"{self.max_context_tokens})")
            need = seq.blocks_needed(n, self.cfg.block_size)
            if need:
                seq.blocks.extend(self.allocator.allocate(need))
            s = self._slots[uid]
            block_tables[s, :len(seq.blocks)] = seq.blocks
            if n == 1 and new_tokens[0] == FEEDBACK_TOKEN:
                # deferred decode token: value comes from the previous
                # step's on-device sample at this sequence's slot
                token_ids[cursor] = 0
                feedback_src[cursor] = s
                src = deferred_from.get(uid) if deferred_from else None
                if src is not None and not seq.chain_broken:
                    seq.deferred.append((src, len(seq.chain)))
                    seq.chain.append(FEEDBACK_TOKEN)
                else:
                    # the host never learns this KV row's token id in
                    # order, so content hashing stops here
                    seq.chain_broken = True
            else:
                token_ids[cursor:cursor + n] = new_tokens
                if not seq.chain_broken:
                    # the chain is kept even with the prefix cache off:
                    # it is the host-known "KV contents in order" record
                    # that preemption-by-eviction re-queues (the index
                    # registration below stays cache-gated)
                    seq.chain.extend(int(t) for t in new_tokens)
            if rc is not None:
                bufs["run_len"][s] = n
                if seq.state_ahead:
                    if n != 1 or seq.state_ahead != 1:
                        raise ValueError(
                            f"uid {uid}: its recurrent state is "
                            f"{seq.state_ahead} rows ahead; exactly one "
                            "row can be replayed")
                    bufs["replay"][s] = True
                    seq.state_ahead = 0
                for at in range(0, n if n > 1 else 0, rc.chunk):
                    if n_chunks >= len(bufs["chunks"]):
                        raise ValueError(
                            f"more than {rc.scan_runs} runs of several "
                            "tokens in a step")
                    rows = min(rc.chunk, n - at)
                    bufs["chunks"][n_chunks] = (cursor + at, rows, s,
                                                at == 0, at + rows == n)
                    n_chunks += 1
            positions[cursor:cursor + n] = np.arange(
                seq.seen_tokens, seq.seen_tokens + n)
            seq_slot[cursor:cursor + n] = s
            seq.seen_tokens += n
            context_lens[s] = seq.seen_tokens
            seq_uids[s] = np.uint32(uid & 0xFFFFFFFF)
            logits_idx[s] = cursor + n - 1
            if n_verify > 1:
                # column 0 is always the row's last token (the legacy
                # sample); a drafting row's window spans its trailing
                # k_draft + 1 tokens
                verify_idx[s, 0] = cursor + n - 1
                if k_draft:
                    verify_idx[s, :k_draft + 1] = np.arange(
                        cursor + n - 1 - k_draft, cursor + n)
                    seq.draft_len = k_draft
            cursor += n
            n_seqs += 1
            if self.prefix_cache and not seq.chain_broken \
                    and not seq.draft_len:
                # draft-pending sequences defer registration to
                # resolve_draft: a shared block must never hold tokens
                # that may roll back
                self._register_chain_blocks(seq)

        return self._device_batch(bufs, cursor, n_seqs, n_verify)

    def _host_buffers(self, T: int, n_verify: int,
                      stager: Optional[BatchStager] = None
                      ) -> Dict[str, np.ndarray]:
        """A step's host arrays at ``T`` rows, at their fill values:
        the stager's next set cut to ``T`` rows where it is as wide as
        this manager and at least as long, else fresh ones."""
        S, nb = self.max_seqs, self.cfg.num_blocks
        rc = self.cfg.runs
        if stager is not None and stager.shape_key[1:] == (S, nb) \
                and stager.shape_key[0] >= T \
                and stager.n_verify >= n_verify:
            bufs = dict(stager.next_buffers())
            for k in ("token_ids", "positions", "seq_slot", "feedback_src"):
                bufs[k] = bufs[k][:T]
            if rc is not None:
                bufs["chunks"] = bufs["chunks"][:rc.n_chunks(T)]
            return bufs
        # block_tables' -1 pad: a negative gather wraps to the KV
        # array's last row, which is the zeroed trash block — padded
        # columns can never alias a live block (they are also masked by
        # position)
        bufs = BatchStager._alloc(T, S, nb, max(1, n_verify))
        if rc is not None:
            bufs.update(BatchStager._alloc_rec(S, rc.n_chunks(T)))
        return bufs

    @staticmethod
    def _device_batch(bufs: Dict[str, np.ndarray], n_tokens: int,
                      n_seqs: int, n_verify: int) -> RaggedBatch:
        T = len(bufs["token_ids"])
        return RaggedBatch(
            token_ids=jnp.asarray(bufs["token_ids"]),
            positions=jnp.asarray(bufs["positions"]),
            seq_slot=jnp.asarray(bufs["seq_slot"]),
            token_valid=jnp.asarray(np.arange(T) < n_tokens),
            block_tables=jnp.asarray(bufs["block_tables"]),
            context_lens=jnp.asarray(bufs["context_lens"]),
            logits_idx=jnp.asarray(bufs["logits_idx"]),
            n_tokens=n_tokens, n_seqs=n_seqs,
            feedback_src=jnp.asarray(bufs["feedback_src"]),
            seq_uids=jnp.asarray(bufs["seq_uids"]),
            verify_idx=(jnp.asarray(bufs["verify_idx"][:, :n_verify])
                        if n_verify > 1 else None),
            rec=None if "chunks" not in bufs else RecBatch(
                run_len=jnp.asarray(bufs["run_len"]),
                replay=jnp.asarray(bufs["replay"]),
                chunks=jnp.asarray(bufs["chunks"])))

    def blank_batch(self, rows: int, n_verify: int = 1) -> RaggedBatch:
        """A step of ``rows`` rows that holds no token, with the shapes
        and types :meth:`build_batch` gives one: what a serving program
        is compiled from ahead of its first step.  Touches no
        sequence."""
        return self._device_batch(self._host_buffers(rows, n_verify),
                                  0, 0, n_verify)
