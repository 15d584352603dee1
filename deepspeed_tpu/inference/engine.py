"""Serving engine: continuous ragged batching with Dynamic SplitFuse.

TPU-native re-design of the reference inference engines
(``InferenceEngineV2.put/query/flush`` engine_v2.py:107/158/242,
schedulability checks ``can_schedule`` :184 + ``scheduling_utils.py``;
v1 ``deepspeed.init_inference`` engine.py:41 is subsumed — there is no
kernel-injection step because models are born with fused TPU kernels).

Dynamic SplitFuse (the FastGen scheduling insight,
blogs/deepspeed-fastgen): every step runs a FIXED token budget mixing
decode tokens (1/seq) with prompt chunks.  On TPU this is doubly right:
the forward is compiled once for [budget] and never re-specializes.

API:
    eng = InferenceEngine(model, InferenceConfig(...))
    eng.put(uid, prompt_tokens)      # enqueue / continue a request
    out = eng.step()                 # one SplitFuse step -> {uid: token}
    eng.generate(prompts, sampling)  # convenience loop
    eng.flush(uid)                   # free a finished sequence
"""

from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.mesh import FSDP_AXIS, MeshTopology, TENSOR_AXIS
from ..models.transformer import Model, TransformerConfig
from ..ops.paged_attention import (LONG, SHORT, group_steps, kv_group,
                                   tile_counts)
from ..telemetry import (AnomalyConfig, AnomalyMonitor, CounterDictView,
                         DeviceTelemetry, FlightRecorder, MetricsRegistry,
                         ProfilerCapture, RequestTracker, SloObjective,
                         SloTracker, SpanTracer, default_serving_detectors,
                         default_slo_objectives)
from ..utils.logging import logger
from .failures import (FATAL_ENGINE, POISON_STEP,
                       DispatchTimeoutError, EngineDeadError,
                       FailureConfig, FailurePolicy, InjectedFault,
                       InjectedTimeout, bisect_groups, classify_failure)
from .model import (fold_projection, fold_projections, moe_stat_rows,
                    pipelined_ragged_step, ragged_forward)
from .overload import (AdmissionVerdict, OverloadConfig, RequestMeta,
                       admission_decision, effective_priority,
                       select_victim)
from .ragged.state import (FEEDBACK_TOKEN, BatchStager, KVCacheConfig,
                           RaggedBatch, RunCut, StateManager, step_rows)
from .sampler import SamplingParams, sample_rows


@dataclasses.dataclass
class InferenceConfig:
    """(reference: RaggedInferenceEngineConfig inference/v2/config_v2.py —
    DSStateManagerConfig: max_ragged_batch_size/token budget,
    memory_config num blocks)."""
    token_budget: int = 256          # tokens per step (SplitFuse budget)
    max_seqs: int = 8                # concurrent sequences
    kv_block_size: int = 64
    num_kv_blocks: int = 256         # pool size
    max_seq_len: Optional[int] = None   # default: model max
    kv_dtype: object = jnp.bfloat16
    param_dtype: object = jnp.bfloat16
    # paged attention implementation: "pallas" is the streaming kernel
    # (ops/paged_attention.py; a latent layer's: ops/mla.py), "xla" the
    # gather formulation it is tested against.  "auto" is a rule,
    # settled at construction: "pallas" on a TPU backend (where it wins
    # every shape the chip has timed, and the XLA path's gather does not
    # fit beside a 7B model), "xla" everywhere else (there the kernel
    # only runs interpreted)
    attn_impl: str = "auto"
    # "int8" | "fp8": store the paged KV cache quantized (one scale per
    # written token/head vector, per-block layout).  Halves (int8) the
    # dominant HBM stream of long-context decode; all paged-attention
    # paths consume it natively (reference analog:
    # ZeRO-Inference KV quantization, deepspeed/inference/quantization/)
    kv_quant: Optional[str] = None
    # --- ZeRO-Inference (reference: inference/quantization, README:35) --
    # "int8" | "int4": group-quantized weights, one layer dequantized at
    # a time inside the forward (2-4x smaller resident model)
    weight_quant: Optional[str] = None
    # mixed-input GEMM (int8 weight x bf16 act, dequant in VMEM —
    # ops/mixed_gemm.py; reference: cuda_linear fp6 GEMM): "on" runs the
    # projections through it and raises unless the weights are in the
    # row-wise int8 or packed row-wise int4 layout it consumes; "off"
    # (the default: the one timing the chip has given chose it)
    # dequantizes in the fused XLA path
    mixed_gemm: str = "off"
    quantize_embeddings: bool = False
    # NVMe per-layer weight streaming (reference:
    # partitioned_param_swapper.py:290 / ZeRO-Inference NVMe): directory
    # to spill the per-layer (quantized, when weight_quant is set)
    # payloads; the forward fetches one layer at a time via io_callback,
    # so HBM never holds the block weights.
    weight_stream: Optional[str] = None
    # KV-cache donation across steps: "on" aliases the cache in place
    # (the right call wherever HBM is the constraint), "off" lets XLA
    # allocate a fresh result cache per step.  "auto" donates everywhere
    # EXCEPT on the CPU backend: XLA:CPU blocks a dispatch whose donated
    # operand is still being produced by the in-flight step (measured:
    # chained donated calls serialize at full step latency), which
    # would silently turn the step that runs ahead (``step()``) back
    # into the synchronous loop.  Host RAM pays one transient cache copy
    # instead.
    kv_donate: str = "auto"
    # --- overlapped & quantized multi-chip collectives (comm/overlap.py;
    # T3 arxiv 2401.16677, EQuARX arxiv 2506.17615; docs/SERVING.md
    # "Overlapped & quantized collectives") ------------------------------
    # "on": the TP hot path's two heavy collectives — the MLP
    # down-projection's partial-sum all-reduce and the unembed's logits
    # all-gather — run tile-decomposed inside shard_map, so XLA can
    # schedule tile i's comm behind tile i+1's GEMM instead of the
    # serial GSPMD collective after the whole GEMM.  Bitwise-identical
    # to "off" (the default exact rung reduces each tile with the same
    # psum; the gather is pure data movement) — asserted by parity
    # tests on 1-chip and simulated 8-device meshes.  "auto": on
    # whenever the mesh has a tensor axis and the shapes divide it;
    # single-chip auto resolves off (there is nothing to overlap).
    # "on" without a tensor axis is a loud no-op, never an error — the
    # same config must run on 1 chip and on the pod.
    comm_overlap: str = "auto"
    # tiles per decomposed collective (clamped to divide the row dim)
    comm_tiles: int = 4
    # EQuARX-style quantized allreduce for the TP activation reduction:
    # "int8" | "int4" wire payloads — bits/8 of the exact bytes on the
    # wire (the telemetry reconciliation test asserts exactly that
    # ratio).  Applies to the down-projection all-reduce only; the
    # unembed GATHER always stays exact, because a perturbed logit
    # could flip a greedy argmax.  Meshes/shapes that cannot support
    # the quantized wire degrade LOUDLY to the exact reduction (the
    # PR-1 contract for every quantized collective).
    comm_quant: Optional[str] = None
    # automatic prefix caching over the paged KV cache: full KV blocks
    # are content-hashed by their token chain (rolling hash of
    # (parent, block_tokens)) and an incoming prompt's longest cached
    # block-aligned prefix is aliased — refcounted, read-only — into its
    # block table, so prefill starts at the first uncached token
    # (copy-on-write when a sequence must append into a shared block).
    # Matching is pure host-side hashing: a miss adds ZERO device work,
    # and blocks only alias within this engine's own pool, so "auto"
    # (default) simply enables it on every backend; "off" disables
    # (strict step-for-step reproduction of a cache-less engine), "on"
    # forces.  Hit counters: engine.timings cached_tokens/prefix_hits/
    # prompt_tokens, query()["cached_tokens"].
    prefix_cache: str = "auto"
    # span tracing of the serving loop (telemetry/tracer.py): host-side
    # perf_counter_ns spans for every pipeline stage (schedule / stage /
    # dispatch / wait / readback, COW drains, prefix-cache lookups) into
    # a preallocated ring buffer; export with
    # ``engine.tracer.export_chrome_trace(path)`` and open in Perfetto.
    # Off by default: the per-span cost is tiny but nonzero.  The
    # metrics registry (``engine.metrics``) and per-request lifecycle
    # records (``engine.request_metrics()``) are ALWAYS on — they are
    # host-side counter bumps that never touch device arrays.
    trace: bool = False
    trace_capacity: int = 1 << 16   # spans retained (ring wraps beyond)
    # device & compiler telemetry (telemetry/device.py,
    # docs/OBSERVABILITY.md "Device & compiler telemetry"): per-program
    # ``compiled.cost_analysis()`` (flops / bytes / HLO size, probed
    # once per executable-cache fill via an explicit AOT compile of the
    # already-warm program), derived ``serving_mfu`` /
    # ``serving_hbm_bw_util`` pull-gauges computed from the existing
    # step timings at export time, and ``device.memory_stats()`` polled
    # at phase boundaries (health checks, dumps).  Off by default: the
    # cost probe pays one duplicate compile per program — "on" is what
    # the future autotuner (ROADMAP item 4) opts into; "auto" defers to
    # the engine and today resolves OFF.  The compile/retrace COUNTERS,
    # the KV-pool pull-gauges, and the flight recorder are always on —
    # they are host counter bumps and read-time probes that cost the
    # hot path nothing.
    device_telemetry: str = "auto"
    # streaming anomaly detection (telemetry/anomaly.py,
    # docs/OBSERVABILITY.md "Anomaly detection & deep capture"): EWMA+
    # MAD / rolling-percentile / threshold detectors over per-step
    # signals the loop already computes — step interval / device /
    # wait / host ms, TTFT/TPOT, runtime retraces, KV-referenced
    # slope, prefix hit rate, spec acceptance.  A fire is note()d into
    # the flight recorder, counted
    # (``serving_anomalies_total{signal=...}``), surfaced through
    # ``engine.health()`` (sustained fires => degraded), and —
    # cooldown- and budget-limited — arms a deep-capture window.  Off
    # costs literally nothing: no monitor is constructed, no clock is
    # read; on reuses the timestamps the loop already takes (the
    # zero-extra-clock-reads bar is tested).  "auto" resolves OFF
    # today — the ROADMAP-4 autotuner is the intended flipper.
    anomaly: str = "auto"
    anomaly_cfg: Optional["AnomalyConfig"] = None
    # deep-capture output directory (telemetry/profiler.py): armed
    # captures record a bounded ``jax.profiler`` device trace + the
    # window's host spans + a flight dump under
    # ``<profile>/capture_<n>_<reason>/``, which
    # ``tools/tracemerge.py`` merges into ONE Perfetto timeline.
    # Setting ``profile`` with ``profile_steps > 0`` arms an explicit
    # window over the first ``profile_steps`` engine steps;
    # ``profile_steps = 0`` just designates the
    # directory (anomaly-armed captures land there).  Explicit windows
    # can also be armed any time via ``engine.capture(steps=N)``.
    # Backends/builds without profiler support degrade loudly: the
    # window completes host-only and the merge says so.
    profile: Optional[str] = None
    profile_steps: int = 4
    # model-free speculative decoding (inference/spec_decode.py,
    # docs/SERVING.md "Speculative decoding"): an n-gram prompt-lookup
    # proposer drafts up to ``spec_max_draft`` continuation tokens per
    # decoding sequence from the request's OWN prompt + emitted tokens
    # (zero extra weights), a ragged verify step scores the window of
    # 1 + k positions in ONE dispatch, and the longest draft prefix
    # matching what the model samples anyway is accepted (rejected
    # tokens roll the paged-KV write cursor back — host bookkeeping
    # only).  Output streams are EXACTLY the non-speculative ones,
    # greedy and seeded (the verify step samples each window position
    # with the same (uid, position)-folded key the stepwise path uses).
    # "on" enables; "off" disables (n_verify=1 — the compiled step is
    # byte-identical to a pre-spec engine); "auto" defers to the
    # engine: today it resolves OFF — acceptance is workload-dependent
    # and the autotuner (ROADMAP item 4) is meant to flip it from the
    # measured acceptance_rate/draft-length profiles this engine
    # records.  Forced off under weight_stream (a verify window is
    # worthless when each layer streams from NVMe at step latency).
    spec_decode: str = "auto"
    # widest draft window per sequence per verify step; the proposer
    # may draft fewer (budget/context capped), and an empty draft
    # degrades the row to a plain 1-token decode
    spec_max_draft: int = 4
    # overload policy (inference/overload.py, docs/SERVING.md "Surviving
    # overload"): bounded admission queue + shed policy, priority /
    # deadline-aware scheduling with anti-starvation aging,
    # preemption-by-eviction when the block pool or slot table starves a
    # higher tier, and per-step chunked-prefill budget caps.  None uses
    # OverloadConfig() defaults, which reproduce the legacy cooperative
    # behavior exactly (unbounded queue, no chunk cap, preemption inert
    # while every request shares one priority tier).
    overload: Optional[OverloadConfig] = None
    # failure-domain policy (inference/failures.py, docs/SERVING.md
    # "Failure domains & recovery"): every device dispatch/readback
    # runs under a watchdog deadline (``FailureConfig.
    # dispatch_timeout_ms`` — "auto" scales it from the observed step
    # latency in the metrics registry), every raised XLA error or
    # expiry routes through ONE classifier seam, and the verdict
    # degrades the failure to a request-level terminal status instead
    # of a wedged or dead process: transient errors re-queue the batch
    # with backoff, deterministic step failures bisect the batch until
    # the poison request is quarantined (terminal status ``failed``),
    # and a dead backend raises EngineDeadError — from which
    # ``snapshot()`` + ``InferenceEngine.restore()`` warm-restart the
    # open work token-identically.  None uses FailureConfig()
    # defaults (auto watchdog, engaged after a calibration warmup).
    failure: Optional[FailureConfig] = None
    # tiered KV cache (inference/ragged/tier.py, docs/KV_TIERING.md):
    # prefix-cache eviction demotes full content-hashed blocks into a
    # bounded host-RAM ring instead of discarding them, with ring
    # overflow spilled to NVMe files through ops/aio.py; a match_prefix
    # digest hit in the tier restages the chain asynchronously —
    # overlapping the dispatch-ahead window the way COW drains do — so
    # a spilled-chain hit pays block uploads, not a re-prefill.  "on"
    # enables (requires prefix_cache != "off"); "off" disables; "auto"
    # defers to the engine and today resolves OFF (the tier trades host
    # RAM/disk for recompute — the ROADMAP-4 autotuner is the intended
    # flipper).
    kv_tier: str = "auto"
    # host-RAM ring budget; overflow spills to kv_tier_dir (if set)
    kv_tier_ram_mb: float = 64.0
    # NVMe spill directory — None (default) runs the tier RAM-only;
    # spill files are named <chain_digest>.kv and are useless without
    # the owning process's in-memory index (restart discards them)
    kv_tier_dir: Optional[str] = None
    kv_tier_nvme_mb: float = 256.0
    # per-class SLO scorecard + error-budget burn-rate signals
    # (telemetry/slo.py, docs/OBSERVABILITY.md "SLOs & error budgets"):
    # "on" attaches an SloTracker to the request tracker's existing
    # first-token / close-out stamp sites (zero new clock reads — the
    # scorecard evaluates timestamps already on the record) and, when
    # the anomaly plane is also on, registers the per-class
    # ``slo_burn_rate_<class>`` burn detectors into its catalog (a
    # burning budget breadcrumbs the flight recorder and arms a
    # budgeted capture like any other anomaly).  Off constructs
    # nothing; "auto" resolves OFF today.
    slo: str = "auto"
    # class -> SloObjective map; None = default_slo_objectives()
    slo_objectives: Optional[Dict[str, "SloObjective"]] = None


class _InFlight(NamedTuple):
    """One dispatched-but-unread serving step: the on-device [max_seqs]
    sample array, the (uid, slot) emission list frozen at dispatch time
    (slots may be reassigned by the time the step is collected), and the
    engine-wide dispatch sequence number (feedback markers name the step
    whose sample array they defer to)."""
    toks: jax.Array
    emit: Tuple[Tuple[int, int], ...]
    sid: int
    # every uid the step scheduled tokens for (emitting or not): a
    # sequence with an uncollected scheduled step is never a preemption
    # victim — its KV blocks are still being written
    uids: Tuple[int, ...] = ()
    # speculative verify windows this step carries: uid -> the drafted
    # token tuple (the window is [fed token, *drafts]).  Acceptance is
    # decided at collect by prefix-comparing the drafts against the
    # [S, W] sample array; frozen here because the proposer's state
    # moves on while the step is in flight
    drafts: Tuple[Tuple[int, Tuple[int, ...]], ...] = ()
    # the sampling stop token at dispatch time: a stop landing INSIDE
    # an accepted draft truncates the emission at collect exactly where
    # the stepwise engine would have stopped feeding
    stop: Optional[int] = None
    # prefix-cache (digest, block) entries THIS step's build registered:
    # their content promise is honored by this step's KV writes, so a
    # failure at collect must withdraw exactly these (the dispatch-
    # failure path uses the state manager's live round ledger instead)
    registered: Tuple[Tuple[bytes, int], ...] = ()
    # tokens the step scheduled (a router makes top_k assignments each)
    n_tokens: int = 0
    # the dispatch rode a first-call program (compile may still be in
    # flight on async backends): its readback runs unguarded too
    cold: bool = False


class InferenceEngine:
    """Serving engine.  With ``topology`` (a :class:`MeshTopology`), the
    model is served SPMD over the mesh: weights follow the training-side
    logical-axis TP rules (Megatron-style head/mlp/vocab splits —
    reference: ``module_inject/auto_tp.py:189`` ``ReplaceWithTensorSlicing``
    :30, and the v2 declarative sharding helpers
    ``inference/v2/model_implementations/sharding/qkv.py``), the paged KV
    cache is head-split over the ``tensor`` axis, and any ``fsdp`` mesh
    axis memory-shards weights ZeRO-Inference-style (XLA gathers per
    use).  GSPMD inserts the per-layer collectives; no imperative tensor
    slicing."""

    def __init__(self, model: Model, config: InferenceConfig = None,
                 topology: Optional[MeshTopology] = None,
                 quant_tree=None):
        """``quant_tree``: a pre-built ZeRO-Inference quantized tree (the
        second output of ``quantization.quantize_model_params``, e.g.
        loaded from a quantized checkpoint) — ``model.params`` must then
        be the matching dense remainder, and ``weight_quant`` is not
        re-applied (the >HBM big-model flow: nothing dense ever
        materializes)."""
        self.model = model
        self.cfg: TransformerConfig = model.config
        self.icfg = config or InferenceConfig()
        if self.icfg.prefix_cache not in ("auto", "on", "off"):
            raise ValueError(f"prefix_cache={self.icfg.prefix_cache!r}: "
                             "expected 'auto', 'on', or 'off'")
        if self.icfg.kv_tier not in ("auto", "on", "off"):
            raise ValueError(f"kv_tier={self.icfg.kv_tier!r}: "
                             "expected 'auto', 'on', or 'off'")
        if self.icfg.kv_tier == "on" and self.icfg.prefix_cache == "off":
            raise ValueError(
                "kv_tier='on' requires the prefix cache: the tier keys "
                "demoted blocks by their chain digests, which only the "
                "prefix-cache index computes (set prefix_cache to "
                "'auto'/'on' or kv_tier to 'auto'/'off')")
        if self.icfg.attn_impl not in ("auto", "xla", "pallas"):
            raise ValueError(f"attn_impl={self.icfg.attn_impl!r}: "
                             "expected 'auto', 'xla', or 'pallas'")
        if self.icfg.mixed_gemm not in ("on", "off"):
            raise ValueError(f"mixed_gemm={self.icfg.mixed_gemm!r}: "
                             "expected 'on' or 'off'")
        # the attention formulation every serving program of this engine
        # runs.  "auto" takes the kernel where it is compiled and the XLA
        # formulation where it would run interpreted: a rule over what
        # the process can observe, so two engines of one process always
        # agree and nothing is compiled or timed to settle it
        # (a latent layer's kernel is ``ops/mla.py``'s, the other layers'
        # ``ops/paged_attention.py``'s; a delta-rule layer has no call
        # to make and is served the same under either)
        self.attn_impl = self.icfg.attn_impl
        if self.attn_impl == "auto":
            self.attn_impl = ("pallas" if jax.default_backend() == "tpu"
                              else "xla")
        # a model with recurrent layers keeps a second kind of per-request
        # cache state, a row of fixed size by slot, that is advanced and
        # cannot be aliased, appended to or overwritten.  What each
        # contract written for block tables does for it is decided here,
        # by the model (docs/SERVING.md "Per-request cache state")
        self._recurrent = self._recurrent_config(topology)
        max_len = self.icfg.max_seq_len or self.cfg.max_seq_len
        # a sequence can never hold more blocks than the pool has
        self.max_blocks_per_seq = min(-(-max_len // self.icfg.kv_block_size),
                                      self.icfg.num_kv_blocks)
        # a model whose layers are of kinds that each hold ONE cache: the
        # pool holds its latent layers' rows (a row a token) or its
        # "full" layers' keys and values, the state rows its recurrent
        # layers'; both are sized by the layers that use them
        latent = "mla" in self.cfg.mixer_stacks
        run_cut = None
        if latent and self._recurrent is None:
            # a latent-only model: the pool is its one cache, a plain
            # block pool (prefix hits alias its blocks, a rewound row is
            # written again).  What is not written for it is refused
            if self.icfg.spec_decode == "on":
                raise ValueError(
                    "spec_decode='on': a latent layer reads a verify "
                    "window as a run of several tokens and a step holds "
                    "a bounded number of those (RunCut.scan_runs); use "
                    "'auto' or 'off'")
            if topology is not None and topology.device_count > 1:
                raise NotImplementedError(
                    "serving over a mesh: latent attention is not sharded")
            run_cut = RunCut(chunk=self.cfg.kda_chunk)
        kv_cfg = KVCacheConfig(
            num_layers=self.cfg.block_layers,
            num_kv_heads=self.cfg.num_kv_heads,
            head_dim=self.cfg.head_dim,
            latent_dim=self.cfg.mla_dims.row if latent else 0,
            block_size=self.icfg.kv_block_size,
            num_blocks=self.icfg.num_kv_blocks,
            dtype=self.icfg.kv_dtype,
            quant=self.icfg.kv_quant or "none",
            recurrent=self._recurrent, run_cut=run_cut,
            # the kernel's DMAs move whole memory tiles: where it runs,
            # each chip's slab of the pool is allocated filled up to them
            tiled=self.attn_impl == "pallas" and not latent,
            head_groups=self._kv_head_groups(topology))
        self.state = StateManager(kv_cfg, max_seqs=self.icfg.max_seqs,
                                  max_blocks_per_seq=self.max_blocks_per_seq,
                                  # a prefix hit aliases blocks and
                                  # cannot alias a state: "auto" is off
                                  # for a model with recurrent layers
                                  prefix_cache=self.icfg.prefix_cache
                                  != "off" and self._recurrent is None)
        # "auto" resolves OFF today — demotion trades host RAM/disk +
        # drain time for saved recompute, a workload call the ROADMAP-4
        # autotuner is meant to make
        if self.icfg.kv_tier == "on":
            from .ragged.tier import KVBlockTier
            self.state.tier = KVBlockTier(
                ram_bytes=int(self.icfg.kv_tier_ram_mb * (1 << 20)),
                nvme_dir=self.icfg.kv_tier_dir,
                nvme_bytes=int(self.icfg.kv_tier_nvme_mb * (1 << 20)))
        self.topology = topology if (
            topology is not None and topology.device_count > 1) else None
        self.params = self._serving_weights(model.params, announce=True)
        self._quant = None
        if quant_tree is not None:
            self._quant = fold_projections(quant_tree)
        elif self.icfg.weight_quant:
            from .quantization import quantize_model_params
            from ..ops.quant import WEIGHT_QUANT_BITS
            self.params, self._quant = quantize_model_params(
                self.params, bits=WEIGHT_QUANT_BITS[self.icfg.weight_quant],
                quantize_embeddings=self.icfg.quantize_embeddings)
        self._stream = None
        if self.icfg.weight_stream:
            self._setup_weight_stream()
        self._mixed_gemm_active = self.icfg.mixed_gemm == "on"
        if self._mixed_gemm_active:
            # fail at construction, not at the first compiled step: an
            # explicit force-on with an ineligible layout is a config error
            self._require_mixed_gemm_eligible()
        self._setup_sharding()
        # resolved overlapped/quantized-collective plan (comm/overlap.py)
        # — None when the mesh/shapes give the decomposition nothing to
        # do — and the plan the compiled programs run
        self._serving_comm = self._resolve_serving_comm()
        comm = self._serving_comm
        if comm is not None and self._mixed_gemm_active and comm.downproj:
            # mixed-GEMM keeps the down-projection weight quantized for
            # the VMEM-dequant kernel — only the unembed gather can
            # still decompose; the plan (and its wire accounting)
            # shrinks to match the compiled program
            comm = comm._replace(downproj=False, quant_bits=None)
            if not comm.unembed:
                comm = None
        self._comm_active = comm
        self._comm_stats: Dict[int, Dict[str, float]] = {}  # by rows
        if self.topology is None:
            self._place_default_device()
        # tpulint: live-set — uid -> unprocessed toks
        self._pending: Dict[int, List[int]] = {}
        self._ctx_exhausted: set = set()
        self._rng = jax.random.PRNGKey(0)
        self._cow_fn = None           # lazy jitted prefix-cache block copy
        self._restage_fn = None       # lazy jitted tier->HBM block upload
        # (bucket, sampler_key) -> the served step, one jit function
        # that holds an executable for every row count of the ladder
        # (``_step_rows``): all of them compiled when it is built
        self._pstep_fns: Dict[tuple, object] = {}
        # always empty: the engine races nothing at start-up any more.
        # Kept because benchmarks/lib/drivers/serve.py iterates it for
        # its "races" notes and a PR of this kind may not edit that file
        # (ROADMAP.md debt B1: retire the readers, then this attribute)
        self.probe_times: Dict[str, Dict[str, float]] = {}
        # (rows, bucket, sampler_key) -> what the compiled step is,
        # noted when it is compiled: "temp_bytes" is what the program
        # needs beside its arguments — the layer scan carries the cache
        # in place, so it stays far under one layer's share of the pool
        self.serving_programs: Dict[tuple, Dict] = {}
        # serving programs that have COMPLETED at least one call: only
        # these run under the dispatch watchdog — a first call may
        # carry an unboundedly-slow (and legitimate) compile
        self._warm_keys: set = set()
        self._steps_done = 0
        # --- model-free speculative decoding (spec_decode.py) ----------
        self._setup_spec_decode()
        # the row counts a served step is compiled at: a step runs at
        # the smallest that holds its scheduled tokens (``step_rows``)
        self._step_rows = step_rows(self.icfg.max_seqs, self._n_verify,
                                    self.icfg.token_budget)
        self._row_tokens = self._row_slots = 0    # serving_step_row_fill
        # served-loop state: alternating host staging buffers, the
        # last dispatched step's on-device sample array (the feedback
        # source for the next step), and a zero fallback for step 0
        self._stager = BatchStager(self.icfg.token_budget,
                                   self.icfg.max_seqs,
                                   self.icfg.num_kv_blocks,
                                   n_verify=self._n_verify,
                                   n_chunks=0 if kv_cfg.runs is None
                                   else kv_cfg.runs.n_chunks(
                                       self.icfg.token_budget))
        # spec engines' steps return [S, W] windows, so the feedback
        # operand (and its step-0 zero fallback) is window-shaped too
        # a sparse-expert model's steps append their routing statistics
        rows = self.icfg.max_seqs + (moe_stat_rows(self.cfg)
                                     if self.cfg.num_experts > 1 else 0)
        self._zero_toks = self._stage(jnp.zeros(
            (rows,) if self._n_verify == 1
            else (rows, self._n_verify), jnp.int32))
        self._last_toks = None
        self._dispatch_seq = 0
        self._fb_step: Dict[int, int] = {}   # uid -> sid its marker defers to
        # --- the served step runs one step ahead (step(), docs/SERVING.md
        # "The served loop"): requests whose continuation the engine owns
        # (uid -> tokens it may still emit; put(max_new_tokens=...)), the
        # one launch step() left in flight, the tokens of a launch that
        # was read back outside step() (snapshot and the like: the next
        # call hands them over), and rows of the launch in flight whose
        # result is void (uid -> sid; hold())
        self._cont: Dict[int, int] = {}
        self._ahead: Optional[_InFlight] = None
        self._held: Dict[int, List[int]] = {}
        self._void: Dict[int, int] = {}
        self._zero_key = jax.random.PRNGKey(0)
        # --- overload policy state (inference/overload.py) -------------
        self.ocfg = self.icfg.overload or OverloadConfig()
        self._meta: Dict[int, RequestMeta] = {}   # uid -> admission meta
        self._deadline_uids: set = set()          # uids with a deadline
        self._inflight_sched: Dict[int, int] = {} # uid -> uncollected steps
        self._preempting: set = set()             # release() = preemption
        self._round_preemptions = 0     # evictions of the last schedule
        self._round_cached = 0          # prompt tokens it aliased
        self._evictions_seen = 0        # state.prefix_evictions, last stage
        self._preempt_gen: Dict[int, List[int]] = {}  # pre-eviction tokens
        # tpulint: live-set — uid -> staged terminal status
        self._closing: Dict[int, str] = {}
        self._reaped: set = set()   # engine-closed uids drivers must drop
        self._setup_telemetry()
        # --- failure-domain state (inference/failures.py) --------------
        self.fcfg = self.icfg.failure or FailureConfig()
        self.failures = FailurePolicy(self.fcfg, self.timings,
                                      flight=self.flight,
                                      metrics=self.metrics,
                                      tracer=self.tracer)
        # the round's fixed slot: the cuts below are written into it, and
        # a round that ran long leaves one ``slow_round`` record
        self._round = self.failures.rounds
        self._strikes: Dict[int, int] = {}   # uid -> failing-batch count
        self._probe_groups: List[List[int]] = []  # bisection quarantine
        self._backoff_rounds = 0             # rounds admitting nothing
        self._consec_failures = 0
        self._consec_timeouts = 0
        self._last_failure_step = -(1 << 30)
        self._health = "healthy"             # healthy|degraded computed;
        self._draining = False               # draining|dead are sticky
        # every KV release — flush, preemption, deadline expiry, or a
        # direct StateManager.release — flows through one close-out hook
        # so request_metrics() can never leak an open record
        self.state.on_release = self._on_state_release

    def _recurrent_config(self, topology):
        """The state rows of a model with recurrent layers, or None;
        refuses by name what cannot serve such a model.  The state is
        stored in the parameters' serving type (a delta-rule state in
        float32; the configuration's, not an option) and advanced in
        float32."""
        from .ragged.state import RecurrentConfig
        cfg, icfg = self.cfg, self.icfg
        if not cfg.has_ssm:
            return None
        why = ("the model's layers hold a recurrent state "
               "(TransformerConfig.has_ssm): ")
        if icfg.prefix_cache == "on":
            raise ValueError(
                "prefix_cache='on': " + why + "a prefix hit aliases KV "
                "blocks and cannot alias a state (snapshots of a state at "
                "block boundaries are not implemented); use 'auto' or "
                "'off'")
        if icfg.spec_decode == "on":
            raise ValueError(
                "spec_decode='on': " + why + "a rejected draft rewinds "
                "the KV write cursor and cannot rewind a state; use "
                "'auto' or 'off'")
        if icfg.kv_tier == "on":
            raise ValueError(
                "kv_tier='on': " + why + "a restaged block chain carries "
                "no state")
        if topology is not None and topology.device_count > 1:
            raise NotImplementedError(
                "serving over a mesh: " + why + "the mixer is not sharded")
        if cfg.recurrent_kind == "kda":
            # a delta-rule state is stored in float32 (the model's, not
            # an option): stored in bfloat16 it is rounded once a token,
            # a channel whose decay is 0.9999 keeps thousands of those
            # roundings, and a sequence decoded for 4,000 tokens read
            # 7.6e-2 of the largest logit off a float32 reference where
            # every shorter comparison read 2.3e-2 (PERF.md, PR 44)
            kd = cfg.kda_dims
            return RecurrentConfig(
                heads=kd.heads, head_dim=kd.key_dim, state=kd.value_dim,
                conv=kd.conv, channels=kd.conv_channels, chunk=kd.chunk,
                dtype=icfg.param_dtype, state_dtype=jnp.float32,
                layers=cfg.layers_of("kda"))
        sd = cfg.ssm_dims
        return RecurrentConfig(
            heads=sd.heads, head_dim=sd.head_dim, state=sd.state,
            conv=sd.conv, channels=sd.conv_channels, chunk=sd.chunk,
            dtype=icfg.param_dtype,
            # a "mamba" layer holds a state and no blocks; a "hybrid"
            # layer both, and every layer is one
            layers=cfg.layers_of("mamba") or None)

    def _setup_telemetry(self) -> None:
        """Build the metrics registry, the span tracer, and the
        request-lifecycle tracker (docs/OBSERVABILITY.md).  Everything
        here is host-side counters/floats — telemetry never touches
        device arrays on the serving path (tpulint telemetry-hotpath +
        serving-sync keep it that way)."""
        self.metrics = MetricsRegistry()
        self.tracer = SpanTracer(capacity=self.icfg.trace_capacity,
                                 enabled=self.icfg.trace)
        self.requests = RequestTracker(
            self.metrics, max_finished=self.ocfg.status_retention)
        reg = self.metrics
        # health-state gauge (docs/OBSERVABILITY.md): 0 healthy,
        # 1 degraded, 2 draining, 3 dead — what the multi-replica
        # router's liveness probe scrapes
        self._health_gauge = reg.gauge(
            "serving_health_state",
            "engine health: 0=healthy 1=degraded 2=draining 3=dead")
        ms = {k: reg.counter(f"serving_{k}_total",
                             f"cumulative serving-loop {k.split('_')[0]} "
                             "phase milliseconds")
              for k in ("schedule_ms", "stage_ms", "device_ms", "wait_ms",
                        "readback_ms")}
        ints = {
            "steps": reg.counter("serving_steps_total",
                                 "dispatched serving steps",
                                 int_valued=True),
            "prompt_tokens": reg.counter(
                "serving_prompt_tokens_total",
                "prompt tokens of admitted requests", int_valued=True),
            "cached_tokens": reg.counter(
                "serving_cached_tokens_total",
                "prompt tokens served from the prefix cache",
                int_valued=True),
            "prefix_hits": reg.counter(
                "serving_prefix_hits_total",
                "admitted requests with a nonzero prefix match",
                int_valued=True),
            "generated_tokens": reg.counter(
                "serving_generated_tokens_total",
                "tokens emitted to live sequences", int_valued=True),
            # speculative decoding (docs/SERVING.md "Speculative
            # decoding"): drafted = proposer tokens a verify window
            # scored; accepted = drafts committed (they match the
            # model's own stream and were emitted); rejected = drafts
            # rolled back.  drafted == accepted + rejected, and the
            # per-request records bump at the SAME statements, so
            # sum(per-request) reconciles with these by construction
            # (tests/test_spec_decode.py holds the invariant)
            # tpulint: pair=spec_drafted_tokens/spec_accepted_tokens
            "spec_drafted_tokens": reg.counter(
                "serving_spec_drafted_tokens_total",
                "draft tokens scored by verify steps", int_valued=True),
            "spec_accepted_tokens": reg.counter(
                "serving_spec_accepted_tokens_total",
                "draft tokens accepted and emitted", int_valued=True),
            "spec_rejected_tokens": reg.counter(
                "serving_spec_rejected_tokens_total",
                "draft tokens rolled back", int_valued=True),
            "spec_windows": reg.counter(
                "serving_spec_windows_total",
                "verify windows resolved (mean accepted draft length = "
                "accepted / windows)", int_valued=True),
            # failure domains (docs/SERVING.md "Failure domains &
            # recovery"): steps the classifier recovered (re-queue /
            # bisect) and requests quarantined terminally as poison
            "step_retries": reg.counter(
                "serving_step_retries_total",
                "serving steps that failed and were recovered by "
                "re-queue (retry or bisect probe)", int_valued=True),
            "requests_failed": reg.counter(
                "serving_requests_failed_total",
                "requests terminally closed with status 'failed' "
                "(poison quarantine / unreplayable after a failure)",
                int_valued=True),
            # compile observatory (docs/OBSERVABILITY.md "Device &
            # compiler telemetry"): every serving executable-cache fill
            # counts; a fill whose (kind, key) was ALREADY compiled in
            # this engine's lifetime is a runtime RETRACE — the dynamic
            # complement of tpulint's static retrace-hazard rule, and
            # each one logs a loud warning (something is churning the
            # program cache: LRU thrash, shape churn, weight refresh)
            "compiles": reg.counter(
                "serving_compiles_total",
                "serving programs built (executable-cache fills)",
                int_valued=True),
            "compile_retraces": reg.counter(
                "serving_compile_retraces_total",
                "re-builds of a program key this engine had already "
                "compiled (runtime retrace — each warns loudly)",
                int_valued=True),
            # tiered KV cache (docs/KV_TIERING.md): demotions count
            # blocks evicted into the host ring, spills the ring's
            # overflow pushed on to NVMe files, revives the blocks
            # restaged back into HBM by source tier; every revive that
            # lands in a round which also dispatched a step overlapped
            # the dispatch-ahead window (the TTFT win the tier exists
            # for).  Verify failures are payloads rejected by the
            # checksum / chain-digest contract — nonzero outside a
            # corruption drill means the spill path is eating data
            "kv_tier_demotions": reg.counter(
                "serving_kv_tier_demotions_total",
                "KV blocks demoted from HBM into the host-RAM tier",
                int_valued=True),
            "kv_tier_spills": reg.counter(
                "serving_kv_tier_spills_total",
                "tier blocks spilled from the host ring to NVMe",
                int_valued=True),
            "kv_tier_drops": reg.counter(
                "serving_kv_tier_drops_total",
                "tier blocks dropped off the bottom of the hierarchy",
                int_valued=True),
            "kv_tier_revives_ram": reg.counter(
                "serving_kv_tier_revives_ram_total",
                "blocks restaged into HBM from the host ring",
                int_valued=True),
            "kv_tier_revives_nvme": reg.counter(
                "serving_kv_tier_revives_nvme_total",
                "blocks restaged into HBM from NVMe spill files",
                int_valued=True),
            "kv_tier_revives_remote": reg.counter(
                "serving_kv_tier_revives_remote_total",
                "blocks restaged into HBM from peer-replica fetches",
                int_valued=True),
            "kv_tier_restage_overlap_hits": reg.counter(
                "serving_kv_tier_restage_overlap_hits_total",
                "revives resolved in a round that also dispatched a "
                "step (the restage overlapped the dispatch-ahead "
                "window)", int_valued=True),
            "kv_tier_verify_failures": reg.counter(
                "serving_kv_tier_verify_failures_total",
                "restage/fetch payloads rejected by checksum or "
                "chain-digest verification (fell back to re-prefill)",
                int_valued=True),
            "kv_tier_demoted_bytes": reg.counter(
                "serving_kv_tier_demoted_bytes_total",
                "payload bytes demoted into the host ring",
                int_valued=True),
            "kv_tier_spilled_bytes": reg.counter(
                "serving_kv_tier_spilled_bytes_total",
                "payload bytes spilled to NVMe", int_valued=True),
            "kv_tier_remote_blocks": reg.counter(
                "serving_kv_tier_remote_blocks_total",
                "tier blocks imported from peer replicas "
                "(snapshot-v2 tier_blocks records)", int_valued=True),
        }
        # first-call wall time of each program (compile rides it): the
        # timestamps are the dispatch path's existing t2/t3, so this
        # adds no clock reads — it is the always-on compile-span feed
        ms["compile_ms"] = reg.counter(
            "serving_compile_wall_ms_total",
            "cumulative first-call (compile-carrying) dispatch wall ms")
        self.timings = CounterDictView({**ms, **ints})
        # what the watchdog's two thread hops cost (inference/failures.py
        # ``Watchdog.hop_us``), summed over every guarded call
        self._c_guard_hop = reg.counter(
            "serving_guard_hop_ms_total",
            "cumulative milliseconds guarded device calls spent in the "
            "watchdog's hand-off (caller to worker and back)")
        # how often the served step runs ahead (step()): launches made
        # with the previous one unread, launches that were not and why,
        # and rows launched ahead for a stream that no longer wanted them
        self._c_ahead = reg.counter(
            "serving_steps_ahead_total",
            "served steps launched before the previous one was read back",
            int_valued=True)
        self._c_strict = reg.counter(
            "serving_strict_steps_total",
            "served steps launched with nothing in flight (reason: "
            "idle|caller_fed|spec_decode|probe)", int_valued=True)
        self._c_discarded = reg.counter(
            "serving_ahead_discarded_rows_total",
            "sampled rows thrown away at collect because their stream "
            "had ended or paused (reason: finished|cancelled|stalled|...)",
            int_valued=True)
        # the row count each dispatched step ran at (``_step_rows``),
        # and how much of those rows held a scheduled token
        self._c_step_rows = reg.counter(
            "serving_step_rows_total",
            "dispatched serving steps by the row count their program "
            "was compiled at (rung)", int_valued=True)
        reg.gauge_fn("serving_step_row_fill",
                     lambda: (self._row_tokens / self._row_slots
                              if self._row_slots else None),
                     "scheduled tokens over compiled rows of the "
                     "dispatched steps (absent before the first one)")
        # a model with recurrent layers (``_recurrent``): what the
        # dispatched steps did to the state rows, from the schedule
        if self._recurrent is not None:
            self._c_state_updates = reg.counter(
                "serving_state_updates_total",
                "tokens the dispatched steps advanced recurrent states by "
                "(kind: decode = one-token runs | scan = tokens of longer "
                "runs), one layer's count", int_valued=True)
            self._c_state_replayed = reg.counter(
                "serving_state_replayed_rows_total",
                "rows fed again after a launch ahead was thrown away: "
                "they read the state they had produced and left it",
                int_valued=True)
            reg.gauge_fn("serving_state_slots_in_use",
                         lambda: len(self.state.seqs),
                         "sequences that hold a state row")
            # the one-token update is dense over the pool's slots: how
            # many of the rows it moved it advanced
            self._state_rows = self._state_slots = 0
            reg.gauge_fn("serving_state_update_fill",
                         lambda: (self._state_rows / self._state_slots
                                  if self._state_slots else None),
                         "state rows the dispatched steps advanced by one "
                         "token over the slot rows their dense update read "
                         "and wrote (absent before the first step)")
            # the chunked form walks the chunks of the step's table that
            # hold rows: how many, and how full they were
            self._c_scan_chunks = reg.counter(
                "serving_scan_chunks_total",
                "chunks of the dispatched steps' tables that held rows of "
                "a run of several tokens (the chunked form computes those "
                "and no others), one layer's count", int_valued=True)
            self._scan_tokens = self._scan_chunks = 0
            reg.gauge_fn("serving_scan_chunk_fill",
                         lambda: (self._scan_tokens
                                  / (self._scan_chunks
                                     * self._recurrent.chunk)
                                  if self._scan_chunks else None),
                         "tokens of the runs of several tokens over the "
                         "rows of the chunks that held them (absent before "
                         "the first such run)")
            reg.gauge_fn(
                "serving_state_bytes",
                lambda: len(self.state.seqs)
                * self._recurrent.bytes_per_seq(
                    self._recurrent.layers or self.cfg.num_layers),
                "bytes of recurrent state and convolution tail the live "
                "sequences hold, all the layers that hold one")
            # the division of memory, as allocated (what the live
            # sequences hold of it is ``serving_state_bytes``)
            reg.gauge_fn(
                "serving_state_rows_bytes",
                lambda: self.state.kv["ssm"].nbytes
                + self.state.kv["conv"].nbytes,
                "bytes of the state rows and convolution tails as "
                "allocated: every slot and the trash row, the layers "
                "that hold a state")
            reg.gauge_fn(
                "serving_block_pool_bytes",
                lambda: sum(a.nbytes for a in jax.tree.leaves(
                    self.state.kv["kv"])),
                "bytes of the paged pool as allocated beside the state "
                "rows: the layers that hold blocks")
        if self.state.cfg.latent_dim:
            reg.gauge_fn(
                "serving_latent_pool_bytes",
                lambda: (self.state.kv["kv"] if self._recurrent is not None
                         else self.state.kv).nbytes,
                "bytes of the latent pool: a row a token, the latent "
                "layers only")
        # sparse experts (parallel/moe.py moe_serve): read from the rows
        # the step appends to its sampled tokens, at their readback;
        # a dense model has neither
        self._moe_metrics = None
        if self.cfg.num_experts > 1:
            self._moe_metrics = (
                reg.counter(
                    "serving_moe_assignments_total",
                    "(token, expert) assignments the serving steps "
                    "computed, summed over the layers: every real "
                    "token's top-k, none dropped.  A model that holds a "
                    "share of its experts "
                    "(experts_held) labels them where: held = computed "
                    "here | absent = made by the router for experts that "
                    "are not here | zero = made for experts that compute "
                    "nothing (moe_zero_experts): the row's input back, "
                    "times the weight"),
                reg.gauge(
                    "serving_moe_expert_load_max_over_mean",
                    "rows of the fullest expert over the mean rows an "
                    "expert, worst layer of the last collected step"))
        # the Pallas attention kernel's grid (ops/paged_attention
        # ``tile_counts``): how many query tiles the dispatched steps
        # were cut into, and how full the long ones were.  Counted on
        # the host from the schedule's run lengths; steps that ran an
        # XLA formulation count nothing.  A model with a latent layer is
        # cut at that kernel's two heights (ops/mla ``tile_heights``: a
        # one-token run is a tile of ONE row, a longer run tiles of a
        # thousand MXU rows), under labels of their own, and its runs of
        # at least ``expand_from`` rows apart: the expanded form's tiles
        self._tile_heights = (SHORT, LONG)
        self._tile_labels = ("short", "long")
        self._tile_wide = None
        if self.state.cfg.latent_dim:
            from ..ops.mla import tile_heights, wide_cut
            self._tile_heights = tile_heights(self.cfg.mla_dims.heads)
            self._tile_labels = ("one", "run", "expanded")
            self._tile_wide = wide_cut(self.cfg.mla_dims)
        self._c_attn_tiles = reg.counter(
            "serving_attn_tiles_total",
            "query tiles of the attention kernel over the dispatched "
            "steps (height: short|long; a latent model's: "
            "one|run|expanded)",
            int_valued=True)
        self._c_attn_long_rows = reg.counter(
            "serving_attn_long_tile_tokens_total",
            "real tokens in the long (run) query tiles", int_valued=True)
        reg.gauge_fn("serving_attn_tile_fill", self._attn_tile_fill,
                     "real tokens over tile rows of the long query tiles "
                     "(absent before the first one)")
        # keys and values one attention layer of each kind has to read
        # for the dispatched steps, from the schedule (host arithmetic,
        # nothing read from the device).  A model without window layers
        # counts the full kind only
        self._window = (self.cfg.attn_window
                        if "window" in self.cfg.layer_pattern else None)
        self._c_attn_kv = reg.counter(
            "serving_attn_kv_tokens_total",
            "cached tokens one attention layer reads for the dispatched "
            "steps (kind: full = every token of the step's sequences | "
            "window = those inside its queries' windows | latent = the "
            "cached rows a latent layer reads)", int_valued=True)
        # the groups of KV blocks the kernel's short call (decode tokens,
        # verify windows) walks in one layer of each kind, a trip of a
        # tile's loop each, and how full they ran (a needed block is a
        # copy the kernel starts): counted with the kernel's own rule
        # for its group (``kv_group``), from the schedule
        self._c_attn_group_steps = reg.counter(
            "serving_attn_kv_group_steps_total",
            "groups of KV blocks the paged-attention kernel's short call "
            "walks (a trip of a tile's loop each), one layer of the kind "
            "(kind: full | window)", int_valued=True)
        self._group_blocks = self._group_slots = 0
        kc = self.state.cfg
        heads, lanes = kc.slab      # a chip's own: the call's shapes
        self._attn_group = kv_group(
            SHORT, self.cfg.num_heads // self.cfg.num_kv_heads,
            heads // kc.head_groups, lanes, kc.block_size, kc.store_dtype,
            self.max_blocks_per_seq, kc.quant != "none")
        reg.gauge_fn("serving_attn_kv_group_fill", self._attn_group_fill,
                     "needed KV blocks over the blocks the short call's "
                     "groups hold (absent before the first one)")
        if self._window:
            reg.gauge_fn(
                "serving_kv_tokens_behind_window", self._behind_window,
                "pooled tokens of the live sequences that no window layer "
                "will read again: what a pool per layer kind would free "
                "in each window layer")
        # --- overlapped/quantized collectives (docs/SERVING.md
        # "Overlapped & quantized collectives"): static per-dispatch
        # wire accounting — the shapes of a compiled step fully
        # determine what its decomposed TP collectives move, so the
        # counters bump from host-side arithmetic, never a device
        # probe.  A quantized op's bytes are bits/8 of the exact op's
        # (asserted by the telemetry reconciliation test).
        self._c_comm_ops = reg.counter(
            "serving_comm_ops_total",
            "decomposed TP collectives dispatched (kind: exact|quant)",
            int_valued=True)
        self._c_comm_tiles = reg.counter(
            "serving_comm_tiles_total",
            "tiles across dispatched decomposed TP collectives",
            int_valued=True)
        self._c_comm_bytes = reg.counter(
            "serving_comm_bytes_total",
            "modeled bytes on the wire for decomposed TP collectives "
            "(kind: exact|quant)")
        # --- KV-pool occupancy gauges: pull-based (FnGauge — computed
        # from allocator truth at export time), so the serving loop
        # never updates them and a scrape is always current.  The
        # scheduler fuzz cross-checks gauge == assert_invariants truth.
        pool = lambda k: (lambda: self.state.pool_stats()[k])  # noqa: E731
        reg.gauge_fn("serving_kv_blocks_free", pool("free"),
                     "plain-free KV blocks (excludes cached-free)")
        reg.gauge_fn("serving_kv_blocks_cached_free", pool("cached_free"),
                     "evictable prefix-cached free KV blocks")
        reg.gauge_fn("serving_kv_blocks_referenced", pool("referenced"),
                     "KV blocks referenced by live sequences")
        reg.gauge_fn("serving_kv_blocks_peak_referenced",
                     pool("peak_referenced"),
                     "high-water mark of referenced KV blocks")
        reg.gauge_fn("serving_kv_blocks_total", pool("total"),
                     "KV pool size")
        reg.gauge_fn("serving_prefix_index_entries",
                     pool("prefix_index_entries"),
                     "content hashes resident in the prefix-cache index")
        reg.gauge_fn("serving_prefix_hit_rate", self._prefix_hit_rate,
                     "cached_tokens / prompt_tokens over the measured "
                     "window (absent before any prompt token)")
        progs = self.serving_programs
        reg.gauge_fn("serving_step_temp_bytes",
                     lambda: max((p["temp_bytes"] for p in progs.values()
                                  if p["temp_bytes"] is not None),
                                 default=None),
                     "largest temporary allocation among the compiled "
                     "serving steps (what has to fit beside weights and "
                     "KV pool; absent before the first step)")
        # tier occupancy: pull-gauges over tier.stats() truth (absent
        # when the tier is off — None suppresses the series, the same
        # contract the devtel gauges use)
        tg = lambda k: (lambda: (self.state.tier.stats()[k]  # noqa: E731
                                 if self.state.tier is not None else None))
        reg.gauge_fn("serving_kv_tier_ram_entries", tg("ram_entries"),
                     "blocks resident in the host-RAM tier ring")
        reg.gauge_fn("serving_kv_tier_ram_bytes", tg("ram_bytes"),
                     "payload bytes resident in the host-RAM tier ring")
        reg.gauge_fn("serving_kv_tier_nvme_entries", tg("nvme_entries"),
                     "blocks resident in NVMe spill files")
        reg.gauge_fn("serving_kv_tier_nvme_bytes", tg("nvme_bytes"),
                     "payload bytes resident in NVMe spill files")
        # --- flight recorder (telemetry/flight.py): always constructed
        # — the happy path never touches it, and the failure path's
        # breadcrumbs must exist BEFORE the crash someone debugs
        self.flight = FlightRecorder()
        # --- gated device telemetry (telemetry/device.py): cost-probe
        # table + derived MFU/BW gauges + memory polling.  None when
        # off: the serving loop then contains not one added clock read,
        # device sync, or cost_analysis call (enforced by test)
        mode = self.icfg.device_telemetry
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"device_telemetry={mode!r}: expected "
                             "'auto', 'on', or 'off'")
        # "auto" resolves OFF today: the cost probe pays one duplicate
        # compile per program — the autotuner (ROADMAP item 4) is meant
        # to flip it where the signals pay for themselves
        self.devtel = DeviceTelemetry(
            reg, "serving",
            step_ms_fn=lambda: (self.timings["device_ms"]
                                + self.timings["wait_ms"])) \
            if mode == "on" else None
        # (kind, key) of every program EVER built by this engine —
        # unlike _warm_keys this survives LRU eviction, so a re-build
        # is recognized as a retrace
        self._compiled_ever: set = set()
        # --- streaming anomaly detection (telemetry/anomaly.py): None
        # when off — the serving loop then contains not one added
        # clock read or detector call (the same zero-cost bar as
        # device telemetry, extended by test to the detector hooks)
        amode = self.icfg.anomaly
        if amode not in ("auto", "on", "off"):
            raise ValueError(f"anomaly={amode!r}: expected 'auto', "
                             "'on', or 'off'")
        # "auto" resolves OFF today — the ROADMAP-4 autotuner is the
        # intended flipper, exactly like device_telemetry
        self._acfg = self.icfg.anomaly_cfg or AnomalyConfig()
        self._anom = None
        if amode == "on":
            self._anom = AnomalyMonitor(self._acfg, reg, "serving")
            self._anom.watch_all(default_serving_detectors(self._acfg))
        # per-step signal scratch (last dispatch t0, last counter
        # reads) — plain floats, touched only when the monitor exists
        self._anom_prev: Dict[str, float] = {}
        # --- deep-capture windows (telemetry/profiler.py): the ONE
        # profiler seam for this engine.  Constructed when a capture
        # directory is configured; engine.capture(out_dir=...) and the
        # anomaly path (falling back to FailureConfig.flight_dir) can
        # also create it lazily via _ensure_capture
        self._cap = None
        self._warned_no_capture_dir = False
        if self.icfg.profile:
            self._cap = ProfilerCapture(
                self.icfg.profile, tracer=self.tracer,
                max_captures=self._acfg.max_captures)
            if self.icfg.profile_steps > 0:
                self._cap.arm(self.icfg.profile_steps, "config")
        # --- per-class SLO scorecard (telemetry/slo.py): None when off
        # — the request tracker's hook sites are then a single
        # attribute test (the zero-cost bar, extended by test); on, it
        # rides the tracker's existing stamp sites (zero new clock
        # reads) and registers its burn detectors into the anomaly
        # catalog when that plane is also on
        smode = self.icfg.slo
        if smode not in ("auto", "on", "off"):
            raise ValueError(f"slo={smode!r}: expected 'auto', 'on', "
                             "or 'off'")
        # "auto" resolves OFF today, like every telemetry gate here
        self._slo = None
        if smode == "on":
            self._slo = SloTracker(
                self.icfg.slo_objectives or default_slo_objectives(),
                reg)
            self.requests.slo = self._slo
            if self._anom is not None:
                self._slo.bind(self._anom,
                               lambda: self._steps_done,
                               self._on_anomaly)

    def _count_attn_kv(self, sched, pallas: bool) -> Dict[str, int]:
        """The cached tokens an attention layer of each kind reads for
        the step ``sched``, counted and returned as the stage span's
        arguments: ``kv_tokens_full``, the sum of ``seen + n`` over its
        sequences, and with window layers ``kv_tokens_window``, the sum
        of ``min(seen + n, window + n - 1)``.  Where the Pallas kernel
        serves, also ``kv_steps_full`` / ``kv_steps_window``: the groups
        of KV blocks its short call walks in one such layer
        (``ops/paged_attention.group_steps``).  A model whose cache
        is a latent pool: ``latent_tokens``, that sum for ONE latent layer,
        ``latent_tokens_one``, its part in the one-token runs (the
        kernel's call that is bound by the rows' bytes), and
        ``latent_pairs``, the (query, cached row) pairs its causal
        attention holds (``n * seen + n (n + 1) / 2`` a run of n rows);
        where the kernel serves, of the runs that take its expanded form
        (``ops/mla.expand_from`` rows or more) ``latent_pairs_expanded``,
        their pairs, and ``latent_rows_expanded``, the cached rows they
        meet (what ONE layer expands, a head)."""
        w = self._window
        full = window = pairs = one = wide_pairs = wide_rows = 0
        wide_from = self._tile_wide[0] if pallas and self._tile_wide else None
        short = []
        for uid, toks in sched:
            seq = self.state.seqs.get(uid)
            seen = seq.seen_tokens if seq else 0
            ctx = seen + len(toks)
            full += ctx
            one += ctx if len(toks) == 1 else 0
            run_pairs = len(toks) * seen + len(toks) * (len(toks) + 1) // 2
            pairs += run_pairs
            if wide_from and len(toks) >= wide_from:
                wide_pairs += run_pairs
                wide_rows += ctx
            if w:
                window += min(ctx, w + len(toks) - 1)
            if 0 < len(toks) <= SHORT:
                short.append((seen, len(toks)))
        if self.state.cfg.latent_dim:
            self._c_attn_kv.inc(full, kind="latent")
            args = {"latent_tokens": full, "latent_tokens_one": one,
                    "latent_pairs": pairs}
            if wide_from:
                args.update(latent_pairs_expanded=wide_pairs,
                            latent_rows_expanded=wide_rows)
            return args
        args = {"kv_tokens_full": full}
        self._c_attn_kv.inc(full, kind="full")
        if w:
            self._c_attn_kv.inc(window, kind="window")
            args["kv_tokens_window"] = window
        if pallas:
            k = self._attn_group
            for kind, win in (("full", None), ("window", w))[:2 if w else 1]:
                steps, blocks = group_steps(short, self.state.cfg.block_size,
                                            k, win)
                self._c_attn_group_steps.inc(steps, kind=kind)
                self._group_blocks += blocks
                self._group_slots += steps * k
                args[f"kv_steps_{kind}"] = steps
        return args

    def _count_state_rows(self, sched) -> Dict[str, int]:
        """What the step ``sched`` does to the recurrent states, counted
        and returned as the stage span's arguments: ``state_rows``, the
        sequences it advances by one token; ``scan_tokens``, the tokens
        of its longer runs; ``state_starts``, the runs that begin at
        position 0 (from zeros, whatever the slot held);
        ``state_replays``, the one-token rows fed again;
        ``scan_chunks``, the chunks of the step's table that hold the
        longer runs' rows (``build_batch`` cuts a run every ``chunk``
        rows)."""
        rows = scan = starts = replays = chunks = 0
        for uid, toks in sched:
            seq = self.state.seqs.get(uid)
            if seq is None or seq.seen_tokens == 0:
                starts += 1
            if seq is not None and seq.state_ahead:
                replays += 1
            elif len(toks) == 1:
                rows += 1
            else:
                scan += len(toks)
                chunks += -(-len(toks) // self._recurrent.chunk)
        self._c_state_updates.inc(rows, kind="decode")
        self._c_state_updates.inc(scan, kind="scan")
        self._state_rows += rows
        self._state_slots += self.icfg.max_seqs
        self._c_state_replayed.inc(replays)
        self._c_scan_chunks.inc(chunks)
        self._scan_tokens += scan
        self._scan_chunks += chunks
        return {"state_rows": rows, "scan_tokens": scan,
                "scan_chunks": chunks, "state_starts": starts,
                "state_replays": replays}

    def _attn_group_fill(self) -> Optional[float]:
        """Needed KV blocks over the blocks held by the groups the
        short call walked so far; None before the first one."""
        return (self._group_blocks / self._group_slots
                if self._group_slots else None)

    def _behind_window(self) -> int:
        """Tokens the live sequences hold that lie behind the window of
        their next query (``seen - window + 1`` of each, where positive):
        every layer keeps every block, so a window layer's pool holds
        them to no purpose."""
        return sum(max(0, q.seen_tokens - self._window + 1)
                   for q in list(self.state.seqs.values()))

    def _attn_tile_fill(self) -> Optional[float]:
        """Real tokens over rows of the long query tiles dispatched so
        far; None before the first one."""
        tiles = self._c_attn_tiles.value(height=self._tile_labels[1])
        return self._c_attn_long_rows.value() / (
            tiles * self._tile_heights[1]) if tiles else None

    def _prefix_hit_rate(self):
        prompt = self.timings["prompt_tokens"]
        if not prompt:
            return None
        return self.timings["cached_tokens"] / prompt

    def _note_compile(self, kind: str, key) -> None:
        """Count one executable-cache fill; a (kind, key) this engine
        already compiled is a runtime retrace and warns loudly (the
        dynamic complement of tpulint's static retrace-hazard rule)."""
        tm = self.timings
        tm["compiles"] += 1
        if (kind, key) in self._compiled_ever:
            tm["compile_retraces"] += 1
            logger.warning(
                "serving program %s/%r RECOMPILED at runtime (retrace "
                "#%d): the executable cache is churning — LRU thrash, "
                "shape churn, or a weight refresh",
                kind, key, int(tm["compile_retraces"]))
        else:
            self._compiled_ever.add((kind, key))

    def _compile_rungs(self, key, step_fn, batch, prev, rng) -> None:
        """Compile the served step ``key`` = (bucket, sampler_key) at
        every row count of the ladder, ahead of any step that needs it,
        and keep it: no rung compiles under traffic.
        ``lower(...).compile()`` fills the caches the jit function's
        own calls read (jit keeps its lowering and the lowering its
        executable), so a step's call finds its program built.  Each
        rung enters ``serving_programs`` with the temporary bytes of
        the executable just built.  ``batch``: the step about to
        launch, which gives its own rung; the other is lowered from a
        batch of its shape that holds no token.  The rungs are built
        side by side, a thread each (tracing, lowering and the compiler
        or its cache take seconds a program on a serving host, and
        set-up would pay them one after the other: 8.6 s against 1.0 in
        ``serve-prefill``, PERF.md section 6, PR 43); an engine of one
        rung builds its program on this thread, as it always did."""
        rungs = self._step_rows
        batches = {rows: batch if rows == batch.token_ids.shape[0] else
                   self._stage(self.state.blank_batch(rows, self._n_verify))
                   for rows in rungs}

        def build(rows):
            return step_fn.lower(self.params, self._quant, self.state.kv,
                                 batches[rows], prev, rng).compile()

        if len(rungs) == 1:
            built = [build(rungs[0])]
        else:
            with ThreadPoolExecutor(len(rungs)) as pool:
                built = list(pool.map(build, rungs))
        for rows, compiled in zip(rungs, built):
            rkey = (rows,) + key
            self._note_compile("p", rkey)
            temp = None
            try:
                mem = compiled.memory_analysis()
                temp = None if mem is None else int(mem.temp_size_in_bytes)
            except Exception as e:
                logger.warning(
                    "serving step %r: no memory analysis (%s: %s)", rkey,
                    type(e).__name__,
                    str(e).splitlines()[0][:120] if str(e) else "")
            self.serving_programs[rkey] = {"temp_bytes": temp}
            logger.info("serving step %r: temporaries %s bytes", rkey, temp)
        if len(self._pstep_fns) >= 16:        # bound retained executables
            evicted = next(iter(self._pstep_fns))
            self._pstep_fns.pop(evicted)
            # a rebuilt executable recompiles: its next call is cold
            # again or the watchdog would time the compile
            for rows in rungs:
                self._warm_keys.discard(("p", (rows,) + evicted))
        self._pstep_fns[key] = step_fn

    def reset_timings(self) -> None:
        """Zero the cumulative per-phase breakdown the serving loop
        records (milliseconds; ``steps`` dispatches): host scheduling,
        batch staging, the jitted call (pure enqueue when dispatch is
        async; the whole device step when something — e.g. CPU-backend
        donation — forces it synchronous), the wait for the collected
        step's sample array, and the pure device->host fetch.  The
        served loop's per-step critical-path host overhead is
        roughly wall/steps - (device_ms + wait_ms)/steps.

        Also zeroes the token counters: ``prompt_tokens`` (total prompt
        tokens of admitted requests), ``cached_tokens`` (prompt tokens
        served from the prefix cache — skipped prefill), ``prefix_hits``
        (admitted requests with a nonzero match; hit rate =
        cached_tokens / prompt_tokens), and ``generated_tokens``
        (tokens emitted to live sequences).

        ``engine.timings`` is a dict-shaped view over ``engine.metrics``
        registry counters — this resets exactly those counters; use
        :meth:`reset_metrics` to also clear request records, latency
        histograms, and the span ring."""
        self.timings.reset()

    def reset_metrics(self) -> None:
        """Full telemetry reset: every registry metric (timings view
        included), the request-lifecycle tracker, and the span ring —
        what a benchmark calls between warmup and its timed region."""
        self.metrics.reset()
        self._group_blocks = self._group_slots = 0
        self._row_tokens = self._row_slots = 0
        self._state_rows = self._state_slots = 0
        self._scan_tokens = self._scan_chunks = 0
        self.requests.clear()
        self.tracer.clear()
        # the timed region's rounds are judged against their own mean
        self._round.reset()
        # rearm the pool high-water mark so a timed region reports ITS
        # peak, not the warmup's (the pull-gauges read live truth)
        self.state.allocator.reset_peaks()
        # rearm the anomaly detectors (fresh baselines for the timed
        # region) and the anomaly-capture budget
        if self._anom is not None:
            self._anom.reset()
            self._anom_prev.clear()
        if self._cap is not None:
            self._cap.reset_budget()
        # rearm the SLO windows + burn detectors alongside the counters
        # they quotient over (attainment restarts exact)
        if self._slo is not None:
            self._slo.reset()

    def device_snapshot(self) -> Optional[Dict]:
        """JSON-able device-telemetry summary (per-program cost
        analysis, derived MFU / HBM-bandwidth utilization, last memory
        poll) — what a benchmark embeds next to its request-metrics
        aggregates.  None when ``device_telemetry`` is off."""
        return None if self.devtel is None else self.devtel.snapshot()

    def anomaly_summary(self) -> Optional[Dict]:
        """JSON-able anomaly tally — total fires, per-signal counts,
        the most recent events, and the completed capture-window dirs
        — what the loadgen SLO sweep embeds.  None when
        anomaly detection is off."""
        if self._anom is None:
            return None
        return {**self._anom.summary(), "captures": self.capture_dirs}

    def slo_scorecard(self) -> Dict:
        """The per-class SLO scorecard (telemetry/slo.py,
        docs/OBSERVABILITY.md "SLOs & error budgets"): per-objective
        good/evaluated counter pairs with their attainment quotient,
        the class error budget, and the burn detector's fast/slow
        rates.  ``{"enabled": False}`` when ``InferenceConfig.slo``
        resolves off — the shape the gateway's ``GET /debug/slo``
        serves either way."""
        if self._slo is None:
            return {"enabled": False}
        return self._slo.scorecard()

    @property
    def capture_dirs(self) -> List[str]:
        """Completed deep-capture window directories (each mergeable
        into one Perfetto timeline by ``tools/tracemerge.py``)."""
        return [] if self._cap is None else list(self._cap.captures)

    def capture(self, steps: Optional[int] = None,
                reason: str = "manual",
                out_dir: Optional[str] = None) -> Optional[str]:
        """Arm an explicit deep-capture window around the next
        ``steps`` engine steps (default ``AnomalyConfig.
        capture_steps``): a bounded ``jax.profiler`` device trace +
        the window's host spans + a flight dump, merged into one
        Perfetto timeline by ``tools/tracemerge.py``.  Returns the
        capture directory (recording starts at the next step
        boundary), or None when a window is already armed/active.
        ``out_dir`` overrides the configured directory for a manager
        not yet constructed; with neither configured nor passed this
        raises — an explicit capture with nowhere to write is a
        caller error (the ANOMALY path degrades instead)."""
        self._settle()      # the window opens on a step boundary
        cap = self._ensure_capture(out_dir)
        if cap is None:
            raise ValueError(
                "no capture directory: pass out_dir=, or set "
                "InferenceConfig.profile / FailureConfig.flight_dir")
        return cap.arm(steps or self._acfg.capture_steps, reason,
                       budgeted=False)

    def arm_budgeted_capture(self, reason: str = "ops") -> Optional[str]:
        """Arm a capture window under the SAME budget the anomaly path
        uses (``AnomalyConfig.max_captures``, one window at a time) —
        the form the gateway's ``POST /debug/capture`` rides, so a wire
        client can never open an unbounded window.  Returns the capture
        dir, or None when no directory is configured, the budget is
        exhausted, or a window is already armed/active (all the quiet
        degradations the anomaly path has)."""
        cap = self._ensure_capture()
        if cap is None:
            return None
        return cap.arm(self._acfg.capture_steps, reason, budgeted=True)

    def _ensure_capture(self, out_dir: Optional[str] = None):
        """The capture manager, constructed on first need from the
        first configured directory (explicit ``out_dir``, then
        ``InferenceConfig.profile``, then ``FailureConfig.flight_dir``
        — the post-mortem dir is a sensible home for anomaly
        captures).  None — once loudly — when no directory exists."""
        if self._cap is None:
            d = out_dir or self.icfg.profile \
                or getattr(self, "fcfg", None) and self.fcfg.flight_dir
            if not d:
                if not self._warned_no_capture_dir:
                    self._warned_no_capture_dir = True
                    logger.warning(
                        "anomaly capture skipped: no capture directory "
                        "(set InferenceConfig.profile or FailureConfig."
                        "flight_dir) — detectors still fire/count")
                return None
            self._cap = ProfilerCapture(
                d, tracer=self.tracer,
                max_captures=self._acfg.max_captures)
        return self._cap

    def _on_anomaly(self, ev) -> None:
        """One fired detector: breadcrumb it into the flight recorder
        (the counter was bumped by the monitor) and — budget and
        one-window-at-a-time permitting — arm a deep capture around
        the next ``capture_steps`` steps so the artifact answers WHY,
        not just WHEN."""
        self.flight.note("anomaly", **ev.as_dict())
        cap = self._ensure_capture()
        if cap is not None:
            cap.arm(self._acfg.capture_steps,
                    f"anomaly_{ev.signal}", budgeted=True)

    def _feed_step_signals(self, t0: float, t2: float,
                           t3: float) -> None:
        """Feed the per-dispatch anomaly signals from the timestamps
        and counters the step already took — zero added clock reads.
        Called only when the monitor exists."""
        anom, prev, tm = self._anom, self._anom_prev, self.timings
        step = self._steps_done
        fired = []
        last_t0 = prev.get("t0")
        prev["t0"] = t0
        if last_t0 is not None:
            fired.append(anom.observe("step_interval_ms",
                                      (t0 - last_t0) * 1e3, step))
        fired.append(anom.observe("step_device_ms", (t3 - t2) * 1e3,
                                  step))
        fired.append(anom.observe("step_host_ms", (t2 - t0) * 1e3,
                                  step))
        retr = tm["compile_retraces"]
        fired.append(anom.observe("retrace",
                                  retr - prev.get("retrace", 0), step))
        prev["retrace"] = retr
        ref = float(self.state.pool_stats()["referenced"])
        last_ref = prev.get("referenced")
        prev["referenced"] = ref
        if last_ref is not None:
            fired.append(anom.observe("kv_referenced_delta",
                                      ref - last_ref, step))
        prompt, cached = tm["prompt_tokens"], tm["cached_tokens"]
        dp = prompt - prev.get("prompt", 0)
        if dp > 0:
            fired.append(anom.observe(
                "prefix_hit_rate",
                (cached - prev.get("cached", 0)) / dp, step))
        prev["prompt"], prev["cached"] = prompt, cached
        for ev in fired:
            if ev is not None:
                self._on_anomaly(ev)

    def request_metrics(self) -> Dict:
        """Per-request lifecycle story + fleet aggregate:
        ``{"aggregate": {requests/finished/open, ttft_ms/tpot_ms/
        queue_wait_ms summaries}, "requests": [record dicts]}`` —
        records carry queue_wait/TTFT/TPOT/e2e ms and prompt/cached/
        generated token counts that reconcile exactly with the
        ``engine.timings`` counters (tests/test_telemetry.py holds the
        invariant)."""
        return {"aggregate": self.requests.aggregate(),
                "requests": [r.as_dict() for r in self.requests.records()]}

    def metrics_snapshot(self) -> Dict:
        """JSON-able snapshot of every serving metric (counters +
        latency histograms); see also ``engine.metrics.prometheus_text()``
        and ``engine.metrics.write_jsonl(path)``."""
        return self.metrics.snapshot()

    def publish_metrics(self, monitor, step: int = 0) -> None:
        """Fan the current metric values out through a ``monitor/``
        writer (CSV/TensorBoard/WandB/Comet) — serving metrics ride the
        same pipeline as training scalars."""
        self.metrics.publish(monitor, step)

    def _serving_weights(self, params, announce: bool = False):
        """The tree this engine serves, from a model's parameter tree:
        float32 leaves in the serving type, and the attention
        projections folded to the matrices their products read
        (``model.fold_projection``; ``wo`` only where this engine serves
        it dense), a leaf at a time, so that no cast copy of a
        projection outlives its fold.  The caller's tree is
        left as it is; a tree that is folded already (the one this
        engine served, a template's weight store) passes through.
        ``announce``: log the folded leaves and their bytes (once an
        engine, at construction)."""
        folded = {}

        def take(path, x):
            if x.dtype == jnp.float32:
                x = x.astype(self.icfg.param_dtype)
            y = fold_projection(path, x, wo=not self.icfg.weight_quant)
            if y is not x:
                folded[jax.tree_util.keystr(path)] = y.nbytes
            return y

        tree = jax.tree_util.tree_map_with_path(take, params)
        if folded and announce:
            logger.info("serving weights: %d attention projections folded "
                        "to rank 3, %d bytes (%s)", len(folded),
                        sum(folded.values()), ", ".join(folded))
        return tree

    def refresh_params(self, params) -> None:
        """Swap the served weights (hybrid-engine policy refresh).

        Re-applies the serving cast AND re-quantizes under weight_quant —
        the step closure captures the quantized tree, so merely assigning
        ``self.params`` would keep serving the old quantized weights."""
        self._settle()      # the launch in flight ran on the old weights
        if self._stream is not None:
            raise NotImplementedError(
                "refresh_params under weight_stream: re-spill the store "
                "by rebuilding the engine")
        self.params = self._serving_weights(params)
        if self.icfg.weight_quant:
            from .quantization import quantize_model_params
            from ..ops.quant import WEIGHT_QUANT_BITS
            self.params, self._quant = quantize_model_params(
                self.params, bits=WEIGHT_QUANT_BITS[self.icfg.weight_quant],
                quantize_embeddings=self.icfg.quantize_embeddings)
            # step closures hold the old quant tree
            self._pstep_fns.clear()
            self.serving_programs.clear()
            # the rebuilt programs recompile on their next call: they
            # are cold again (warm programs run under the watchdog,
            # and a deadline must never time an XLA compile)
            self._warm_keys.clear()
            # rebuilding against fresh weights is a LEGITIMATE
            # recompile: reset the retrace ledger and the per-program
            # cost table (the new programs get probed anew)
            self._compiled_ever.clear()
            if self.devtel is not None:
                self.devtel.program_costs.clear()
        self._shard_weights()

    # ------------------------------------------------------------------
    # SPMD sharding (TP + ZeRO-Inference weight sharding)
    # ------------------------------------------------------------------
    def _kv_head_groups(self, topology) -> int:
        """The chips a tensor mesh splits the kv heads over (1: none)."""
        tp = topology.tp_size if (
            topology is not None and topology.device_count > 1) else 1
        split = (tp > 1 and self.cfg.num_kv_heads % tp == 0
                 and self.cfg.num_heads % tp == 0)
        return tp if split else 1

    def _setup_sharding(self) -> None:
        """Resolve mesh shardings once: KV head-split + weight specs."""
        self._repl = None
        self._kv_nsh = None
        self._tp_mesh = None
        topo = self.topology
        if topo is None:
            return
        self._repl = topo.replicated
        head_split = self._kv_head_groups(topo) > 1
        heads = TENSOR_AXIS if head_split else None
        # kv: [L, blocks, bs, 2, Hkv, D] — split the kv-head dim; a
        # quantized cache's scales [L, blocks, Hkv, 2 * bs] with it
        self._kv_nsh = NamedSharding(topo.mesh,
                                     P(None, None, None, None, heads))
        if isinstance(self.state.kv, tuple):
            self._kv_nsh = (self._kv_nsh,
                            NamedSharding(topo.mesh, P(None, None, heads)))
        if head_split:
            # the Pallas kernel runs under shard_map, one head group/chip
            self._tp_mesh = topo.mesh
        self.state.kv = jax.device_put(self.state.kv, self._kv_nsh)
        self._shard_weights()

    def _resolve_serving_comm(self):
        """Resolve ``comm_overlap``/``comm_quant``/``comm_tiles`` against
        the mesh and model shapes into a :class:`ServingComm` plan (or
        None).  The contract: an eligible mesh gets the decomposed
        collectives, anything else degrades LOUDLY to the serial exact
        path — never an error, because one config must serve on a
        laptop and on the pod (docs/SERVING.md "Overlapped & quantized
        collectives")."""
        mode = self.icfg.comm_overlap
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"comm_overlap={mode!r}: expected 'auto', "
                             "'on', or 'off'")
        qname = self.icfg.comm_quant
        if qname not in (None, "int8", "int4"):
            raise ValueError(f"comm_quant={qname!r}: expected None, "
                             "'int8', or 'int4'")
        topo = self.topology
        tp = 0 if topo is None else topo.tp_size
        if tp <= 1:
            if mode == "on" or qname is not None:
                logger.warning(
                    "comm_overlap/comm_quant: no tensor axis on this "
                    "engine (%s) — collectives stay serial and exact",
                    "single-chip" if topo is None
                    else f"mesh {topo.axis_sizes}")
            return None
        if mode == "off" and qname is None:
            return None
        cfg = self.cfg
        downproj = cfg.num_experts <= 1 and cfg.d_ff % tp == 0
        unembed = cfg.vocab_size % tp == 0
        if not downproj and not unembed:
            logger.warning(
                "comm_overlap: neither d_ff=%d nor vocab=%d is eligible "
                "on tensor=%d (MoE layers and indivisible dims stay "
                "with GSPMD); serial exact collectives",
                cfg.d_ff, cfg.vocab_size, tp)
            return None
        bits = {None: None, "int8": 8, "int4": 4}[qname]
        if bits is not None and not downproj:
            logger.warning(
                "comm_quant=%s: the down-projection all-reduce is "
                "ineligible on this model/mesh and the logits gather "
                "never quantizes — exact wire", qname)
            bits = None
        if mode == "off":
            # comm_quant alone: ONE serial quantized all-reduce on the
            # down-projection — and nothing else; "off" must leave the
            # unembed gather with GSPMD (quantization never applies to
            # it, and a tiles=1 ppermute ring would replace the fused
            # all-gather for no benefit)
            if bits is None:
                return None
            tiles, unembed = 1, False
        else:
            tiles = max(1, self.icfg.comm_tiles)
        from ..comm.overlap import ServingComm
        return ServingComm(mesh=topo.mesh, axis_name=TENSOR_AXIS,
                           tiles=tiles, quant_bits=bits,
                           downproj=downproj, unembed=unembed)

    def _shard_weights(self) -> None:
        """Place the (possibly quantized) weight trees on the mesh.

        Dense un-quantized weights use the logical-axis TP rules
        (parallel/sharding.py — the same specs that shard training), with
        any ``fsdp`` axis layered on as pure memory sharding (the
        ZeRO-Inference analog: XLA all-gathers each layer at use).
        Quantized trees have grouped flat layouts the head rules cannot
        address, so they are memory-sharded over the largest divisible
        dim instead."""
        topo = self.topology
        if topo is None:
            return
        from ..parallel import sharding as shd

        def put(x, spec):
            return jax.device_put(x, NamedSharding(topo.mesh, spec))

        def generic(tree):
            """Memory-shard every array leaf: tensor axis first, then
            fsdp, over whichever large dims divide."""
            def go(x):
                if not isinstance(x, (jax.Array, np.ndarray)) \
                        or np.ndim(x) == 0:
                    return x
                spec = shd.add_fsdp_to_spec(P(), x.shape, topo,
                                            min_size=1 << 14,
                                            axis=TENSOR_AXIS)
                spec = shd.add_fsdp_to_spec(spec, x.shape, topo,
                                            min_size=1 << 14,
                                            axis=FSDP_AXIS)
                return put(x, spec)
            return jax.tree.map(go, tree)

        if self._quant is None:
            shapes = jax.tree.map(lambda x: tuple(x.shape), self.params)
            specs = shd.tree_specs(self._folded_axes(), topo, shapes=shapes)
            is_spec = lambda s: isinstance(s, P)   # noqa: E731
            specs = jax.tree.map(
                lambda s, x: shd.add_fsdp_to_spec(s, tuple(x.shape), topo,
                                                  min_size=1 << 14),
                specs, self.params, is_leaf=is_spec)
            self.params = jax.tree.map(put, self.params, specs,
                                       is_leaf=lambda x: isinstance(x, P))
        else:
            # dense remainder (norms/biases/embeds) + quantized payloads
            self.params = generic(self.params)
            self._quant = generic(self._quant)

    def _folded_axes(self):
        """The model's logical axes with a folded projection's ``(heads,
        head_dim)`` as the one axis ``heads`` (``kv_heads``): the rules
        give it the tensor axis, and ``H*D / tp`` contiguous columns
        (rows of ``wo``) are whole heads where ``tp`` divides the heads,
        which is all the rules could ask of the unfolded leaf's ``H``;
        where it does not, the axis is left whole."""
        tp = self.topology.tp_size
        is_axes = lambda a: isinstance(a, tuple) and all(  # noqa: E731
            e is None or isinstance(e, str) for e in a)

        def fold(ax, w):
            if len(ax) == w.ndim:
                return ax
            i = ax.index("head_dim")
            heads = {"heads": self.cfg.num_heads,
                     "kv_heads": self.cfg.num_kv_heads}[ax[i - 1]]
            return ax[:i - 1] + (ax[i - 1] if heads % tp == 0 else None,) \
                + ax[i + 1:]

        axes = self.model.param_axes
        if self._stream is not None:
            # block weights were spilled to the NVMe store; only the
            # resident remainder needs placement
            axes = {k: v for k, v in axes.items() if k in self.params}
        return jax.tree.map(fold, axes, self.params, is_leaf=is_axes)

    def _setup_weight_stream(self) -> None:
        """Spill per-layer block weights (quantized payloads under
        weight_quant) to the NVMe store; the forward streams them back
        one layer at a time.  HBM then holds: embeddings/head/norms, the
        KV cache, and ONE layer's weights."""
        from .weight_stream import NVMeWeightStore

        store = NVMeWeightStore(self.icfg.weight_stream,
                                self.cfg.num_layers)
        if self.topology is not None:
            # SPMD serving: the fetch callback pins to one mesh device;
            # GSPMD broadcasts each layer to the mesh at first use
            store.spmd_device = self.topology.mesh.devices.flat[0]
        record: Dict[str, object] = {"dense": self.params.pop("blocks")}
        store.qmeta = None
        if self._quant is not None and self._quant.get("blocks"):
            qblocks = self._quant["blocks"]
            self._quant = {**self._quant, "blocks": {}}
            qarrays, qmeta = {}, {}
            for gname, grp in qblocks.items():
                qarrays[gname], qmeta[gname] = {}, {}
                for name, qt in grp.items():
                    a = {"data": qt.data, "scale": qt.scale}
                    if qt.zero is not None:
                        a["zero"] = qt.zero
                    qarrays[gname][name] = a
                    qmeta[gname][name] = (qt.bits, qt.shape[1:], qt.dtype,
                                          qt.layout)
            record["quant"] = qarrays
            store.qmeta = qmeta
            # mixed-gemm eligibility: row-wise int8 (weight-shaped) or
            # packed row-wise int4 per-layer payloads; expert and
            # shared-expert weights don't count — the forward always
            # consumes them dense
            from ..ops.quant import is_mixed_gemm_layout
            from .quantization import DENSE_ONLY_GROUPS
            store.mixed_gemm_eligible = all(
                is_mixed_gemm_layout(qt)
                for gname, grp in qblocks.items()
                if gname not in DENSE_ONLY_GROUPS
                for qt in grp.values())
        store.spill(record)
        self._stream = store

    def _setup_spec_decode(self) -> None:
        """Resolve the ``spec_decode`` config to a proposer (or None)
        and the engine's fixed verify-window width ``_n_verify``
        (``spec_max_draft + 1`` when on, else 1 — which keeps every
        compiled program byte-identical to a pre-spec engine)."""
        mode = self.icfg.spec_decode
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"spec_decode={mode!r}: expected 'auto', "
                             "'on', or 'off'")
        if mode == "on" and self._stream is not None:
            # a verify window is worthless when each layer streams from
            # NVMe at step latency anyway ("auto" resolves off silently)
            logger.warning("weight_stream: spec_decode='on' needs "
                           "resident weights; forcing spec_decode='off'")
            mode = "off"
            self.icfg = dataclasses.replace(self.icfg, spec_decode=mode)
        # "auto" currently resolves OFF: draft acceptance is workload-
        # dependent, and the per-request acceptance_rate / draft-length
        # profiles recorded below are exactly the measured signal the
        # autotuner (ROADMAP item 4) needs to flip this from data
        on = mode == "on"
        self._spec = None
        self._n_verify = 1
        if on:
            if self.icfg.spec_max_draft < 1:
                raise ValueError("spec_max_draft must be >= 1")
            from .spec_decode import NgramProposer
            self._spec = NgramProposer(self.icfg.spec_max_draft)
            self._n_verify = self.icfg.spec_max_draft + 1
        self._sched_drafts: Dict[int, List[int]] = {}

    def _place_default_device(self) -> None:
        """Ship weights to the serving device if they were built on
        another backend — the ZeRO-Inference big-model flow: a model too
        large to materialize dense in HBM is initialized/loaded and
        group-quantized ON HOST (``jax.default_device(cpu)``), and only
        the int8/int4 payloads ever reach the chip (reference:
        inference/quantization — quantize-then-place)."""
        dev = jax.devices()[0]

        def to_dev(x):
            if isinstance(x, jax.Array) and x.committed and \
                    next(iter(x.devices())).platform != dev.platform:
                return jax.device_put(x, dev)
            return x

        self.params = jax.tree.map(to_dev, self.params)
        if self._quant is not None:
            self._quant = jax.tree.map(to_dev, self._quant)

    def _stage(self, tree):
        """Replicate host-built batch metadata onto the mesh."""
        if self._repl is None:
            return tree
        return jax.device_put(tree, self._repl)

    def _kv_zeros(self):
        """A pristine zero cache with the serving sharding applied."""
        kv = self.state.cfg.cache_zeros(self.icfg.max_seqs)
        if self._kv_nsh is not None:
            kv = jax.device_put(kv, self._kv_nsh)
        return kv

    # ------------------------------------------------------------------
    def _resolve_fw(self, mbs: Optional[int]):
        """The forward-pass knobs shared by every compiled serving
        program."""
        return dict(attn_impl=self.attn_impl,
                    mixed_gemm=self._mixed_gemm_active,
                    shard_mesh=self._tp_mesh, stream=self._stream,
                    comm=self._comm_active), mbs or self.max_blocks_per_seq

    def _donate_kv(self) -> bool:
        """Whether serving programs donate the paged cache.  See
        ``InferenceConfig.kv_donate``: donation on XLA:CPU blocks each
        dispatch until the in-flight producer of the donated cache
        finishes, so a CPU engine trades one transient cache copy for
        async dispatch."""
        mode = self.icfg.kv_donate
        if mode == "auto":
            return jax.default_backend() != "cpu"
        return mode != "off"

    def _serving_jit(self, fn, kv_argnum: int = 2,
                     kv_only_output: bool = False):
        """jit a serving program whose paged-KV operand rides
        ``kv_argnum`` and whose output is (small replicated output,
        new_kv) — or bare new_kv with ``kv_only_output`` (the COW block
        copy) — with the cache donated (see ``_donate_kv``) and its
        head-split sharding pinned.  THE one place the KV
        donation/placement jit policy lives."""
        donate = (kv_argnum,) if self._donate_kv() else ()
        if self._kv_nsh is not None:
            # logits/tokens replicated (one small host fetch), cache
            # keeps its head-split sharding across the donation
            out_sh = self._kv_nsh if kv_only_output \
                else (self._repl, self._kv_nsh)
            return jax.jit(fn, donate_argnums=donate, out_shardings=out_sh)
        return jax.jit(fn, donate_argnums=donate)

    def _build_step(self, mbs: Optional[int] = None,
                    with_routing: bool = False):
        """Compile one SplitFuse step bounded to ``mbs`` context blocks —
        the logits-returning sibling of :meth:`_build_pstep` (the serving
        loop runs pstep; this entry serves logits-level consumers:
        quant/TP parity tests and offline scoring).  ``with_routing``
        (sparse-expert models): a third output, the experts each row
        took in each expert layer (``ragged_forward``).

        Steps are compiled per power-of-two context bucket: the XLA
        attention paths do work proportional to the compiled block
        bound, so early prefill steps must not pay for the engine's
        maximum context (the Pallas kernel skips dead blocks
        dynamically; the dense paths cannot)."""
        cfg = self.cfg
        bs = self.icfg.kv_block_size
        fw, mbs = self._resolve_fw(mbs)
        if with_routing and cfg.num_experts <= 1:
            raise ValueError("with_routing: the model routes no token")

        # NOTE: the quant tree is a jit ARGUMENT, never a closure —
        # closed-over trees bake into the HLO as constants (7.5 GB of
        # captured constants for llama3-8b int8, which killed the remote
        # compile); as an argument it is device buffers, like params
        def step(params, quant, kv, batch: RaggedBatch):
            return ragged_forward(cfg, params, kv, batch, bs, mbs,
                                  quant=quant, with_routing=with_routing,
                                  **fw)

        return self._serving_jit(step)

    def _build_pstep(self, mbs: Optional[int], sampling: SamplingParams):
        """Compile one pipelined serving step for a context bucket:
        deferred-token feedback + ragged forward + ON-DEVICE sampling.
        The sampled [max_seqs] token array is both a program output (read
        back one step later) and the next step's feedback operand, so
        the host round trip leaves the critical path.  Cached per
        (bucket, sampler_key) — stop_token/max_new_tokens are host loop
        concerns and never force a recompile."""
        cfg = self.cfg
        bs = self.icfg.kv_block_size
        fw, mbs = self._resolve_fw(mbs)

        def sample_fn(logits, keys):
            # no sharding constraint needed for seeded streams to be
            # invariant to the comm plan: jax's partitionable threefry
            # (the default) draws the same bits whether the logits
            # arrive vocab-sharded (serial unembed) or replicated (the
            # shard_map overlap path) — tests/test_comm_overlap.py
            # holds on-vs-off seeded tokens equal
            return sample_rows(logits, sampling, keys)

        def pstep(params, quant, kv, batch: RaggedBatch, prev_toks, rng):
            return pipelined_ragged_step(cfg, params, quant, kv, batch,
                                         prev_toks, rng, sample_fn,
                                         bs, mbs, **fw)

        return self._serving_jit(pstep)

    def _quant_is_rowwise(self) -> bool:
        """The mixed-input kernel family consumes the row-wise int8
        (weight-shaped payload) and packed row-wise int4 layouts.
        Only the weights the ``_mm`` projection sites consume count:
        expert/shared-expert weights (dense in moe_ffn/_shared_expert)
        and the embedding table (dequantized once per step) are always
        dequantized regardless."""
        from ..ops.quant import QuantizedTensor, is_mixed_gemm_layout
        from .quantization import DENSE_ONLY_GROUPS
        if self._quant is None:
            return False
        blocks = {k: v for k, v in
                  (self._quant.get("blocks") or {}).items()
                  if k not in DENSE_ONLY_GROUPS}
        leaves = [x for x in jax.tree.leaves(
            blocks, is_leaf=lambda x: isinstance(x, QuantizedTensor))
            if isinstance(x, QuantizedTensor)]
        return bool(leaves) and all(is_mixed_gemm_layout(q)
                                    for q in leaves)

    def _require_mixed_gemm_eligible(self) -> None:
        streamed = self._stream is not None
        if not (self._stream.mixed_gemm_eligible if streamed
                else self._quant_is_rowwise()):
            what = ("the weight-stream payloads are" if streamed
                    else "the resident quantized weights are")
            raise ValueError(
                f"mixed_gemm='on': {what} not a row-wise int8/int4 "
                "layout the kernel family consumes; use 'off'")

    # ------------------------------------------------------------------
    # request API (reference: engine_v2.put :107)
    # ------------------------------------------------------------------
    def put(self, uid: int, tokens: Sequence[int], priority: int = 0,
            deadline_ms: Optional[float] = None,
            slo_class: Optional[str] = None,
            max_new_tokens: Optional[int] = None) -> AdmissionVerdict:
        """Enqueue a new request or continue a known one; returns an
        :class:`AdmissionVerdict` (truthy iff the tokens entered the
        engine) instead of growing the backlog unboundedly.

        ``priority``: lower = more important (nice-level semantics;
        default 0).  ``deadline_ms``: relative to arrival — a request
        still unfinished when it elapses is terminally closed with
        status ``deadline_exceeded``.  Both only matter on the FIRST
        put for a uid; continuations keep the admitted values and are
        never shed (the request already holds KV or a queue place).
        ``slo_class`` tags the lifecycle record with the class the
        request was admitted under — pure attribution for the SLO
        scorecard (telemetry/slo.py); it changes no admission or
        scheduling decision here (class->priority/deadline folding is
        the gateway's job, class->pool the fleet router's).  With the
        default :class:`OverloadConfig` (unbounded queue) the verdict
        is always truthy — legacy callers that ignore the return value
        see the legacy behavior.

        ``max_new_tokens`` (first put only) hands the request's
        continuation to the ENGINE: each token it samples is fed back by
        the engine itself, on the device where :meth:`step` runs ahead,
        until that many were emitted.  The caller only reads the tokens
        ``step()`` returns and ends the request (``flush``/``cancel``),
        or pauses it (:meth:`hold`); it puts no continuation.  Without
        it the caller feeds every continuation, and a step that holds
        such a request's row is launched and read back in one call."""
        now = time.perf_counter()
        toks = [int(t) for t in tokens]
        if uid in self._meta or uid in self.state.seqs \
                or uid in self._pending:
            self.requests.on_arrival(uid, now, slo_class=slo_class)
            self._pending.setdefault(uid, []).extend(toks)
            return AdmissionVerdict(True, "continued")
        if self._draining or self._health == "dead":
            # the drain/death contract: admission is stopped for NEW
            # requests (the continuation branch above still lands —
            # in-flight work must be able to finish); the record exists
            # so the router sees shed-at-drain, not silence (and the
            # class tag keeps the shed attributable to its SLO budget)
            self.requests.on_arrival(uid, now, slo_class=slo_class)
            self.requests.on_finish(uid, now, status="shed")
            return AdmissionVerdict(False, "shed",
                                    reason="engine is "
                                    + ("dead" if self._health == "dead"
                                       else "draining"))
        ocfg = self.ocfg
        queued: List[tuple] = []
        if ocfg.max_queued_requests is not None \
                or ocfg.max_queued_tokens is not None:
            # requests still waiting for their FIRST admission (a live
            # sequence is not queued — it is never shed here)
            for quid, qt in self._pending.items():
                if not qt or quid in self.state.seqs:
                    continue
                m = self._meta.get(quid)
                queued.append((
                    quid,
                    effective_priority(m.priority if m else 0,
                                       m.t_arrival if m else now,
                                       now, ocfg.aging_ms),
                    len(qt)))
        action, victims = admission_decision(ocfg, priority, len(toks),
                                             queued, now)
        if action == "shed":
            # terminal from birth: the record exists (the load harness
            # counts shed vs finished) but never holds KV or budget
            self.requests.on_arrival(uid, now, slo_class=slo_class)
            self.requests.on_finish(uid, now, status="shed")
            return AdmissionVerdict(False, "shed",
                                    reason="admission queue bound")
        for victim in victims:
            self._finish(victim, "shed")
            self._reaped.add(victim)
        if action == "degrade":
            priority = max(priority, ocfg.degrade_priority)
        self._meta[uid] = RequestMeta(priority=priority,
                                      deadline_ms=deadline_ms,
                                      t_arrival=now,
                                      degraded=(action == "degrade"))
        if deadline_ms is not None:
            self._deadline_uids.add(uid)
        if max_new_tokens is not None:
            self._cont[uid] = int(max_new_tokens)
        self.requests.on_arrival(uid, now, slo_class=slo_class)
        self._pending.setdefault(uid, []).extend(toks)
        if self._spec is not None:
            # seed the prompt-lookup history with the prompt (emitted
            # tokens are observed at collect; continuation puts carry
            # tokens the history already holds)
            self._spec.observe(uid, toks)
        return AdmissionVerdict(
            True, "degraded" if action == "degrade" else "queued",
            evicted_uids=victims)

    def flush(self, uid: int) -> None:
        """(reference: engine_v2.flush :242)."""
        self._finish(uid, "finished")

    def hold(self, uid: int) -> None:
        """Pause a request whose continuation the engine owns (the
        gateway's backpressure: its client has not read the last token
        yet).  The continuation the engine queued for itself is taken
        back — a row already launched ahead with it is rewound and its
        result thrown away at collect, at most one token computed in
        vain — and nothing more is scheduled for ``uid`` until the
        caller puts the last token it was given, which resumes the
        request exactly where a caller-fed one would be.  A no-op for
        any other request."""
        seq = self.state.seqs.get(uid)
        if uid not in self._cont or seq is None:
            return
        self._pending[uid] = []
        self._fb_step.pop(uid, None)
        st = self._ahead
        if st is not None and uid in st.uids and uid not in self._void:
            self.state.rewind(uid)
            self._void[uid] = st.sid

    def cancel(self, uid: int) -> None:
        """Client abort: terminally close ``uid`` wherever it is —
        queued (drops its backlog entry), running (KV released back
        through the refcounted allocator), or already gone (no-op).
        Safe mid-flight: an uncollected step's emit for a cancelled uid
        is discarded by the slot guard in ``_collect``, and its stale KV
        writes land in rows no surviving sequence reads."""
        self._finish(uid, "cancelled")
        self._reaped.add(uid)

    def _finish(self, uid: int, status: str) -> None:
        """Terminally close a request through whichever exit applies: a
        live sequence releases its KV (the ``on_release`` hook below
        does the bookkeeping), a queued-only request just drops its
        backlog entry.  Idempotent — closing an already-closed or
        unknown uid is a no-op."""
        if uid in self.state.seqs:
            self._closing[uid] = status
            try:
                self.state.release(uid)   # -> _on_state_release
            finally:
                self._closing.pop(uid, None)
            return
        self._forget(uid, status)

    def _forget(self, uid: int, status: str) -> None:
        """Drop every per-request bookkeeping entry and close the
        lifecycle record terminally — the ONE teardown both exit shapes
        (queued-only close, KV-release close) share; add any future
        per-request state here and it is cleaned on every path."""
        self._pending.pop(uid, None)
        self._fb_step.pop(uid, None)
        self._cont.pop(uid, None)
        self._void.pop(uid, None)
        self._meta.pop(uid, None)
        self._deadline_uids.discard(uid)
        self._preempt_gen.pop(uid, None)
        self._ctx_exhausted.discard(uid)
        self._strikes.pop(uid, None)
        if self._spec is not None:
            self._spec.forget(uid)
        rec = self.requests.open.get(uid) if self._anom is not None \
            else None
        self.requests.on_finish(uid, status=status)
        if rec is not None and rec.tpot_ms is not None:
            # TPOT is only final at terminal close — feed it here so a
            # decode-tail slowdown is a per-request latency signal too
            evt = self._anom.observe("tpot_ms", rec.tpot_ms,
                                     self._steps_done)
            if evt is not None:
                self._on_anomaly(evt)

    def _on_state_release(self, uid: int) -> None:
        """``StateManager.on_release`` hook: a sequence's KV was just
        freed.  Preemption is the one non-terminal release (the request
        re-queues and its record stays open); every other path closes
        the lifecycle record — ``flush`` ("finished"), engine close-outs
        (the status staged in ``_closing``: deadline expiry, cancel,
        context exhaustion), or a direct ``StateManager.release`` from
        outside the engine ("released").  This is what makes
        ``request_metrics()`` leak-free: there is no way to drop KV
        without a terminal lifecycle event."""
        if uid in self._preempting:
            return
        self._forget(uid, self._closing.get(uid, "released"))

    def _drain_reaped(self) -> set:
        """Uids the ENGINE terminally closed since the last call
        (deadline expiry, ``cancel()``, shed-by-eviction, context
        exhausted) — ``generate()`` drops them from its active set;
        direct-API callers can poll ``query()["status"]`` instead."""
        out = self._reaped
        self._reaped = set()
        return out

    def query(self, uid: int) -> Dict:
        """(reference: engine_v2.query :158).  ``status`` is ``queued``
        (admitted, waiting for KV — including preempted-and-requeued),
        ``running`` (holds KV), a terminal status (``finished`` /
        ``shed`` / ``cancelled`` / ``deadline_exceeded`` /
        ``context_exhausted`` / ``released`` / ``failed``),
        ``forgotten`` for a uid whose terminal record aged out of the
        finished ring (sized by ``OverloadConfig.status_retention``),
        or ``unknown`` for a uid the engine never saw — so load-harness
        clients can tell shed from done from a retention miss instead
        of reading silent zeros."""
        seq = self.state.seqs.get(uid)
        if seq is not None:
            status = "running"
        elif self._pending.get(uid) or uid in self._meta:
            status = "queued"
        else:
            s = self.requests.status_of(uid)
            status = "queued" if s == "open" else (s or "unknown")
        gen = self._preempt_gen.get(uid, [])
        return {
            "status": status,
            "pending_tokens": len(self._pending.get(uid, [])),
            "seen_tokens": seq.seen_tokens if seq else 0,
            # across preemptions: tokens generated before each eviction
            # are stashed so the full output survives the re-prefill
            "generated": list(gen) + (list(seq.tokens) if seq else []),
            "max_context": self.max_blocks_per_seq * self.icfg.kv_block_size,
            # prompt tokens this sequence got from the prefix cache
            # (prefill started at the first uncached token)
            "cached_tokens": seq.cached_tokens if seq else 0,
        }

    # ------------------------------------------------------------------
    def _schedule(self) -> List[tuple]:  # tpulint: serving-loop
        """Dynamic SplitFuse + overload policy: pack the fixed token
        budget — decode tokens first (latency), then prompt chunks
        (throughput) — while *reserving* KV blocks and slots as requests
        are admitted so the collective admission can never exceed the
        pool (reference: can_schedule engine_v2.py:184 +
        SchedulingResult).

        New prompts first consult the prefix cache: the longest cached
        block-aligned prefix is aliased into the sequence's table and
        those tokens never enter the budget — prefill starts at the
        first uncached token.  Blocks/slots are tracked as *reservations*
        against the live allocator (matching mutates it mid-round).

        Overload policy (docs/SERVING.md "Surviving overload"): expired
        deadlines are reaped first; candidates are ordered by *aged*
        effective priority within each class (decode before prefill —
        TPOT never queues behind prompt work); each prefill takes at
        most ``prefill_chunk`` tokens per step so a long prompt
        interleaves instead of head-of-line-blocking; and when the pool
        or slot table starves a candidate, a strictly-lower-priority
        running victim is preempted-by-eviction (``_preempt``) to make
        room.  With the default config every knob is inert and this is
        exactly the legacy FIFO SplitFuse packer.

        A verify window emits up to 1 + len(draft) tokens, so the
        drafts of a request the engine continues are capped by what it
        may still emit (``_cont``): the engine never emits — or counts
        — a token past ``max_new_tokens``."""
        budget = self.icfg.token_budget
        bs = self.icfg.kv_block_size
        ocfg = self.ocfg
        now = time.perf_counter()
        self._sched_drafts = {}
        self._reap_deadlines(now)
        if self._backoff_rounds > 0:
            # retry backoff after a transient step failure: admit
            # nothing for a bounded, step-counted number of rounds
            self._backoff_rounds -= 1
            return []
        # bisection quarantine: while probe groups are queued, ONLY the
        # head group's requests are schedulable — each probe step either
        # clears its group (success) or bisects it further (failure),
        # so the poison request is isolated in O(log batch) steps.
        # Groups whose requests all left the engine (cancel/fail/flush)
        # are pruned or the quarantine would wedge the scheduler.
        probe_allowed = None
        while self._probe_groups:
            head = [u for u in self._probe_groups[0]
                    if self._pending.get(u) or u in self.state.seqs]
            if head:
                probe_allowed = set(head)
                break
            self._probe_groups.pop(0)
        # blocks/slots promised to earlier admits this round but only
        # allocated for real in build_batch
        reserved_blocks = 0
        reserved_slots = 0
        prefix_on = self.state.prefix_cache
        sched: List[tuple] = []
        sched_uids: set = set()
        preempts_left = (ocfg.max_preemptions_per_step
                         if ocfg.preemption else 0)
        self._round_preemptions = 0     # the stage span's ``preemptions``
        self._round_cached = 0          # and its ``cached_tokens``
        # a model with recurrent layers: the runs of several tokens a
        # step may hold (its chunk table is of fixed size)
        run_cut = self.state.cfg.runs
        scan_runs_left = run_cut.scan_runs if run_cut is not None else 0

        def admit(uid, toks) -> str:
            """"ok" (tokens or a cache match landed), "starved" (the
            block pool or slot table blocked it — a preemption could
            help), or "skip" (nothing a preemption can fix)."""
            nonlocal budget, reserved_blocks, reserved_slots, scan_runs_left
            seq = self.state.seqs.get(uid)
            ctx_rem = self.state.context_remaining(uid)
            if ctx_rem <= 0:
                self._ctx_exhausted.add(uid)
                return "skip"
            needs_slot = uid not in self.state._slots
            if needs_slot and \
                    len(self.state._free_slots) - reserved_slots <= 0:
                return "starved"
            new_prompt = seq is None
            prompt_len = len(toks) if new_prompt else 0
            cached = 0
            if new_prompt and prefix_on and toks[0] != FEEDBACK_TOKEN:
                if self.state.restaging(uid):
                    # a tiered chain is restaging for this request —
                    # defer (keep it queued, schedule nothing): the
                    # pre-dispatch drain re-indexes the chain and the
                    # next round's match covers it, instead of
                    # re-prefilling content already in flight
                    return "ok"
                # the match may revive cached-free blocks / take a COW
                # copy ONLY from the headroom not already reserved by
                # earlier admits this round
                with self.tracer.span("ds.serve.prefix_match",
                                      track="schedule", uid=uid):
                    cached = self.state.match_prefix(
                        uid, toks,
                        max_pool_take=self.state.allocator.free_blocks
                        - reserved_blocks)
                if not cached and self.state.restaging(uid):
                    return "ok"       # the match itself began a restage
                if cached:
                    del toks[:cached]
                    seq = self.state.seqs[uid]
                    needs_slot = False     # match_prefix claimed the slot
                    ctx_rem = self.state.context_remaining(uid)
            draft: List[int] = []
            if (self._spec is not None and seq is not None
                    and len(toks) == 1 and toks[0] >= 0
                    and not seq.draft_len):
                # decoding row with a concrete fed token: mine a draft
                # window from the request's own history.  Drafted tokens
                # are REAL budget/block consumers (the window writes KV
                # like a chunked prefill), so it is capped by the step's
                # leftover budget and context headroom alongside
                # spec_max_draft — drafts compete with prefill chunks
                # for the same fixed SplitFuse budget
                limit = min(self._n_verify - 1, budget - 1, ctx_rem - 1)
                if uid in self._cont:
                    limit = min(limit, self._cont[uid] - 1)
                if limit > 0:
                    draft = self._spec.propose(uid, toks[0], limit)
            n = min(len(toks), budget, ctx_rem)
            if run_cut is not None and n > 1:
                if seq is not None and seq.state_ahead:
                    n = 1            # the row fed again goes alone
                elif scan_runs_left <= 0:
                    return "skip"    # next step: the chunk table is full
            if len(toks) > 1 and ocfg.prefill_chunk is not None:
                # chunked prefill: a prompt takes at most one chunk of
                # this step's budget; the remainder waits its turn while
                # other prefills (and every decode) share the step
                n = min(n, ocfg.prefill_chunk)
            nw = n + len(draft)       # scheduled window incl. drafts
            avail = self.state.allocator.free_blocks - reserved_blocks
            need = 0
            while nw > 0:
                seen = seq.seen_tokens if seq else 0
                have = len(seq.blocks) if seq else 0
                need = max(0, -(-(seen + nw) // bs) - have)
                if need <= avail:
                    break
                nw //= 2
            if nw <= 0:
                if not cached:
                    return "starved"
                draft, n = [], 0
            elif nw <= n:
                draft, n = [], nw     # pool pressure ate the window
            else:
                del draft[nw - n:]
            tm = self.timings
            tm["prompt_tokens"] += prompt_len
            if cached:
                tm["cached_tokens"] += cached
                tm["prefix_hits"] += 1
                self._round_cached += cached
            if prompt_len or cached:
                # lifecycle admission — SAME statement block as the
                # engine counters above, so per-request token sums
                # reconcile with them by construction
                self.requests.on_admitted(uid, prompt_len, cached,
                                          time.perf_counter())
            if n <= 0:
                # matched but the pool can't take the uncached remainder
                # yet: the sequence keeps its aliased blocks and waits
                return "ok"
            sched.append((uid, toks[:n] + draft))
            sched_uids.add(uid)
            scan_runs_left -= n > 1
            if draft:
                self._sched_drafts[uid] = draft
            del toks[:n]
            budget -= n + len(draft)
            reserved_blocks += need
            if needs_slot:
                reserved_slots += 1
            return "ok"

        # decode requests (continuing sequences, single token) first,
        # then prompt chunks — one O(n) pass keyed on the entry itself
        # (the old value-membership split re-scanned the decode list for
        # every pending request: O(n^2) tuple compares under load)
        decodes: List[tuple] = []
        prefills: List[tuple] = []
        effs: Dict[int, float] = {}
        for uid, t in self._pending.items():
            if not t:
                continue
            if probe_allowed is not None and uid not in probe_allowed:
                continue
            m = self._meta.get(uid)
            # aged priority: waiting promotes a tier per aging_ms, so a
            # low tier is delayed under load but never starved.  Equal
            # tiers keep FIFO order (aging is monotonic in arrival; the
            # sort is stable for putless direct-API entries)
            effs[uid] = effective_priority(
                m.priority if m else 0, m.t_arrival if m else now,
                now, ocfg.aging_ms) if m is not None else 0.0
            (decodes if len(t) == 1 and uid in self.state.seqs
             else prefills).append((uid, t))
        decodes.sort(key=lambda e: effs[e[0]])
        prefills.sort(key=lambda e: effs[e[0]])
        for uid, toks in decodes + prefills:
            if budget <= 0:
                break
            if self._pending.get(uid) is not toks:
                # a mid-round preemption rebound this uid's pending list
                # (the requeued chain replaced it): the stale entry here
                # holds mid-stream tokens that must NOT be admitted as a
                # fresh prompt at position 0 — the requeue waits its turn
                # next round
                continue
            verdict = admit(uid, toks)
            while verdict == "starved" and preempts_left > 0:
                # preemption compares RAW tiers (not aged): two equal
                # requests must never evict each other back and forth,
                # so at one shared tier preemption is provably inert
                m = self._meta.get(uid)
                victim = select_victim(
                    self._victim_candidates(sched_uids | {uid}),
                    better_than=m.priority if m else 0)
                if victim is None:
                    break
                self._preempt(victim)
                preempts_left -= 1
                verdict = admit(uid, toks)
        return sched

    def _victim_candidates(self, exclude: set) -> List[tuple]:
        """``(uid, raw_priority, n_blocks)`` for every live sequence
        preemption may legally evict: nothing scheduled this round or
        still in flight (its KV rows are being written), nothing whose
        KV contents the host cannot reconstruct (a broken chain, or a
        deferred on-device token), nothing already at the
        context limit (re-queueing it would re-prefill to exhaustion)."""
        out = []
        for uid, seq in self.state.seqs.items():
            if uid in exclude or uid in self._ctx_exhausted:
                continue
            if self._inflight_sched.get(uid, 0):
                continue
            if not seq.resumable:
                continue
            p = self._pending.get(uid)
            if p and p[0] == FEEDBACK_TOKEN:
                continue
            m = self._meta.get(uid)
            out.append((uid, float(m.priority if m else 0),
                        len(seq.blocks)))
        return out

    def _evict_to_queue(self, uid: int) -> None:
        """Release ``uid``'s KV back through the refcounted allocator
        (content-hashed full blocks retire to the cached-free LRU pool,
        so with the prefix cache on the re-prefill is one aliasing
        pass, not a recompute) and re-queue its full host-known token
        stream — KV chain + still-pending concrete tokens — as a
        prompt.  NOT terminal: the lifecycle record stays open across
        the eviction, and the (uid, position)-folded sampling keys make
        the resumed output token-identical to an undisturbed run.  The
        shared mechanics of preemption-by-eviction AND failure-recovery
        re-queueing; callers count the event on the lifecycle record
        themselves (``on_preempted`` vs ``on_retried``)."""
        seq = self.state.seqs[uid]
        requeue = [int(t) for t in seq.chain]
        tail = [int(t) for t in self._pending.get(uid, [])
                if t != FEEDBACK_TOKEN]
        if seq.tokens:
            # stash generated-so-far: they become prompt tokens on the
            # re-prefill, but query() keeps reporting the full output
            self._preempt_gen[uid] = (self._preempt_gen.get(uid, [])
                                      + [int(t) for t in seq.tokens])
        self._preempting.add(uid)
        try:
            self.state.release(uid)
        finally:
            self._preempting.discard(uid)
        self._fb_step.pop(uid, None)
        self._pending[uid] = requeue + tail

    def _preempt(self, uid: int) -> None:
        """Preemption-by-eviction (docs/SERVING.md "Surviving
        overload"): evict-and-requeue, counted on the record
        (tests/test_scheduler_fuzz.py parity test)."""
        self._evict_to_queue(uid)
        self.requests.on_preempted(uid)
        self._round_preemptions += 1

    def _reap_deadlines(self, now: float) -> None:
        """Terminally close every request whose ``deadline_ms`` elapsed
        — queued entries just drop; running sequences release their KV.
        A sequence with an uncollected in-flight step is deferred one
        round (its KV rows are still being written)."""
        if not self._deadline_uids:
            return
        for uid in list(self._deadline_uids):
            m = self._meta.get(uid)
            if m is None:
                self._deadline_uids.discard(uid)
                continue
            if not m.expired(now):
                continue
            if self._inflight_sched.get(uid, 0):
                # closed next round; nothing more is scheduled for it
                # meanwhile, or a stream that step() keeps one launch
                # ahead would have a row in flight at every pass
                self._pending[uid] = []
                continue
            self._finish(uid, "deadline_exceeded")
            self._reaped.add(uid)

    def _close_ctx_exhausted(self) -> None:
        """Terminally close context-exhausted sequences once nothing is
        in flight for them (status ``context_exhausted``) — without this
        the direct step() API leaks their open lifecycle records
        forever.  Closure reaps the uid (``_drain_reaped`` tells
        ``generate()``) and ``_forget`` drops it from
        ``_ctx_exhausted``, so the set never grows without bound under
        long direct-API traffic and a later reused uid is not
        permanently unschedulable."""
        for uid in list(self._ctx_exhausted):
            if uid not in self.state.seqs:
                # closed through another exit path (flush/cancel/...)
                # before this round got to it
                self._ctx_exhausted.discard(uid)
            elif not self._inflight_sched.get(uid, 0):
                self._finish(uid, "context_exhausted")
                self._reaped.add(uid)

    # ------------------------------------------------------------------
    # failure domains (inference/failures.py, docs/SERVING.md "Failure
    # domains & recovery")
    # ------------------------------------------------------------------
    def _ensure_alive(self) -> None:
        """Refuse device work on a dead engine — ``snapshot()`` still
        works; ``restore()`` the truth onto a fresh one."""
        if self._health == "dead":
            raise EngineDeadError(
                "serving engine is dead — snapshot() holds the host-side "
                "truth; InferenceEngine.restore() it onto a fresh engine")

    def _note_step_success(self, uids) -> None:
        """One completed device step: reset the failure-escalation
        counters, clear suspicion from every sequence it carried, and
        exonerate exactly the COVERED part of the head bisection probe
        group — a clean step carrying only half the group (budget /
        chunking split it) must not acquit the unprobed other half."""
        self._consec_failures = 0
        self._consec_timeouts = 0
        for uid in uids:
            self._strikes.pop(uid, None)
        if self._probe_groups:
            covered = set(self._probe_groups[0]) & set(uids)
            if covered:
                rest = [u for u in self._probe_groups[0]
                        if u not in covered]
                if rest:
                    self._probe_groups[0] = rest
                else:
                    self._probe_groups.pop(0)

    def _handle_step_failure(self, exc: BaseException, uids,
                             phase: str, registered=()) -> None:
        """Recover from one failed device dispatch/readback: classify
        the exception at the ONE seam (`classify_failure`) and act on
        the verdict so the failure degrades to request-level outcomes:

        * ``retry`` — transient: every affected sequence is released
          and re-queued (the chain re-prefills token-identically, an
          aliasing pass when the prefix cache holds its blocks) and the
          scheduler backs off a bounded, step-counted number of rounds.
        * ``poison`` — deterministic for this batch: same re-queue,
          plus the batch bisects into probe groups the scheduler runs
          in isolation; a singleton failing batch is proof and closes
          that request terminally with status ``failed``.
        * ``fatal`` — the backend is gone: the engine is marked dead
          and :class:`EngineDeadError` raised; ``snapshot()`` +
          ``restore()`` warm-restart the open work elsewhere.

        Exceptions the classifier does not recognize (host programming
        errors) re-raise untouched.  A sequence whose stream the host
        cannot replay (broken chain — device-side tokens lost with the
        failed step) closes as ``failed`` regardless of verdict."""
        if isinstance(exc, DispatchTimeoutError):
            self._consec_timeouts += 1
            if self.failures.watchdog.abandoned \
                    >= self.fcfg.max_abandoned_workers:
                # consecutive-expiry escalation resets on every clean
                # step, so an INTERMITTENTLY hanging device could
                # strand workers forever — the lifetime cap declares
                # it dead first
                self._consec_timeouts = max(self._consec_timeouts,
                                            self.fcfg.fatal_timeouts)
        verdict = classify_failure(
            exc, attempt=self._consec_failures,
            consecutive_timeouts=self._consec_timeouts, cfg=self.fcfg)
        if verdict is None:
            raise exc
        # the FIRST failure of a window flips health() to degraded —
        # the transition (not every failure) is a flight-dump trigger
        fresh_degrade = self._steps_done - self._last_failure_step \
            > self.fcfg.health_window_steps
        self._consec_failures += 1
        self._last_failure_step = self._steps_done
        logger.warning(
            f"serving step failure at {phase} "
            f"({type(exc).__name__}: "
            f"{(str(exc).splitlines() or [''])[0][:120]}) -> {verdict}")
        # black-box breadcrumb (telemetry/flight.py): verdicts survive
        # in the ring even when no dump is configured, so a later
        # debug_dump() still carries the failure history
        self.flight.note(
            "step_failure", verdict=verdict, phase=phase,
            exc=type(exc).__name__, step=self._steps_done,
            uids=[int(u) for u in uids])
        if self._cap is not None and self._cap.active:
            # a capture that witnessed the failure is worth more
            # finished than abandoned — close it with what it has
            fin = self._cap.finish_now()
            if fin is not None:
                self._finish_capture(fin)
        if verdict == FATAL_ENGINE:
            self._health = "dead"
            self._health_gauge.set(3)
            self.flight.note("engine_dead", phase=phase,
                             exc=type(exc).__name__,
                             step=self._steps_done)
            self._flight_autodump("engine_dead")
            raise EngineDeadError(
                f"serving backend dead after {type(exc).__name__} at "
                f"{phase}; snapshot() holds the host-side truth — "
                "restore onto a fresh engine") from exc
        tm = self.timings
        tm["step_retries"] += 1
        affected = [int(u) for u in uids]
        # an INJECTED fault (crash or synthetic timeout) raises before
        # the guarded call runs, so the cache buffer is untouched.  A
        # real device error — and a REAL watchdog expiry, whose
        # abandoned call already consumed the donated cache operand —
        # may have invalidated it: conservatively re-queue EVERY live
        # sequence and rebuild a zero pool (chains re-prefill the
        # truth; the prefix index must drop with the content it hashed)
        kv_lost = not isinstance(exc, (InjectedFault,
                                       InjectedTimeout)) \
            and self._donate_kv()
        if kv_lost:
            affected = list(dict.fromkeys(list(self.state.seqs)
                                          + affected))
        singleton = verdict == POISON_STEP and len(affected) == 1
        requeued: List[int] = []
        # recovery below may register post-rollback blocks into the
        # LIVE ledger (resolve_draft); those writes rode the failed
        # step, so they are withdrawn alongside ``registered`` — but a
        # NEWER in-flight step's ledger entries (depth-2 collect
        # failure) are its own and must survive
        pre_recovery = len(self.state.round_registered)
        for uid in affected:
            self._strikes[uid] = self._strikes.get(uid, 0) + 1
            seq = self.state.seqs.get(uid)
            if seq is not None and seq.draft_len:
                # drafts in the failed window were never verified:
                # reject them all before judging the chain
                self.state.resolve_draft(uid, 0)
                seq = self.state.seqs.get(uid)
            poison = singleton \
                or self._strikes[uid] >= self.fcfg.poison_strikes
            # a sequence with ANOTHER dispatched-but-uncollected step
            # (depth>=2 chunked prefill spanning two in-flight steps)
            # cannot be re-queued: the surviving step would emit from a
            # context the re-queue is about to regenerate (duplicate /
            # garbage tokens).  Terminal is the one honest outcome —
            # same conservatism as a broken chain.  (The failed step
            # itself is not counted: dispatch failures never
            # incremented it, collect failures already decremented.)
            inflight_elsewhere = self._inflight_sched.get(uid, 0) > 0
            if poison or inflight_elsewhere \
                    or (seq is not None and not seq.resumable):
                tm["requests_failed"] += 1
                self._finish(uid, "failed")
                self._reaped.add(uid)
            else:
                if seq is not None:
                    self._evict_to_queue(uid)
                self.requests.on_retried(uid)
                requeued.append(uid)
        # the failed step's KV writes never (reliably) happened: every
        # prefix-index registration that step made promises content the
        # pool does not hold — withdraw exactly those entries (plus any
        # the recovery itself just appended), or a later match would
        # alias never-written blocks
        self.state.unregister_blocks(
            list(registered)
            + list(self.state.round_registered[pre_recovery:]))
        if kv_lost:
            self.state.kv = self._kv_zeros()
            self.state.reset_prefix_cache()
            self._last_toks = None
        # a failed probe step retires its group — but NEVER loses it:
        # its bisected split (poison) or the group itself (transient
        # failure mid-quarantine) takes its place, so isolation always
        # completes and the poison cannot slip back into the pool
        hit_probe = bool(self._probe_groups) \
            and bool(set(self._probe_groups[0]) & set(affected))
        if hit_probe:
            self._probe_groups.pop(0)
        if verdict == POISON_STEP and len(requeued) > 1:
            self._probe_groups = bisect_groups(requeued) \
                + self._probe_groups
        else:
            if requeued and (hit_probe or verdict == POISON_STEP):
                # a transient keeps the same probe group for retry; a
                # poison remnant (siblings already failed) probes alone
                # so its next failure is singleton proof
                self._probe_groups = [list(requeued)] \
                    + self._probe_groups
            # transient: step-counted exponential backoff (determinis-
            # tic — the chaos replay's op sequence stays machine-
            # independent), bounded so the loop always makes progress
            self._backoff_rounds = min(
                self.fcfg.max_backoff_rounds,
                1 << min(self._consec_failures - 1, 6))
        # non-fatal auto-dump triggers (docs/OBSERVABILITY.md): a
        # watchdog expiry (the call was abandoned — the artifact is how
        # anyone learns what it carried) and the healthy->degraded
        # transition of a fresh failure window
        if isinstance(exc, DispatchTimeoutError):
            self._flight_autodump("watchdog_expiry")
        elif fresh_degrade:
            self._flight_autodump("health_degraded")

    def _finish_capture(self, cdir: str) -> None:
        """A capture window just completed: drop the flight dump next
        to its traces (the post-mortem half of the artifact) and leave
        a breadcrumb.  ``tools/tracemerge.py`` merges the dir into one
        Perfetto timeline."""
        import os
        self.flight.note("capture_complete", path=cdir)
        self.debug_dump(os.path.join(cdir, "flight.json"),
                        reason="capture")

    def finish_capture(self) -> Optional[str]:
        """Close any ACTIVE capture window immediately with the steps
        it has (the artifact is written; the jax profiler session and
        the force-enabled tracer are released).  ``generate()`` and
        ``drain()`` call this when their work runs out —
        a window armed for more steps than the workload will run must
        not strand the process-wide profiler session — and direct
        step()-API callers can call it themselves.  Returns the
        capture dir, or None when no window was active."""
        if self._cap is None or not self._cap.active:
            return None
        fin = self._cap.finish_now()
        if fin is not None:
            self._finish_capture(fin)
        return fin

    def _flight_autodump(self, reason: str) -> Optional[str]:
        """Write one black-box artifact into ``FailureConfig.
        flight_dir`` (no-op when unset).  Best-effort: the recorder
        itself swallows I/O failures — a post-mortem writer must never
        make a failing engine fail harder."""
        d = self.fcfg.flight_dir
        if not d:
            return None
        import os
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            logger.warning("flight_dir %r unusable (%s)", d, e)
            return None
        # collision-avoid across engine GENERATIONS sharing one dir: a
        # warm-restarted engine replaying the same workload dies at the
        # same step with the same counters, and overwriting the prior
        # engine's black box would destroy the one artifact the
        # recorder exists to preserve
        n = self.flight.dumps
        while True:
            path = os.path.join(
                d, f"flight_{reason}_s{self._steps_done}_{n}.json")
            if not os.path.exists(path):
                break
            n += 1
        self.flight.note("dump", reason=reason, path=path)
        return self.flight.dump(
            path, reason, metrics=self.metrics, tracer=self.tracer,
            requests=self.requests, health=self.health(),
            steps=self._steps_done,
            extra={"device": None if self.devtel is None
                   else self.devtel.snapshot(),
                   "anomalies": self.anomaly_summary()})

    def ops_dump(self) -> Optional[str]:
        """The gateway ``POST /debug/dump`` seam: one flight-recorder
        artifact into ``FailureConfig.flight_dir`` through the same
        collision-safe writer the failure path uses.  Returns the
        written path, or None when no flight_dir is configured — a
        wire client can name neither the path nor the budget."""
        return self._flight_autodump("ops")

    def debug_dump(self, path: Optional[str] = None,
                   reason: str = "debug") -> Dict:
        """On-demand flight-recorder snapshot (docs/OBSERVABILITY.md
        "Device & compiler telemetry"): the same black-box artifact the
        failure path auto-dumps — last-N spans, full metrics snapshot,
        recent request statuses, config fingerprint, health, failure
        breadcrumbs, and the device-telemetry summary when enabled.
        Returns the dict; with ``path`` also writes it as JSON (through
        the recorder's best-effort writer — a post-mortem must never
        make a failing engine fail harder).  Valid on a DEAD engine
        (everything it reads is host truth)."""
        snap = self.flight.snapshot(
            reason, metrics=self.metrics, tracer=self.tracer,
            requests=self.requests, health=self.health(),
            steps=self._steps_done,
            extra={"device": None if self.devtel is None
                   else self.devtel.snapshot(),
                   "anomalies": self.anomaly_summary()})
        if path is not None:
            self.flight.dump(path, reason, snap=snap)
        return snap

    def health_state(self) -> str:
        """The health-ladder state ALONE — no gauge write, no memory
        poll: the cheap form a fleet router may read every step for
        its per-replica gauges.  :meth:`health` is the phase-boundary
        probe that additionally refreshes gauges and polls device
        memory."""
        state = self._health
        if state == "healthy" and self._steps_done \
                - self._last_failure_step <= self.fcfg.health_window_steps:
            state = "degraded"
        if state == "healthy" and self._anom is not None \
                and self._anom.sustained(self._steps_done):
            # sustained anomaly fires inside the window: the engine is
            # not failing, but it is not behaving either — the router
            # should prefer another replica while this one is probed
            state = "degraded"
        return state

    def health(self) -> Dict:
        """Engine health for the router's liveness probe
        (docs/OBSERVABILITY.md): ``state`` walks
        ``healthy -> degraded -> (draining | dead)`` — ``degraded``
        while the most recent step failure is within
        ``FailureConfig.health_window_steps`` dispatched steps
        (failure *rates* from the metrics registry drive it, not a
        latched flag), ``draining``/``dead`` sticky.  Also exported as
        the ``serving_health_state`` gauge (0/1/2/3) through the
        Prometheus exposition."""
        state = self.health_state()
        self._health_gauge.set(
            {"healthy": 0, "degraded": 1, "draining": 2,
             "dead": 3}[state])
        if self.devtel is not None:
            # a health check is a phase boundary: refresh the memory
            # gauges here (one host call per device, never per step)
            self.devtel.poll_memory()
        tm = self.timings
        return {
            "state": state,
            "steps": int(tm["steps"]),
            "step_retries": int(tm["step_retries"]),
            "requests_failed": int(tm["requests_failed"]),
            "consecutive_failures": self._consec_failures,
            "consecutive_timeouts": self._consec_timeouts,
            "dispatch_deadline_ms": self.failures.deadline_ms(),
            "probing": bool(self._probe_groups),
            "backoff_rounds": self._backoff_rounds,
            "live": len(self.state.seqs),
            "queued": sum(1 for t in self._pending.values() if t),
            # streaming-detector view (0 / [] while anomaly is off)
            "anomalies": 0 if self._anom is None else self._anom.total(),
            "captures": len(self.capture_dirs),
        }

    # every snapshot this engine emits or restores carries this schema
    # version.  v2 (PR 13): per-request extraction (`snapshot_requests`)
    # and merge-restore (`load_snapshot(..., merge=True)`) — the
    # record shape is unchanged, but v1 consumers assumed a snapshot
    # was always the WHOLE engine restored onto a FRESH one, so
    # partial/merging payloads must be rejected by v1 engines (and
    # vice versa) rather than silently half-applied
    SNAPSHOT_VERSION = 2

    def _open_uids(self) -> List[int]:
        """Every uid with open work on this engine (admitted metadata,
        queued tokens, or a live sequence), in stable admission-ish
        order — the domain of :meth:`snapshot` / :meth:`snapshot_requests`."""
        return list(dict.fromkeys(list(self._meta) + list(self._pending)
                                  + list(self.state.seqs)))

    def _request_record(self, uid: int, now: float) -> Dict:
        """One open request's restore()-compatible record: the
        replayable token stream (KV chain + still-pending tokens),
        generated output so far, and admission metadata — the unit of
        currency snapshots, drains, and fleet migrations all move.  A
        stream the host cannot replay (a broken chain, an in-flight
        feedback marker) is recorded ``exact: False``."""
        seq = self.state.seqs.get(uid)
        pend = [int(t) for t in self._pending.get(uid, [])]
        gen = list(self._preempt_gen.get(uid, []))
        exact = FEEDBACK_TOKEN not in pend
        stream = pend
        if seq is not None:
            exact = exact and seq.resumable
            stream = [int(t) for t in seq.chain] \
                + [t for t in pend if t != FEEDBACK_TOKEN]
            gen += [int(t) for t in seq.tokens]
        m = self._meta.get(uid)
        remaining = None
        if m is not None and m.deadline_ms is not None:
            remaining = max(
                0.0, m.deadline_ms - (now - m.t_arrival) * 1e3)
        rec = self.requests.open.get(uid)
        return {
            "uid": int(uid),
            "tokens": stream if exact else None,
            "generated": gen,
            "priority": int(m.priority) if m else 0,
            "deadline_ms": remaining,
            "preemptions": rec.preemptions if rec else 0,
            "retries": rec.retries if rec else 0,
            "slo": rec.slo_class if rec else None,
            "exact": exact,
        }

    def snapshot(self) -> Dict:
        """Serialize the engine's host-side truth — every open
        request's replayable token stream (KV chain + still-pending
        tokens), its generated output so far, and its admission
        metadata — plus the counters and the prefix-cache index keys
        (the content hashes: a router's cache-affinity signal, NOT
        revivable KV).  Device state is deliberately absent: KV blocks
        re-prefill from the chains on :meth:`restore` (an aliasing pass
        for streams whose prefixes re-register in the new engine's
        cache, plain prefill otherwise), and the (uid, position)-folded
        sampling keys make the resumed outputs token-identical to an
        uninterrupted run — greedy and seeded (reuse the same explicit
        base key), prefix cache on or off.

        Valid on a DEAD engine (host truth survives the backend) —
        that is the warm-restart story: catch
        :class:`EngineDeadError`, ``snapshot()``, ``restore()``.  Take
        it at a step boundary (no dispatched-but-uncollected step); a
        sequence whose stream the host cannot replay (a broken chain,
        an in-flight feedback marker) is recorded ``exact: False`` and
        closed ``failed`` at restore."""
        from .. import __version__
        self._settle()
        now = time.perf_counter()
        reqs = [self._request_record(uid, now)
                for uid in self._open_uids()]
        return {
            "version": self.SNAPSHOT_VERSION,
            "engine_version": __version__,
            "health": self.health()["state"],
            "counters": {k: self.timings[k]
                         for k in ("steps", "prompt_tokens",
                                   "cached_tokens", "generated_tokens",
                                   "step_retries", "requests_failed")},
            "requests": reqs,
            # content digests of the resident prefix-cache index: the
            # cache-affinity routing key (ROADMAP item 5), not KV
            "prefix_index": sorted(self.state.prefix_digests()),
        }

    def snapshot_requests(self, uids: Sequence[int]) -> Dict:
        """Extract restore()-compatible records for a SUBSET of this
        engine's open requests — the fleet router's migration payload
        (move some open work to another replica without touching the
        rest).  Same schema/version as :meth:`snapshot`, marked
        ``"partial": True``; uids with no open work here are skipped
        (the caller may be racing a terminal close).  Extraction does
        NOT close the requests — :meth:`migrate_out` is the
        extract-and-close composition."""
        from .. import __version__
        self._settle()
        now = time.perf_counter()
        known = set(self._open_uids())
        wanted = dict.fromkeys(int(u) for u in uids)   # dedup, ordered
        return {
            "version": self.SNAPSHOT_VERSION,
            "engine_version": __version__,
            "partial": True,
            "requests": [self._request_record(u, now)
                         for u in wanted if u in known],
        }

    def migrate_out(self, uids: Sequence[int]) -> Dict:
        """Live-migration extraction (docs/SERVING.md "Fleet: routing,
        failover, migration"): :meth:`snapshot_requests` the given open
        requests, then terminally close them on THIS engine with
        status ``migrated`` (their KV releases back through the
        refcounted allocator; the lifecycle record closes — the
        request lives on wherever the returned records are
        ``load_snapshot(..., merge=True)``-ed).  Requests a move would
        DESTROY are skipped, not extracted: a dispatched-but-
        uncollected step (its KV rows are still being written) and a
        non-replayable stream (broken chain — the destination could
        only close it ``failed``, killing a healthy request); both
        stay in place, retry at a later step boundary."""
        self._settle()
        eligible = [int(u) for u in uids
                    if not self._inflight_sched.get(int(u), 0)]
        part = self.snapshot_requests(eligible)
        part["requests"] = [rec for rec in part["requests"]
                            if rec["exact"] and rec["tokens"]]
        for rec in part["requests"]:
            self._finish(rec["uid"], "migrated")
            self._reaped.add(rec["uid"])
        return part

    def handoff_out(self, uids: Sequence[int]) -> Dict:
        """Prefill→decode handoff extraction (docs/SERVING.md
        "Disaggregated pools & elasticity"): the same extract-and-close
        composition as :meth:`migrate_out`, with two differences.  The
        close status is ``handed_off`` — terminal here, a routing hop
        at the fleet level — and BEFORE closing, each request's
        still-indexed chain blocks are staged into the KV tier
        (``stage_chain_demotes`` + an immediate demote drain, reading
        the device while the blocks are guaranteed unrewritten), so the
        router's :meth:`export_tier_chain` fetch on the decode side
        ships the prefilled KV instead of re-prefilling it.  The same
        destroy-avoidance rules apply: dispatched-but-uncollected and
        non-replayable requests stay in place for a later boundary."""
        self._settle()
        eligible = [int(u) for u in uids
                    if not self._inflight_sched.get(int(u), 0)]
        part = self.snapshot_requests(eligible)
        part["requests"] = [rec for rec in part["requests"]
                            if rec["exact"] and rec["tokens"]]
        staged = 0
        for rec in part["requests"]:
            staged += self.state.stage_chain_demotes(rec["uid"])
            self._finish(rec["uid"], "handed_off")
            self._reaped.add(rec["uid"])
        if staged:
            self._drain_tier_demote()
        return part

    def export_tier_chain(self, digests: Sequence[bytes]) -> Optional[Dict]:
        """Extract the leading contiguous run of ``digests`` this
        engine's KV tier can serve, as a snapshot-v2-shaped partial
        payload (``tier_blocks`` records ride the same fabric migration
        records do — ``load_snapshot(merge=True)`` on the destination).
        Non-destructive: this replica keeps its tier entries.  Returns
        None when the tier is off or the first digest misses; every
        record was checksum-verified on the way out, so a corrupted
        spill file truncates the run instead of exporting bad bytes."""
        tier = self.state.tier
        if tier is None:
            return None
        blocks = []
        for h in digests:
            rec = tier.export(bytes(h))
            if rec is None:
                break          # only a leading run is restageable
            blocks.append(rec)
        if not blocks:
            return None
        return {"version": self.SNAPSHOT_VERSION, "partial": True,
                "requests": [], "tier_blocks": blocks}

    def load_snapshot(self, snap: Dict, merge: bool = False) -> None:
        """Re-open a snapshot's requests on THIS engine (the restore
        half of the warm restart — :meth:`restore` wraps construction +
        this).  Admission bounds are bypassed: restored work was
        already admitted once; shedding it again would double-charge
        the client.  Streams re-enter as prompts (the scheduler
        re-prefills them, through the prefix cache when their blocks
        re-register), prior generated tokens keep ``query()`` output
        complete, and inexact records (device-side tokens lost with
        the old engine) close terminally as ``failed``.

        By default the engine must be FRESH (no open work) — the warm-
        restart contract.  ``merge=True`` is the fleet-migration mode:
        records join a replica that is already serving (a uid already
        open here raises — one request must never run twice)."""
        if snap.get("version") != self.SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {snap.get('version')!r}: this engine "
                f"restores version {self.SNAPSHOT_VERSION}")
        self._settle()
        open_now = set(self._open_uids()) | set(self.requests.open)
        if not merge and open_now:
            raise ValueError(
                f"load_snapshot onto an engine with {len(open_now)} open "
                "request(s): restore assumes a fresh engine — pass "
                "merge=True to migrate records into live traffic")
        # validate the WHOLE payload before applying any record: a
        # rejection must leave the engine untouched, or the caller's
        # retry-on-another-replica re-places the half-applied records
        # and double-runs them — the exact hazard this guard exists for
        incoming = [int(rec["uid"]) for rec in snap["requests"]]
        seen: set = set()
        dupes: set = set()
        for u in incoming:
            (dupes if u in seen else seen).add(u)
        if dupes:
            raise ValueError(
                f"snapshot payload repeats uid(s) {sorted(dupes)}: a "
                "request must never be applied twice")
        if merge:
            clash = open_now & set(incoming)
            if clash:
                raise ValueError(
                    f"load_snapshot(merge=True): uid(s) {sorted(clash)} "
                    "already open on this engine — a request must never "
                    "run on two replicas at once")
        # fetched KV tier blocks (docs/KV_TIERING.md): part of the same
        # whole-payload-first validation — every record must recompute
        # its chain digest from (parent, tokens) AND match its payload
        # checksum before anything is applied.  A forged or corrupted
        # block rejects the payload; it can never reach the device cache
        tier_blocks = snap.get("tier_blocks") or []
        if tier_blocks:
            from .ragged.tier import KVBlockTier
            bad = [i for i, rec in enumerate(tier_blocks)
                   if not KVBlockTier.verify_record(rec)]
            if bad:
                raise ValueError(
                    f"snapshot tier_blocks {bad} failed digest/checksum "
                    "verification: refusing the whole payload")
        now = time.perf_counter()
        tm = self.timings
        for rec in snap["requests"]:
            uid = int(rec["uid"])
            # the class tag travels with the record, so a migrated /
            # handed-off / restored request is still charged to its SLO
            # budget on the replica that finishes it
            self.requests.on_arrival(uid, now,
                                     slo_class=rec.get("slo"))
            if not rec.get("exact", True) or not rec.get("tokens"):
                # device-side tokens died with the old engine: the one
                # honest outcome is terminal (and reaped, so drivers
                # drop the uid instead of waiting on it forever)
                tm["requests_failed"] += 1
                self.requests.on_finish(uid, status="failed")
                self._reaped.add(uid)
                continue
            self._meta[uid] = RequestMeta(
                priority=int(rec.get("priority", 0)),
                deadline_ms=rec.get("deadline_ms"),
                t_arrival=now)
            if rec.get("deadline_ms") is not None:
                self._deadline_uids.add(uid)
            toks = [int(t) for t in rec["tokens"]]
            self._pending[uid] = toks
            if rec.get("generated"):
                self._preempt_gen[uid] = [int(t)
                                          for t in rec["generated"]]
            open_rec = self.requests.open.get(uid)
            if open_rec is not None:
                open_rec.preemptions = int(rec.get("preemptions", 0))
                open_rec.retries = int(rec.get("retries", 0))
            if self._spec is not None:
                self._spec.observe(uid, toks)
        if tier_blocks:
            tier = self.state.tier
            if tier is None:
                logger.warning(
                    "load_snapshot: %d tier_blocks arrived but kv_tier "
                    "is off on this engine — dropping them (the request "
                    "records were applied normally)", len(tier_blocks))
            else:
                for rec in tier_blocks:
                    ev = tier.insert_record(rec)
                    tm["kv_tier_remote_blocks"] += ev["stored"]
                    tm["kv_tier_spills"] += ev["spilled"]
                    tm["kv_tier_spilled_bytes"] += ev["spilled_bytes"]
                    tm["kv_tier_drops"] += ev["dropped"]

    @classmethod
    def restore(cls, model: Model, snap: Dict,
                config: InferenceConfig = None,
                topology: Optional[MeshTopology] = None,
                quant_tree=None) -> "InferenceEngine":
        """Warm restart: build a fresh engine from weights + a
        :meth:`snapshot` and re-open every captured request on it.
        The chaos harness's elastic-restart loop
        (tools/loadgen.py) is the canonical caller::

            try:
                out = eng.step(...)
            except EngineDeadError:
                eng = InferenceEngine.restore(model, eng.snapshot(),
                                              eng.icfg)
        """
        eng = cls(model, config, topology, quant_tree)
        eng.load_snapshot(snap)
        return eng

    def drain(self, deadline_ms: Optional[float] = None,
              sampling: SamplingParams = SamplingParams(),
              rng: Optional[jax.Array] = None) -> Dict:
        """Graceful drain — the router's replica-restart contract
        (ROADMAP item 5): stop admitting NEW requests (``put`` sheds
        them; continuations still land), run the backlog down until no
        pending work remains or ``deadline_ms`` elapses (always
        step-bounded: a wedged pool cannot hang the drain), then emit
        the final :meth:`snapshot` and terminally close everything
        still open as ``shed`` — exactly-one-terminal-status holds
        through a drain like every other exit path.  The snapshot is
        the hand-off: restore it onto the replacement replica and the
        undone work resumes token-identically.

        The returned snapshot additionally reports the drain's outcome
        split: ``shed_uids`` — requests closed ``shed`` by the drain
        (their records are in ``requests``; the router re-places
        exactly these on surviving replicas) — and ``completed_uids``
        — requests that reached some OTHER terminal status during the
        drain (deadline expiry, context exhaustion, a failure close):
        already settled, so re-placing them would double-run them."""
        self._draining = True
        # a draining engine continues nothing by itself: what is in
        # flight is read back, and every request goes on as caller-fed
        # (one more step for a token already queued, like any other)
        self._settle()
        self._cont.clear()
        open_at_start = set(self._open_uids()) | set(self.requests.open)
        if self._health != "dead":
            self._health = "draining"
            self._health_gauge.set(2)
        t0 = time.perf_counter()
        pending_tokens = sum(len(t) for t in self._pending.values())
        # generous progress bound: every pending token plus headroom
        # for chunking/backoff rounds — the drain NEVER spins forever
        step_budget = 4 * (pending_tokens // max(self.icfg.token_budget,
                                                 1) + len(self._pending)) \
            + 4 * self.fcfg.max_backoff_rounds + 16
        empty_rounds = 0
        while any(self._pending.values()) and step_budget > 0:
            if deadline_ms is not None \
                    and (time.perf_counter() - t0) * 1e3 >= deadline_ms:
                break
            step_budget -= 1
            try:
                out = self.step(rng=rng, sampling=sampling)
            except EngineDeadError:
                break
            # backoff rounds return {} with work still pending; more
            # than the backoff cap of consecutive empties means the
            # remaining work is unschedulable — shed it via the close
            empty_rounds = 0 if out else empty_rounds + 1
            if empty_rounds > self.fcfg.max_backoff_rounds + 2:
                break
        # a drain ends this engine's serving life: an active capture
        # window closes with what it has (never strands the session)
        self.finish_capture()
        snap = self.snapshot()
        shed: List[int] = []
        for uid in self._open_uids():
            self._finish(uid, "shed")
            self._reaped.add(uid)
            shed.append(int(uid))
        shed_set = set(shed)
        snap["shed_uids"] = sorted(shed_set)
        snap["completed_uids"] = sorted(
            int(u) for u in open_at_start if u not in shed_set)
        return snap

    def step(self, rng: Optional[jax.Array] = None,
             sampling: SamplingParams = SamplingParams()
             ) -> Dict[int, int]:  # tpulint: serving-loop
        """Run one engine step; returns {uid: next_token} for sequences
        whose last pending token was consumed (i.e. ready to sample).

        A step whose rows all belong to requests the engine continues
        itself (``put(max_new_tokens=...)``) runs ONE STEP AHEAD: the
        call schedules, stages and launches step N+1 — continuing
        decodes take their token from step N's sample array on the
        device — and only then reads step N back and returns ITS
        tokens, so the host's work of a step hides under the device's.
        With nothing in flight such a step is launched and the call
        returns ``{}`` at once: a launch left in flight is the only way
        the loop can start, and a driver that comes straight back
        (``in_flight``) makes the first token wait one call, not one
        step.  With nothing schedulable the call reads N back.

        A step that holds a row of a request its CALLER feeds (``put``
        of concrete tokens between calls) is strict, as ever: launched
        with nothing in flight and read back in the same call; so are a
        speculative engine's steps (a draft window's continuation is
        decided on the host at collect) and bisection probes.  The
        choice is read from the batch, nothing configures it.

        With ``spec_decode`` on, a step may emit SEVERAL tokens for a
        sequence (an accepted verify window); the returned token is the
        LAST one — exactly the right continuation to feed back via
        ``put`` — and the full stream accumulates on the sequence
        (``query()["generated"]``); :meth:`generate` reads the whole
        lists (:meth:`_step`)."""
        return self._last(self._step(rng, sampling))

    def _step(self, rng: Optional[jax.Array], sampling: SamplingParams
              ) -> Dict[int, List[int]]:  # tpulint: serving-loop
        """:meth:`step` with every token the call emitted, a LIST per
        uid (several for a resolved verify window).  One call is one
        ROUND of the loop: its return closes the round and judges it
        (``failures.RoundWatch``: a round that ran long leaves one
        ``slow_round`` record saying where it went)."""
        try:
            out = self._advance(rng, sampling)
        except BaseException:
            self._round.void = True     # a failed round is not judged
            raise
        self._round.end(self._ahead is not None)
        return out

    def _advance(self, rng: Optional[jax.Array], sampling: SamplingParams
                 ) -> Dict[int, List[int]]:  # tpulint: serving-loop
        if self._held:
            # a launch read back outside step() (snapshot, a failed
            # launch behind it): its tokens are handed over first
            out, self._held = self._held, {}
            return out
        prev = self._ahead
        if prev is not None and any(
                t and u not in self._cont
                for u, t in self._pending.items()):
            # a caller-fed request waits: read the launch in flight
            # back now, its step is launched strict by the next call
            self._ahead = None
            return self._collect(prev)
        st = self._dispatch(sampling, rng)
        if prev is not None and self._ahead is None:
            # the launch failed, and its failure path read ``prev`` back
            out, self._held = self._held, {}
            return out
        if st is None:
            self._ahead = None
            return self._collect(prev) if prev is not None else {}
        if prev is None:
            why = "spec_decode" if self._spec is not None \
                else "probe" if self._probe_groups \
                else "caller_fed" if any(u not in self._cont
                                         for u in st.uids) else None
            if why is not None:
                self._c_strict.inc(reason=why)
                out = self._collect(st)
                for uid, toks in out.items():
                    # an engine-continued row of a strict step goes on
                    # from its concrete token
                    if self._cont.get(uid, 0) > 0 \
                            and uid in self.state.seqs \
                            and not self._pending.get(uid):
                        self._pending[uid] = [toks[-1]]
                return out
            self._c_strict.inc(reason="idle")
        else:
            self._c_ahead.inc()
        # continuing decodes of the next launch read this one's samples
        # on the device; a request's last token is not speculated past
        unread = {u for u, _ in prev.emit} if prev is not None else ()
        for uid, _slot in st.emit:
            if self._cont.get(uid, 0) - 1 - (uid in unread) > 0:
                self._mark_feedback(uid, st)
        self._ahead = st
        if prev is None:
            return {}
        return self._collect(prev, nxt=st)

    @staticmethod
    def _last(out: Dict[int, List[int]]) -> Dict[int, int]:
        return {u: ts[-1] for u, ts in out.items()}

    @property
    def in_flight(self) -> bool:
        """:meth:`step` left a launch unread: the next call has tokens
        to return even if nothing new is schedulable, so a driver must
        not take an empty return for an idle round."""
        return self._ahead is not None or bool(self._held)

    def _settle(self) -> None:
        """Read the launch :meth:`step` left in flight back now, at a
        boundary that needs every request's stream on the host
        (snapshot, migration, hand-off, a weight refresh, a capture, the
        end of a drain or of :meth:`generate`).  Its tokens are emitted
        as ever and handed to the caller by the next :meth:`step`; a
        dead engine's launch is dropped unread."""
        st, self._ahead = self._ahead, None
        if st is None:
            return
        self._round.void = True         # read back outside a round
        if self._health == "dead":
            self._uncount_inflight(st.uids)
            return
        try:
            self._held.update(self._collect(st))
        except EngineDeadError:
            pass        # the host's truth stands; the caller reads it

    def _dispatch(self, sampling: SamplingParams,
                  rng: Optional[jax.Array] = None
                  ) -> Optional[_InFlight]:  # tpulint: serving-loop
        """Schedule, stage, and launch one serving step WITHOUT reading
        the sampled tokens back; returns the in-flight record (tokens
        still on device) or None when nothing is schedulable.  ``rng``:
        an explicit PRNG key, used verbatim — per-token randomness is
        the (uid, position) fold inside the jitted step
        (``sampler.row_keys``), which makes seeded outputs
        schedule-invariant: prompt chunking, the step that runs ahead
        and prefix-cache hits all change the step stream, but never a
        token's folded key — or None (engine-internal key stream when
        the sampler needs one)."""
        self._ensure_alive()
        # the step's phases are live spans (telemetry/tracer.py): each
        # cut below ends one phase, begins the next, and returns the one
        # clock reading that engine.timings takes there anyway
        tr = self.tracer
        sid = self._dispatch_seq + 1
        t0 = tr.phase("ds.serve.schedule", track="schedule", sid=sid)
        sched = self._schedule()
        self._close_ctx_exhausted()
        if not sched:
            # an idle round still moves tier work: evictions queued by
            # the schedule pass demote, and in-flight restages resolve
            # (a deferred request is waiting on exactly this)
            self._drain_tier_demote()
            self._drain_tier_restage(dispatching=False)
            tr.phase_end(n_seqs=0)
            return None
        cap = self._cap
        if cap is not None and cap.armed:
            # the armed deep-capture window opens only once a step is
            # KNOWN to launch (an idle/backoff round must not start a
            # session nothing will count down), before staging — the
            # one profiler seam (tpulint: profiler-capture)
            cap.begin(sid=sid, step=self._steps_done)
        # context bucket: the compiled block bound covers every scheduled
        # sequence's post-step context, rounded to a power of two so a
        # growing context mints O(log) programs, not one per block.  The
        # XLA formulations do work proportional to that bound; the
        # Pallas kernels' grids follow the batch (their tiles, and the
        # blocks of the deepest one: the paged kernel's and the latent
        # layers', ``ops/mla.py`` ``latent_attend_tiles``), so there
        # one program, bounded by the engine's longest context, serves
        # every step; so does the latent layers' XLA formulation, whose
        # loop over cached blocks stops behind the last block a query
        # reads (``latent_attend``), and a delta-rule layer reads no
        # block
        pallas = self.attn_impl == "pallas"
        mbs = self.max_blocks_per_seq
        if not pallas and (not self.cfg.mixer_stacks
                           or "full" in self.cfg.mixer_stacks):
            bs_blk = self.icfg.kv_block_size
            need = 1
            for uid, toks in sched:
                seq = self.state.seqs.get(uid)
                seen = seq.seen_tokens if seq else 0
                need = max(need, -(-(seen + len(toks)) // bs_blk))
            mbs = 1
            while mbs < need:
                mbs *= 2
            mbs = min(mbs, self.max_blocks_per_seq)
        key = (mbs, sampling.sampler_key)
        step_fn = self._pstep_fns.pop(key, None)
        fresh = step_fn is None
        if fresh:
            # kept once its first launch has compiled its rungs
            step_fn = self._build_pstep(mbs, sampling)
        else:
            self._pstep_fns[key] = step_fn    # reinsert: LRU, not FIFO
        # the step runs at the smallest compiled row count that holds
        # what was scheduled: rows that hold no token cost every matrix
        # product as much as rows that do
        n_tokens = sum(len(t) for _, t in sched)
        n_rows = next(r for r in self._step_rows if r >= n_tokens)
        key = (n_rows,) + key
        cold = ("p", key) not in self._warm_keys
        tiles = {}
        if pallas:
            short, long, *wide = self._tile_labels
            n_short, n_long, rows, *n_wide = tile_counts(
                [len(t) for _, t in sched], *self._tile_heights,
                wide=self._tile_wide)
            self._c_attn_tiles.inc(n_short, height=short)
            if n_long:
                self._c_attn_tiles.inc(n_long, height=long)
                self._c_attn_long_rows.inc(rows)
            tiles = {f"n_tiles_{short}": n_short, f"n_tiles_{long}": n_long,
                     "tile_fill": rows / (n_long * self._tile_heights[1])
                     if n_long else 0.0}
            if wide:
                self._c_attn_tiles.inc(n_wide[0], height=wide[0])
                tiles[f"n_tiles_{wide[0]}"] = n_wide[0]
        if self._recurrent is not None:
            tiles.update(self._count_state_rows(sched))
        t1 = tr.phase("ds.serve.stage", track="stage", sid=sid,
                      n_tokens=n_tokens, rows=n_rows, n_seqs=len(sched),
                      mbs=mbs, preemptions=self._round_preemptions,
                      **tiles, **self._count_attn_kv(sched, pallas))
        batch = self._stage(
            self.state.build_batch(
                sched, n_rows, stager=self._stager,
                draft_lens={u: len(d)
                            for u, d in self._sched_drafts.items()},
                n_verify=self._n_verify,
                # a request the engine continues keeps its chain whole:
                # the row fed from the device is written in when the
                # step that sampled it is read back
                deferred_from={u: self._fb_step[u] for u, t in sched
                               if t[0] == FEEDBACK_TOKEN
                               and u in self._cont} or None))
        if self.state.prefix_cache:
            # the prompt rows this step's admissions were spared by
            # aliasing indexed blocks, and the indexed blocks reclaimed
            # for new content since the last staged step (the batch's
            # allocations just made among them)
            evicted = self.state.prefix_evictions
            tr.phase_set(cached_tokens=self._round_cached,
                         prefix_evictions=evicted - self._evictions_seen)
            self._evictions_seen = evicted
        # device-order bracket: demote reads of just-evicted blocks must
        # enqueue before ANY write that may reuse them (COW copies,
        # restage uploads, the step itself) — stream ordering then makes
        # the read see the old content
        self._drain_tier_demote()
        self._drain_cow()       # COW copies land before the step's write
        self._drain_tier_restage(dispatching=True)
        # a cold call carries the XLA compile in its dispatch wall time:
        # the same interval, under the name that says so
        t2 = tr.phase("ds.serve.compile" if cold else "ds.serve.dispatch",
                      track="dispatch", sid=sid, n_tokens=n_tokens,
                      rows=n_rows, n_seqs=len(sched),
                      n_decode=sum(1 for _, t in sched if len(t) == 1),
                      mbs=mbs, ahead=int(bool(self._inflight_sched)))
        if rng is None and sampling.needs_rng:
            self._rng, rng = jax.random.split(self._rng)
        if rng is None:
            rng = self._zero_key          # greedy: the sampler ignores it
        prev = self._last_toks if self._last_toks is not None \
            else self._zero_toks
        uids = tuple(uid for uid, _ in sched)
        guard: Dict[str, float] = {}      # the watchdog's hand-off time

        def launch():
            if fresh:
                # a step function just built compiles every rung with
                # its first call, which is cold and runs unguarded
                self._compile_rungs(key[1:], step_fn, batch, prev, rng)
            return step_fn(self.params, self._quant, self.state.kv, batch,
                           prev, rng)

        try:
            # the one deadline-guarded dispatch seam: the watchdog
            # (and the chaos harness's fault injector) wrap exactly
            # this call — see inference/failures.py
            toks, self.state.kv = self.failures.run(
                launch, uids=uids, cold=cold, site="dispatch", sid=sid,
                stamps=guard)
        except Exception as e:
            # every failure on the dispatch path funnels through the
            # classifier seam (tpulint's serving-except rule holds the
            # loop to this); the live ledger IS this step's build
            tr.phase_end(failed=type(e).__name__)
            self._round.void = True
            registered = tuple(self.state.round_registered)
            prev, self._ahead = self._ahead, None
            if prev is not None:
                # the launch step() left in flight is read back first:
                # its tokens are real (step() hands them over), and the
                # failure path would otherwise close every row of it as
                # in flight elsewhere.  Should that read fail too, it
                # takes this step's rows with it (_collect, ``nxt``)
                retries = self.timings["step_retries"]
                self._held.update(self._collect(
                    prev, nxt=_InFlight(toks=None, emit=(), sid=sid,
                                        uids=uids, registered=registered)))
                if self.timings["step_retries"] != retries:
                    return None
            self._handle_step_failure(e, uids, "dispatch",
                                      registered=registered)
            return None
        hop_us = guard.get("hop_us", 0.0)
        t3 = tr.phase_end(hop_us=round(hop_us, 1))
        self._c_guard_hop.inc(hop_us / 1e3)
        self._warm_keys.add(("p", key))
        self._steps_done += 1
        self._last_toks = toks
        tm = self.timings
        tm["schedule_ms"] += (t1 - t0) * 1e3
        tm["stage_ms"] += (t2 - t1) * 1e3
        tm["device_ms"] += (t3 - t2) * 1e3
        tm["steps"] += 1
        self._round.cut_dispatch(t0, t1, t2, t3, cold, guard)
        self._c_step_rows.inc(1, rung=str(n_rows))
        self._row_tokens += n_tokens
        self._row_slots += n_rows
        if self._comm_active is not None:
            self._bump_comm_counters(n_rows)
        if cold:
            # first completed call of this program: where its step
            # function was built with it, its dispatch wall time carried
            # the XLA compiles (the timestamps are the ones above — the
            # compile span costs no extra clock reads)
            tm["compile_ms"] += (t3 - t2) * 1e3
            if self.devtel is not None:
                # once per program, on the warm executable — args are
                # the post-call live buffers (the donated kv was
                # rebound to the step's output)
                self.devtel.probe_program(
                    ("p",) + key, step_fn,
                    (self.params, self._quant, self.state.kv, batch, prev,
                     rng))
        if self.devtel is not None:
            self.devtel.on_dispatch(("p",) + key)
        if self._anom is not None:
            # streaming detectors fed from the timestamps/counters
            # above — no clock reads beyond the ones timings took
            self._feed_step_signals(t0, t2, t3)
        for uid, _ in sched:
            self.requests.on_prefill_start(uid, t3)
        emit = tuple((uid, self.state.slot(uid)) for uid, _ in sched
                     if not self._pending.get(uid))
        for uid in uids:
            self._inflight_sched[uid] = self._inflight_sched.get(uid, 0) + 1
        self._dispatch_seq += 1
        return _InFlight(toks=toks, emit=emit, sid=self._dispatch_seq,
                         uids=uids, n_tokens=n_tokens,
                         drafts=tuple((u, tuple(d)) for u, d in
                                      self._sched_drafts.items()),
                         stop=sampling.stop_token,
                         registered=tuple(self.state.round_registered),
                         cold=cold)

    def _comm_step_stats(self, n_rows: int) -> Dict[str, float]:
        """Modeled wire accounting for ONE dispatched step's decomposed
        TP collectives, derived from the compiled shapes (host
        arithmetic only): the down-projection all-reduces one
        [n_rows, d_model] partial per layer (the rung the step ran
        at), the unembed gathers one [rows, vocab] logits block.  Tile
        counts mirror the compiled program's ``_resolve_tiles`` clamp,
        not the raw config knob."""
        from ..comm.overlap import _resolve_tiles, wire_bytes

        comm = self._comm_active
        n = self.topology.tp_size
        isz = jnp.dtype(self.icfg.param_dtype).itemsize
        st = {"ops_exact": 0, "ops_quant": 0, "tiles": 0,
              "bytes_exact": 0.0, "bytes_quant": 0.0}
        if comm.downproj:
            elems = n_rows * self.cfg.d_model
            per = wire_bytes("all_reduce", elems, isz, n, comm.quant_bits)
            L = self.cfg.num_layers
            kind = "quant" if comm.quant_bits else "exact"
            st[f"ops_{kind}"] += L
            st[f"bytes_{kind}"] += per * L
            st["tiles"] += L * _resolve_tiles(n_rows, comm.tiles)
        if comm.unembed:
            rows = self.icfg.max_seqs * self._n_verify
            per = wire_bytes("all_gather", rows * self.cfg.vocab_size,
                             isz, n)
            st["ops_exact"] += 1
            st["bytes_exact"] += per
            st["tiles"] += _resolve_tiles(rows, comm.tiles)
        return st

    def _bump_comm_counters(self, n_rows: int) -> None:
        st = self._comm_stats.get(n_rows)
        if st is None:
            st = self._comm_stats[n_rows] = \
                self._comm_step_stats(n_rows)
        if st["ops_exact"]:
            self._c_comm_ops.inc(st["ops_exact"], kind="exact")
            self._c_comm_bytes.inc(st["bytes_exact"], kind="exact")
        if st["ops_quant"]:
            self._c_comm_ops.inc(st["ops_quant"], kind="quant")
            self._c_comm_bytes.inc(st["bytes_quant"], kind="quant")
        self._c_comm_tiles.inc(st["tiles"])

    def _drain_cow(self) -> None:  # tpulint: serving-loop
        """Execute queued copy-on-write block copies (a prefix-cache
        match that covered a whole prompt aliases its last block as a
        private copy) on device BEFORE the dispatch that appends into
        the copy.  Pure async enqueue — no host sync; a round with no
        full-cover match is a no-op."""
        copies = self.state.take_cow_copies()
        if not copies:
            return
        if self._cow_fn is None:
            def copy_block(kv, src, dst):
                return jax.tree.map(
                    lambda x: x.at[:, dst].set(x[:, src]), kv)

            # compiled once per engine (src/dst ride as traced scalars);
            # donation/placement policy shared with the step programs
            self._cow_fn = self._serving_jit(copy_block, kv_argnum=0,
                                             kv_only_output=True)
        with self.tracer.span("ds.serve.cow_drain", track="stage",
                              n=len(copies)):
            for src, dst in copies:
                self.state.kv = self._cow_fn(self.state.kv, np.int32(src),
                                             np.int32(dst))

    def _drain_tier_demote(self) -> None:  # tpulint: serving-loop
        """Read each just-evicted block off the device and demote its
        payload into the host tier (tier.py owns the host-side copy and
        any NVMe spill).  Runs BEFORE every write that could reuse the
        block — the COW drain, restage uploads, the step dispatch — so
        stream ordering guarantees the read sees the old content.  A
        round with no eviction is a no-op."""
        q = self.state.take_tier_demotes()
        if not q:
            return
        tm = self.timings
        with self.tracer.span("ds.serve.tier_demote", track="stage",
                              n=len(q)):
            for parent, digest, tokens, blk in q:
                payload = jax.tree.map(lambda x: x[:, blk], self.state.kv)
                ev = self.state.tier.put(parent, digest, tokens,
                                         jax.tree.leaves(payload))
                tm["kv_tier_demotions"] += ev["stored"]
                tm["kv_tier_demoted_bytes"] += ev["nbytes"]
                tm["kv_tier_spills"] += ev["spilled"]
                tm["kv_tier_spilled_bytes"] += ev["spilled_bytes"]
                tm["kv_tier_drops"] += ev["dropped"]

    def _drain_tier_restage(self,
                            dispatching: bool
                            ) -> None:  # tpulint: serving-loop
        """Resolve every queued tier->HBM restage: finish its I/O,
        verify the payload (checksum; the chain digest was verified at
        import for remote records), upload it into the reserved block
        and register the digest — or free the block and count a verify
        failure, leaving the deferred request to re-prefill.  Runs
        after the COW drain so uploads into just-evicted blocks enqueue
        AFTER the demote reads of those same blocks."""
        q = self.state.take_tier_restage()
        if not q:
            return
        if self._restage_fn is None:
            def write_block(kv, dst, payload):
                return jax.tree.map(
                    lambda x, p: x.at[:, dst].set(p), kv, payload)

            # same donation/placement policy as the step programs (and
            # the COW copy): the upload is an async enqueue, the drain
            # never waits on the device
            self._restage_fn = self._serving_jit(write_block, kv_argnum=0,
                                                 kv_only_output=True)
        tm = self.timings
        treedef = jax.tree.structure(self.state.kv)
        with self.tracer.span("ds.serve.tier_restage", track="stage",
                              n=len(q)):
            for ent in q:
                leaves = self.state.tier.resolve(ent.op)
                if leaves is None:
                    self.state.abort_restage(ent)
                    tm["kv_tier_verify_failures"] += 1
                    continue
                payload = jax.tree.unflatten(treedef, leaves)
                self.state.kv = self._restage_fn(
                    self.state.kv, np.int32(ent.dst), payload)
                self.state.commit_restage(ent)
                tm["kv_tier_revives_" + ent.op.source] += 1
                if dispatching:
                    tm["kv_tier_restage_overlap_hits"] += 1

    def _mark_feedback(self, uid: int, st: _InFlight) -> None:
        """Queue uid's next decode token as a deferred on-device read of
        step ``st``'s sample array (the driver speculates continuation
        without waiting for readback)."""
        self._pending[uid] = [FEEDBACK_TOKEN]
        self._fb_step[uid] = st.sid

    def _uncount_inflight(self, uids) -> None:
        """One dispatched step of ``uids`` is no longer uncollected."""
        for uid in uids:
            n = self._inflight_sched.get(uid, 0) - 1
            if n > 0:
                self._inflight_sched[uid] = n
            else:
                self._inflight_sched.pop(uid, None)

    @staticmethod
    def _samples_ready(nxt: Optional[_InFlight]) -> Optional[bool]:
        """Had the launch behind the one read back (``nxt``) already
        produced its samples?  None where there is none to ask."""
        if nxt is None or nxt.toks is None:
            return None
        return bool(nxt.toks.is_ready())

    def note_loop_lag(self, lag_ms: float, next_due_s: float) -> None:
        """The gateway's event loop reports how late its heartbeat ran
        and when the next is due, on ``time.monotonic`` (0.0: none, the
        driver has stopped) (``Gateway._beat``); the round under way
        keeps the worst lateness.  Called on the loop's thread: two
        floats, written bare."""
        rw = self._round
        rw.beat_due = next_due_s
        if lag_ms > rw.lag_ms:
            rw.lag_ms = lag_ms

    def _fetch_tokens(self, arr) -> np.ndarray:  # tpulint: serving-loop
        """THE sanctioned serving-loop readback: every device->host token
        fetch funnels through here so the ``serving-sync`` lint rule can
        keep ad-hoc syncs off the decode critical path."""
        return np.asarray(arr)  # tpulint: disable=serving-sync

    def _collect(self, st: _InFlight, nxt: Optional[_InFlight] = None
                 ) -> Dict[int, List[int]]:  # tpulint: serving-loop
        """Read one in-flight step's tokens back and emit them (a LIST
        per uid: one token for a plain decode/prefill row, up to
        ``1 + spec_max_draft`` for a resolved verify window); patches
        any still-deferred feedback marker THIS step owns to the concrete
        value (a later batch built after this read must never reference a
        stale device sample array).  Markers owned by a newer in-flight
        step — the same sequence sampled again before this read — are
        left for that step's collect.

        Speculative acceptance happens HERE (accept-longest-matching-
        prefix): a drafting row's [W] sample column ``j`` is the model's
        token after window position ``j``, so the drafts ``d_1..d_k``
        are compared against samples ``0..k-1`` — ``a`` leading matches
        emit ``a + 1`` tokens (the accepted drafts ARE samples
        ``0..a-1``, plus sample ``a``, the model's "bonus" token
        computed with every accepted draft already in context) and
        ``resolve_draft`` rewinds the KV write cursor over the rejected
        tail.  A stop token landing inside the window truncates the
        emission exactly where the stepwise engine would have stopped
        feeding, and the commit rolls back to it.

        ``nxt``: the step :meth:`step` launched behind this one, still
        unread.  It took this step's tokens from the device, so when
        this read fails its rows are re-queued with this step's; when
        this read makes the round a slow one, its record says whether
        ``nxt``'s samples were ready by then (``next_ready``: the device
        had gone on and only the completion came late)."""
        self._uncount_inflight(st.uids)
        tr = self.tracer
        guard: Dict[str, float] = {}      # the watchdog's hand-off time
        t0 = tr.phase("ds.serve.wait", track="wait", sid=st.sid)
        try:
            # readbacks surface deferred async-execution errors and can
            # hang with the device: same deadline guard + classifier
            # seam as the dispatch.  The host transfer itself rides the
            # same try — a device dying between the wait and the copy
            # must degrade like any other failure, not crash the loop
            self.failures.run(
                lambda: jax.block_until_ready(st.toks),
                uids=st.uids, cold=st.cold, site="collect", sid=st.sid,
                stamps=guard)
            hop_us = guard.get("hop_us", 0.0)
            rw = self._round
            if guard:
                # the hand-off's stamps lie on the trace's clock too
                fn_us = guard["fn_us"]
                tr.phase_set(hop_us=round(hop_us, 1),
                             queued_us=round(guard["queued_us"], 1),
                             fn_us=round(fn_us, 1),
                             taken_us=round(guard["taken_us"], 1))
                if hop_us + fn_us > rw.limit_us and not rw.void:
                    # this wait alone makes the round a slow one: the
                    # verdict is reached while its phase is open
                    tr.phase_set(slow=rw.judge_wait(
                        st.sid, guard, self._samples_ready(nxt)))
            else:
                tr.phase_set(hop_us=0.0)
            t1 = tr.phase("ds.serve.readback", track="readback",
                          sid=st.sid)
            if not guard and (t1 - t0) * 1e6 > rw.limit_us:
                rw.next_ready = self._samples_ready(nxt)
            toks_np = self._fetch_tokens(st.toks)
        except Exception as e:
            tr.phase_end(failed=type(e).__name__)
            self._round.void = True
            uids, registered = st.uids, st.registered
            if nxt is not None:
                # the launch behind this one read this step's tokens on
                # the device: its rows go back to the queue with these
                # (the rows it fed from the device are rewound first, so
                # every chain ends at a token the host knows)
                self._ahead = None
                self._uncount_inflight(nxt.uids)
                for uid in nxt.uids:
                    seq = self.state.seqs.get(uid)
                    if seq is not None and seq.deferred:
                        self.state.rewind(uid, len(seq.deferred))
                    self._void.pop(uid, None)
                uids = tuple(dict.fromkeys(uids + nxt.uids))
                registered = registered + nxt.registered
            if nxt is not None or st.sid == self._dispatch_seq:
                # this WAS the latest dispatch (or fed it): its sample
                # array must never feed a later step (markers deferring
                # to it are cleaned by the re-queue below; zero fallback
                # is safe)
                self._last_toks = None
            self._handle_step_failure(e, uids, "collect",
                                      registered=registered)
            return {}
        moe: Dict[str, float] = {}
        if self._moe_metrics is not None:
            # the routing statistics rode the tokens' own readback
            rows = moe_stat_rows(self.cfg)
            n, load, touched, *more = toks_np[-rows:].reshape(
                rows, -1)[:, 0]
            moe = {"moe_assignments": int(n), "moe_load": load / 1e3,
                   "moe_experts_touched": int(touched)}
            if self.cfg.held_groups is not None:
                # of the step's rows (a row a layer), those that opened
                # a device group held here
                moe["moe_groups_open_here"] = int(more.pop())
            nothing = more
            if self.cfg.experts_held is None and not nothing:
                self._moe_metrics[0].inc(moe["moe_assignments"])
            else:
                # the router made top_k a real row a layer; the step
                # computed those that fell on the experts held here, and
                # gave the input back for those that compute nothing
                made = st.n_tokens * self.cfg.moe_top_k \
                    * self.cfg.expert_layers
                zero = int(nothing[0]) if nothing else 0
                moe["moe_assignments_made"] = made
                self._moe_metrics[0].inc(int(n), where="held")
                self._moe_metrics[0].inc(made - int(n) - zero,
                                         where="absent")
                if nothing:
                    moe["moe_zero_assignments"] = zero
                    self._moe_metrics[0].inc(zero, where="zero")
            self._moe_metrics[1].set(moe["moe_load"])
        t2 = tr.phase_end(**moe)
        self._c_guard_hop.inc(hop_us / 1e3)
        self._note_step_success(st.uids)
        tm = self.timings
        tm["wait_ms"] += (t1 - t0) * 1e3
        tm["readback_ms"] += (t2 - t1) * 1e3
        rw.cut_collect(st.sid, t0, t1, t2, st.cold, guard)
        if self._anom is not None:
            ev = self._anom.observe("step_wait_ms", (t1 - t0) * 1e3,
                                    self._steps_done)
            if ev is not None:
                self._on_anomaly(ev)
        spec = self._n_verify > 1
        drafts = dict(st.drafts)
        out: Dict[int, List[int]] = {}
        for uid, slot in st.emit:
            row = toks_np[slot]        # [W] on a spec engine, else 0-d
            seq = self.state.seqs.get(uid)
            if self._void.get(uid) == st.sid:
                # paused after this row was launched (hold()): rewound
                # there, never emitted; the caller's put computes it anew
                del self._void[uid]
                self._c_discarded.inc(reason="stalled")
                continue
            if seq is None or self.state._slots.get(uid) != slot:
                # launched for a stream that has ended since (or was
                # re-queued): nobody is handed this token
                status = self.requests.status_of(uid)
                self._c_discarded.inc(
                    reason=status if status not in (None, "open")
                    else "requeued")
                continue
            d = drafts.get(uid)
            if d:
                a = 0
                while a < len(d) and int(row[a]) == d[a]:
                    a += 1
                emitted = [int(row[j]) for j in range(a + 1)]
                if st.stop is not None and st.stop in emitted:
                    # stop inside the window: everything past it was
                    # never fed by a stepwise engine — roll it back too
                    emitted = emitted[:emitted.index(st.stop) + 1]
                # commit fed token + the emitted tokens already in
                # KV (all but the bonus sample); rewind the rest
                self.state.resolve_draft(uid, len(emitted) - 1)
                # spec accounting — engine counters and the request
                # record move at the same statements so
                # sum(per-request) reconciles by construction
                tm["spec_windows"] += 1
                tm["spec_drafted_tokens"] += len(d)
                tm["spec_accepted_tokens"] += len(emitted) - 1
                tm["spec_rejected_tokens"] += len(d) - (len(emitted)
                                                        - 1)
                self.requests.on_draft(uid, len(d), len(emitted) - 1)
                if self._anom is not None:
                    evt = self._anom.observe(
                        "spec_acceptance",
                        (len(emitted) - 1) / len(d),
                        self._steps_done)
                    if evt is not None:
                        self._on_anomaly(evt)
            else:
                emitted = [int(row[0] if spec else row)]
            seq.tokens.extend(emitted)
            if seq.deferred:
                self.state.resolve_feedback(uid, st.sid, emitted[-1])
            if uid in self._cont:
                self._cont[uid] -= len(emitted)
            # emitted to a live sequence: the engine generated-token
            # counter and the request record move together (parity
            # invariant, tests/test_telemetry.py)
            tm["generated_tokens"] += len(emitted)
            self.requests.on_tokens(uid, len(emitted), t2)
            if self._anom is not None:
                rec = self.requests.open.get(uid)
                if rec is not None \
                        and rec.generated_tokens == len(emitted):
                    # this emission WAS the first token — TTFT is
                    # known now, not at finish
                    evt = self._anom.observe(
                        "ttft_ms", rec.ttft_ms, self._steps_done)
                    if evt is not None:
                        self._on_anomaly(evt)
            if self._spec is not None:
                self._spec.observe(uid, emitted)
            out[uid] = emitted
            if self._fb_step.get(uid) == st.sid:
                self._fb_step.pop(uid)
                p = self._pending.get(uid)
                if p and p[0] == FEEDBACK_TOKEN:
                    # the marker's value is the NEXT fed token = the
                    # last emitted one (markers are never speculated
                    # for drafting rows, so this is column 0's sample)
                    p[0] = emitted[-1]
        cap = self._cap
        if cap is not None and cap.active:
            fin = cap.end_step(sid=st.sid, step=self._steps_done)
            if fin is not None:
                self._finish_capture(fin)
        return out

    # ------------------------------------------------------------------
    def generate(self, prompts: Dict[int, Sequence[int]],
                 sampling: SamplingParams = SamplingParams(),
                 rng: Optional[jax.Array] = None
                 ) -> Dict[int, List[int]]:  # tpulint: serving-loop
        """Convenience loop: run all prompts to max_new_tokens/stop over
        the served path — every prompt is put with ``max_new_tokens``
        and :meth:`step` runs one launch ahead until each request of
        the call is closed.  A stream that stops is flushed, and the
        token launched ahead for it is thrown away at its read
        (``serving_ahead_discarded_rows_total{reason="finished"}``): the
        caller sees no token past the stop and never more than
        ``max_new_tokens``."""
        done: Dict[int, List[int]] = {}
        active = set()
        for uid, p in prompts.items():
            done[uid] = []
            if self.put(uid, p, max_new_tokens=sampling.max_new_tokens):
                # under a bounded admission queue a prompt may be shed
                # at put() time — its row stays empty (query() says why)
                active.add(uid)
        idle = 0
        while active:
            out = self._step(rng, sampling)
            # engine-side terminal closures (deadline expiry, cancel,
            # shed-by-eviction, context exhausted) end those requests'
            # generation here
            active -= self._drain_reaped()
            for uid, toks in out.items():
                if uid not in active:
                    continue               # put() outside generate()
                row = done[uid]
                row.extend(toks)
                if sampling.stop_token in toks \
                        or len(row) >= sampling.max_new_tokens:
                    active.discard(uid)
                    self.flush(uid)
            # nothing read and nothing in flight: either every remaining
            # request just finished above, or the pool is wedged
            idle = 0 if out or self.in_flight else idle + 1
            if idle > 100_000:
                raise RuntimeError("generate() did not terminate")
        # a stream that stopped left its next token launched: that
        # launch is read back here (the token is thrown away there), so
        # the engine is handed back with nothing in flight
        self._settle()
        # the workload ran out before an active capture window did:
        # close it with the steps it has rather than strand the
        # process-wide profiler session
        self.finish_capture()
        return done
