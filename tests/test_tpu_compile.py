"""The main path's Pallas kernels compile for a TPU v5e — no chip needed.

The TPU compiler ships with the installed libtpu and compiles for a chip
that is *described* (``v5e:2x2``), not attached, so every kernel the
serving and training hot paths launch is lowered through Mosaic here at
its real widths: a kernel the chip's compiler refuses (an op it cannot
legalize, a mis-tiled slice, too much VMEM) fails in tier-1 instead of
on the first chip run.  Nothing executes — this says nothing about
results or speed; ``chip_smoke.py`` checks those on the chip.

The topology is described only inside the module-scoped fixture below
(one process at a time may hold libtpu, and every xdist worker imports
every test file — see the on-chip-measurement guide), all such tests
live in this one file and compile in the test's own process.
"""

import base64
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.models.presets import PRESETS
from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
from deepspeed_tpu.ops.mixed_gemm import mixed_matmul
from deepspeed_tpu.ops.paged_attention import (GROUP_SCORE_BYTES,
                                               GROUP_VMEM_BYTES, LONG, SHORT,
                                               block_vmem_bytes,
                                               group_vmem_bytes, kv_group,
                                               paged_attention, query_tiles)
from deepspeed_tpu.inference.ragged.state import KVCacheConfig
from deepspeed_tpu.ops.quant import QuantizedTensor

LLAMA = PRESETS["llama3-8b"]
GPT2 = PRESETS["gpt2"]
OLMOE = PRESETS["olmoe-1b-7b"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # tpulint: disable=silent-except — capability probe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the kernels' interpret-mode choice (they ask
    ``jax.default_backend()``, which says ``cpu`` here) to the compiled
    path, with the persistent compile cache off: an executable compiled
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    return text.count("tpu_custom_call")


# ---------------------------------------------------------------- paged
# decode attention over the paged cache, one layer: the serving step's
# token budget T, max_seqs 8, blocks of 64
_LLAMA_PAGED = dict(T=1024, H=LLAMA["num_heads"], Hkv=LLAMA["num_kv_heads"],
                    D=128, blocks=128, nb=16)
PAGED = {
    "llama3-8b-bf16": dict(_LLAMA_PAGED, kv_quant=False),
    "llama3-8b-int8kv": dict(_LLAMA_PAGED, kv_quant=True),
    # one query head per kv head: the kernel's static loop runs 16 times
    # over [1, 128] x [128, 64] products
    "olmoe-1b-7b-bf16": dict(T=512, H=OLMOE["num_heads"],
                             Hkv=OLMOE["num_kv_heads"], D=128, blocks=768,
                             nb=16, kv_quant=False),
    "gpt2-bf16": dict(T=256, H=GPT2["num_heads"], Hkv=GPT2["num_heads"],
                      D=GPT2["d_model"] // GPT2["num_heads"], blocks=256,
                      nb=16, kv_quant=False),
    # eight query heads to a kv head (1024 folded rows a long tile), a
    # table of 192 blocks, and the window layers' form of the kernel
    "trinity-mini-bf16-full": dict(T=512, H=32, Hkv=4, D=128, blocks=1024,
                                   nb=192, kv_quant=False),
    "trinity-mini-bf16-window": dict(T=512, H=32, Hkv=4, D=128, blocks=1024,
                                     nb=192, kv_quant=False, window=2048),
}


def _paged_calls(jaxpr):
    """(equation, is it inside a scan, height, KV blocks a group of it
    holds) for the calls of ``ops/paged_attention.py`` in ``jaxpr``: the
    kernel fetches a group into one of two VMEM buffers ``[2, k, bs, 2,
    Hkv, D]``, its only scratch of that rank."""
    for e, scanned in _eqns(jaxpr):
        if e.primitive.name != "pallas_call" or not e.params[
                "name"].startswith("paged_attention"):
            continue
        gm = e.params["grid_mapping"]
        (buf,) = [v.aval for v in
                  e.params["jaxpr"].invars[-gm.num_scratch_operands:]
                  if len(getattr(v.aval, "shape", ())) == 6]
        assert buf.shape[0] == 2
        yield (e, scanned, int(e.params["name"].rpartition("_h")[2]),
               buf.shape[1])


def _tiled_bytes(aval) -> int:
    """VMEM a scratch buffer takes, its trailing dims padded to whole
    (sublane, lane) tiles of its type."""
    if not hasattr(aval.dtype, "itemsize"):     # semaphores
        return 0
    *lead, rows, lanes = (1,) + tuple(aval.shape)
    sub = 32 // aval.dtype.itemsize
    n = aval.dtype.itemsize * (-(-rows // sub) * sub) * (-(-lanes // 128)
                                                          * 128)
    for d in lead:
        n *= d
    return n


@pytest.mark.parametrize("case", sorted(PAGED))
def test_paged_attention_compiles(one_chip, on_chip, case):
    c = PAGED[case]
    T, bs = c["T"], 64
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    # the pool as the engine allocates it for the kernel (a block's slab
    # filled up to whole memory tiles: gpt2's 12 x 64 as 16 x 128), the
    # queries with its heads and lanes
    cache = KVCacheConfig(1, c["Hkv"], c["D"], block_size=bs,
                          num_blocks=c["blocks"],
                          quant="int8" if c["kv_quant"] else "none",
                          tiled=True)
    Hkv, D = cache.slab
    H = c["H"] // c["Hkv"] * Hkv
    kv = jax.tree.map(lambda a: S(a.shape[1:], a.dtype),
                      jax.eval_shape(cache.kv_zeros))
    q = S((T, H, D), jnp.bfloat16)
    idx = S((T,), jnp.int32)
    tables = S((8, c["nb"]), jnp.int32)

    def fn(kv, q, slot, pos, valid, tables):
        tiles = query_tiles(slot, pos, valid, tables, bs, c["nb"],
                            trash=c["blocks"])
        return paged_attention(kv, q, tiles, c["D"] ** -0.5,
                               window=c.get("window"))

    # the one kernel body at its two heights
    shapes = (kv, q, idx, idx, S((T,), jnp.bool_), tables)
    assert _compile(fn, *shapes) == 2
    # each call's group is what the rule gives its shape, and what the
    # short call keeps in VMEM (the group's two buffers, as Mosaic tiles
    # them, the rest of its scratch and the score tile) is inside the
    # rule's budget and well inside what Mosaic scopes by default
    calls = {h: (e, k) for e, _, h, k in _paged_calls(
        jax.make_jaxpr(fn)(*shapes).jaxpr)}
    assert sorted(calls) == [SHORT, LONG]
    kv_dtype = jnp.int8 if c["kv_quant"] else jnp.bfloat16
    for height, (e, k) in calls.items():
        assert k == kv_group(height, H // Hkv, Hkv, D, bs, kv_dtype,
                             c["nb"], c["kv_quant"])
        # one launch row a tile; the pool (and its scales) stay in HBM
        gm = e.params["grid_mapping"]
        assert gm.num_dynamic_grid_bounds == 1
        assert [len(bm.block_shape) for bm in gm.block_mappings] == [
            3] + [5, 3][:1 + c["kv_quant"]] + [3, 3]
    e, k = calls[SHORT]
    assert k > 1
    rows = SHORT * H // Hkv
    held = group_vmem_bytes(k, rows, Hkv, D, bs, kv_dtype, c["kv_quant"])
    assert rows * k * bs * 4 <= GROUP_SCORE_BYTES
    assert 2 * k * block_vmem_bytes(Hkv, D, bs, kv_dtype,
                                    c["kv_quant"]) < held <= GROUP_VMEM_BYTES
    gm = e.params["grid_mapping"]
    scratch = [v.aval for v in
               e.params["jaxpr"].invars[-gm.num_scratch_operands:]]
    # (``held`` counts the two buffers, the scratch of rank 5 and 6)
    assert held + sum(_tiled_bytes(a) for a in scratch
                      if len(getattr(a, "shape", ())) < 5) <= 16 * 2 ** 20


# ---------------------------------------------------------------- flash
FLASH = {
    "gpt2-B4-S1024-H12-D64": (4, 1024, 12, 12, 64),
    "llama3-8b-B1-S2048-H32kv8-D128": (1, 2048, 32, 8, 128),
}


def _flash_shapes(one_chip, case):
    B, S, H, Hkv, D = FLASH[case]
    return (jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                                 sharding=one_chip))


@pytest.mark.parametrize("case", sorted(FLASH))
def test_flash_forward_compiles(one_chip, on_chip, case):
    q, kv = _flash_shapes(one_chip, case)
    assert _compile(flash_attention, q, kv, kv) == 1


@pytest.mark.parametrize("case", sorted(FLASH))
def test_flash_backward_compiles(one_chip, on_chip, case):
    q, kv = _flash_shapes(one_chip, case)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    # forward (for the residuals) + the dq kernel + the dk/dv kernel
    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 3


# ----------------------------------------------------------- mixed GEMM
# llama3-8b MLP up-projection [d_model, d_ff]: decode (M=8 sequences)
# and prefill (M=1024-token budget)
@pytest.mark.parametrize("M", [8, 1024])
@pytest.mark.parametrize("bits", [8, 4])
def test_mixed_gemm_compiles(one_chip, on_chip, bits, M):
    K, N = LLAMA["d_model"], LLAMA["d_ff"]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    data = S((K, N) if bits == 8 else (K // 2, N), jnp.int8)
    qt = QuantizedTensor(data, S((K, 1), jnp.float32), None, bits, (K, N),
                         jnp.bfloat16,
                         "rowwise" if bits == 8 else "rowwise4")
    assert _compile(mixed_matmul, S((M, K), jnp.bfloat16), qt) == 1


# ------------------------------------------------------- grouped matmul
# olmoe-1b-7b's expert projections: 512 tokens x 8 experts a token are
# 4096 sorted rows over 64 experts; up/gate [2048, 1024], down [1024, 2048]
@pytest.mark.parametrize("K,N", [(2048, 1024), (1024, 2048)],
                         ids=["olmoe-up", "olmoe-down"])
def test_grouped_matmul_compiles(one_chip, on_chip, K, N):
    E, rows = OLMOE["num_experts"], 512 * OLMOE["moe_top_k"]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    assert _compile(grouped_matmul, S((rows, K), jnp.bfloat16),
                    S((E, K, N), jnp.bfloat16), S((E,), jnp.int32)) == 1


# granite-4.0-h-small's expert layer at the token budget: 512 rows, 36 of
# 72 experts held, ten choices a row (and twelve, longcat-flash's count):
# 5,120 and 6,144 expert rows of 4,096 come back to 512
@pytest.mark.parametrize("top_k", [10, 12])
def test_expert_combine_is_one_pass_over_the_expert_rows(one_chip, on_chip,
                                                         top_k):
    """``moe_serve``'s weighted sum reads the experts' bf16 rows where
    the gather wrote them: no float32 array of ``T * K * d`` elements
    stands in the compiled layer.  (A sum over the middle axis of
    ``[T, K, d]`` makes one, ``f32[512,10,4096]`` with its second-minor
    10 padded to 16 under the (8, 128) tiling: 134 MB written and read a
    layer, PERF.md, PR 53.)"""
    from deepspeed_tpu.parallel.moe import moe_serve

    T, d, ff, outputs, held = 512, 4096, 768, 72, (0, 36)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    gate = {"kernel": S((d, outputs), jnp.bfloat16)}
    experts = {k: S((held[1],) + ((ff, d) if k == "wo" else (d, ff)),
                    jnp.bfloat16) for k in ("wi", "wg", "wo")}
    text = jax.jit(functools.partial(
        moe_serve, top_k=top_k, activation=jax.nn.silu, gated=True,
        norm_topk=True, kernel=True, held=held)).lower(
            gate, experts, S((T, d), jnp.bfloat16),
            S((T,), jnp.bool_)).compile().as_text()
    entry = text[text.index("ENTRY "):]
    # the reader reads: the rows gathered for the experts and back
    assert f" = bf16[{T * top_k},{d}]" in entry
    largest = max(math.prod(map(int, dims.split(",")))
                  for dims in re.findall(r" = f32\[([\d,]+)\]", entry))
    assert largest < T * top_k * d // 8


# --------------------------------------------------------- serving step
# the whole pipelined serving step of mistral-7b-d16 (the benchmark's
# serving configuration: 16 layers, a pool of 1024 blocks of 64 tokens,
# 512 tokens and 64 sequences a step, the 16-block decode bucket) with
# the cache donated, as ``InferenceEngine._build_pstep`` jits it
_ITEM = {"bf16": 2, "f32": 4, "s8": 1, "s32": 4, "u32": 4, "pred": 1}
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _moves_of(text: str, floor: int):
    """Instructions outside fused computations that copy, slice or
    update-slice (alone or as the fusion XLA names after them) and
    whose result holds ``floor`` bytes or more."""
    found, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():      # a computation's head
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if fused or not m:
            continue
        name, result, op = m.groups()
        if not (op.startswith(_MOVES) or (op == "fusion" and any(
                k in name for k in _MOVES))):
            continue
        for dt, dims in re.findall(r"(\w+)\[([\d,]+)\]", result):
            n = _ITEM.get(dt, 0)
            for d in dims.split(","):
                n *= int(d)
            if n >= floor:
                found.append(f"{name}: {dt}[{dims}]")
    return found


def _kernel_bodies(lowered):
    """The Mosaic kernels' serialised modules as a lowered program's text
    holds them (which escapes a quote as \\22).  A kernel's module
    travels in the program and so in the program's key in the compile
    cache, locations and all: the ten innermost frames of every
    operation."""
    return re.findall(r'body\\22: \\22([\w+/=]+)', lowered.as_text())


def _kernel_source_files(body):
    """The source files that the locations of a kernel's serialised
    module name."""
    return sorted({f.decode() for f in re.findall(
        rb"/[\w/.\-]+\.py", base64.b64decode(body))})


def _pstep_compiled(one_chip, cfg, kv_quant, T, seqs, bs, mbs, blocks):
    """``InferenceEngine._build_pstep``'s program for ``cfg`` compiled
    for the described chip, the cache donated → (compiled, bytes of one
    layer's share of the pool); ``compiled.jaxpr()`` traces the step
    again for a test that reads its equations."""
    lowered, pstep, args, layer_bytes = _pstep_lowered(
        one_chip, cfg, kv_quant, T, seqs, bs, mbs, blocks)
    compiled = lowered.compile()
    compiled.jaxpr = lambda: jax.make_jaxpr(pstep)(*args).jaxpr
    return compiled, layer_bytes


def _pstep_lowered(one_chip, cfg, kv_quant, T, seqs, bs, mbs, blocks):
    """``_pstep_compiled``'s program lowered → (lowered, the step, its
    arguments, bytes of one layer's share of the pool).  ``one_chip``
    None: lowered for the platform with no chip described (the kernels'
    modules are made by then; nothing of it can be compiled)."""
    from deepspeed_tpu.inference import SamplingParams
    from deepspeed_tpu.inference.model import (fold_projections,
                                               moe_stat_rows,
                                               pipelined_ragged_step)
    from deepspeed_tpu.inference.ragged.state import RaggedBatch
    from deepspeed_tpu.inference.sampler import sample_rows
    from deepspeed_tpu.models.transformer import init_params

    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: fold_projections(init_params(cfg, k)[0]),
                       jax.random.PRNGKey(0)))
    # the pool as the engine allocates it where it runs the kernel; a
    # layer holds ONE kind of cache: a latent model's is its latent rows
    latent = "mla" in cfg.mixer_stacks
    kv = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(KVCacheConfig(
            cfg.block_layers,
            cfg.num_kv_heads, cfg.head_dim, block_size=bs,
            num_blocks=blocks, quant="int8" if kv_quant else "none",
            latent_dim=cfg.mla_dims.row if latent else 0,
            tiled=not latent).kv_zeros))
    data = jax.tree.leaves(kv)[0]
    layer_bytes = data.size * data.dtype.itemsize // data.shape[0]
    rec = None
    if cfg.has_ssm:
        # a model with recurrent layers: the state rows beside the pool
        from deepspeed_tpu.inference.ragged.state import RecBatch
        if cfg.recurrent_kind == "kda":
            sd = cfg.kda_dims
            rows = (cfg.layers_of("kda"), seqs + 1)
            state = (sd.heads, sd.key_dim, sd.value_dim)
        else:
            sd = cfg.ssm_dims
            # (a "mamba" layer holds a state and no blocks; a "hybrid"
            # layer both, and every layer is one)
            rows = (cfg.layers_of("mamba") or cfg.num_layers, seqs + 1)
            state = (sd.heads, sd.head_dim, sd.state)
        # (a delta-rule state is stored in float32: the engine's rule)
        kv = {"kv": kv,
              "ssm": S(rows + state, jnp.float32
                       if cfg.recurrent_kind == "kda" else jnp.bfloat16),
              "conv": S(rows + (sd.conv, sd.conv_channels), jnp.bfloat16)}
        rec = RecBatch(run_len=S((seqs,), jnp.int32),
                       replay=S((seqs,), jnp.bool_),
                       chunks=S((-(-T // sd.chunk) + 4, 5), jnp.int32))
    elif cfg.mixer_stacks:
        # a latent-only model: its runs cut as the engine's RunCut cuts
        from deepspeed_tpu.inference.ragged.state import RecBatch, RunCut
        rec = RecBatch(run_len=S((seqs,), jnp.int32),
                       replay=S((seqs,), jnp.bool_),
                       chunks=S((RunCut(cfg.kda_chunk).n_chunks(T), 5),
                                jnp.int32))
    tok = S((T,), jnp.int32)
    batch = RaggedBatch(
        token_ids=tok, positions=tok, seq_slot=tok,
        token_valid=S((T,), jnp.bool_),
        block_tables=S((seqs, max(32, mbs)), jnp.int32),
        context_lens=S((seqs,), jnp.int32),
        logits_idx=S((seqs,), jnp.int32), n_tokens=T, n_seqs=seqs,
        feedback_src=tok, seq_uids=S((seqs,), jnp.uint32), rec=rec)
    greedy = SamplingParams(temperature=0.0, max_new_tokens=1)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def pstep(params, kv, batch, prev, rng):
        return pipelined_ragged_step(
            cfg, params, None, kv, batch, prev, rng,
            lambda logits, keys: sample_rows(logits, greedy, keys),
            bs, mbs, attn_impl="pallas")

    prev = seqs + (moe_stat_rows(cfg) if cfg.num_experts > 1 else 0)
    args = (params, kv, batch, S((prev,), jnp.int32),
            S(key.shape, key.dtype))
    traced = jax.jit(pstep, donate_argnums=(1,)).trace(*args)
    lowered = traced.lower() if one_chip is not None \
        else traced.lower(lowering_platforms=("tpu",))
    return lowered, pstep, args, layer_bytes


def _eqns(jaxpr, inside_scan=False):
    """(equation, is it inside a scan) for every equation of ``jaxpr``
    and of the jaxprs its equations hold."""
    for e in jaxpr.eqns:
        yield e, inside_scan
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub, inside_scan or e.primitive.name == "scan")


def _tile_grid_conditions(jaxpr, T, seqs, mbs, groups):
    """The tile grid of ``ops/paged_attention.py`` in the step's jaxpr:
    two calls a layer (one kernel body, two heights), each over a traced
    count of tiles whose static bound is at most ``T/128 + seqs`` launch
    rows (a tile walks its own groups of KV blocks inside its row), and
    the block-table rows those tiles carry gathered once a step, outside
    the layer scan, and handed to the calls as they are.  ``groups``:
    the KV blocks a group of the short and of the long call holds."""
    calls = list(_paged_calls(jaxpr))
    assert len(calls) == 2 and all(scanned for _, scanned, _, _ in calls)
    assert sorted(h for _, _, h, _ in calls) == [SHORT, LONG]
    for e, _, height, group in calls:
        gm = e.params["grid_mapping"]
        assert gm.num_dynamic_grid_bounds == 1      # (tiles,)
        tables = e.invars[gm.num_dynamic_grid_bounds].aval
        assert tables.shape[1] == mbs
        assert group == groups[height != SHORT]
        assert tables.shape[0] <= T // 128 + seqs
    table_gathers = [scanned for e, scanned in _eqns(jaxpr)
                     if e.primitive.name == "gather"
                     and e.outvars[0].aval.ndim == 2
                     and e.outvars[0].aval.shape[1] >= mbs
                     and e.outvars[0].aval.dtype == jnp.int32]
    # one gather a height: nothing lays the tables out a second time
    assert len(table_gathers) == 2 and not any(table_gathers)


def test_recurrent_serving_step_compiles_fits_and_keeps_its_pools_in_place(
        one_chip, on_chip):
    """The whole serving step of ``falcon-h1-34b-d6`` as the benchmark
    runs it (6 layers, 1536 blocks of 64, 512 tokens and 128 sequences a
    step): the paged kernel at five query heads a kv head and the
    one-token state update's kernel; the state rows ride the layer scan
    beside the paged pool and neither is copied, sliced out of its stack
    or written back whole; weights, both pools and temporaries fit a
    16 GB chip."""
    from deepspeed_tpu.models.presets import build_config

    cfg = build_config("falcon-h1-34b", num_layers=6, max_seq_len=1024)
    compiled, layer_bytes = _pstep_compiled(
        one_chip, cfg, False, T=512, seqs=128, bs=64, mbs=16, blocks=1536)
    text = compiled.as_text()
    # the paged kernel's two, the state update's one, the chunked form's
    assert text.count("tpu_custom_call") == 4
    assert len(re.findall(r"%ssm_state_update[\w.]* = ", text)) == 1
    assert len(re.findall(r"%ssm_chunk_scan[\w.]* = ", text)) == 1
    sd = cfg.ssm_dims
    state_layer = 129 * sd.heads * sd.head_dim * sd.state * 2
    assert layer_bytes == 1537 * 64 * 2 * 4 * 128 * 2
    # all the temporaries together stay under one layer's share of
    # either pool (103 MB, 34 MB of them the update kernel's vectors a
    # slot, laid out for its tiles): the one-token update and the
    # chunked form read and write the state rows where they lie (each
    # kernel's stack is aliased to its result; XLA's chunked form cut a
    # chunk's first state out and wrote its last back a 2 MiB row at a
    # time, and a gather over the layer made a 270 MB copy of it)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < min(layer_bytes, state_layer)
    moved = [m for m in _moves_of(text, state_layer)
             if "dynamic-update-slice" not in m and "fusion" not in m]
    assert moved == [], moved
    assert mem.argument_size_in_bytes > 13.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_one_cache_a_layer_serving_step_holds_no_decay_tensor(one_chip,
                                                              on_chip):
    """The serving step of ``granite-4.0-h-small-d10`` as the benchmark
    runs it (nine Mamba-2 layers of 128 heads, chunk 256, one attention
    layer, 36 of 72 experts; 64 sequences) at its 512-row rung: the chunked
    form is ONE kernel a place the layers are written out, and no
    float32 array of ``chunks x 256 x 256 x 128`` elements (XLA's decay
    and scores, 201 MB each at six chunks) or of a chunk's rows by head
    (``[chunks, 256, 128, 64]``, three relayouts a layer) is left in the
    program; its temporaries are printed (the parent's 512-row step:
    243.3 MB, PERF.md section 6, PR 53)."""
    from deepspeed_tpu.models.presets import build_config

    rows = 512
    cfg = build_config("granite-4.0-h-small", num_layers=10,
                       experts_held=(0, 36), max_seq_len=9216)
    compiled, _ = _pstep_compiled(
        one_chip, cfg, False, T=rows, seqs=64, bs=64, mbs=144, blocks=9216)
    text = compiled.as_text()
    kernels = re.findall(r"%(ssm_chunk_scan[\w.]*) = ", text)
    updates = re.findall(r"%(ssm_state_update[\w.]*) = ", text)
    assert kernels and len(kernels) == len(updates)
    sd = cfg.ssm_dims
    chunks = -(-rows // sd.chunk) + 4
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = [int(d) for d in dims.split(",")]
        assert math.prod(shape) < chunks * sd.chunk * sd.heads * sd.head_dim, \
            shape
    mem = compiled.memory_analysis()
    print(f"granite-4.0-h-small-d10, {rows} rows: temporaries "
          f"{mem.temp_size_in_bytes / 1e6:.1f} MB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_delta_rule_and_latent_serving_step_compiles_and_fits(one_chip,
                                                             on_chip):
    """The whole serving step of ``ling-3.0-flash-d7`` as the benchmark
    runs it (a dense layer and a period of five delta-rule layers and a
    latent one, 128 of 512 experts a layer, 12288 blocks of 64 latent
    rows, 512 tokens and 128 sequences a step, tables of 96 blocks): the
    grouped kernel's three projections a layer, the delta rule's state
    update in six and the latent layer's attention at its two heights
    are the program's only Pallas calls; the state
    rows of six layers and the latent pool of one ride
    the layer scan and neither is copied whole; weights, both caches and
    temporaries fit a 16 GB chip."""
    import json

    from benchmarks.lib.drivers.serve_hybrid_share import preset_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmarks/configs/ling-3.0-flash-d7.json")) as f:
        cfg = preset_config(json.load(f))
    compiled, layer_bytes = _pstep_compiled(
        one_chip, cfg, False, T=512, seqs=128, bs=64, mbs=96, blocks=12288)
    text = compiled.as_text()
    # one period of six expert layers, which a scan of one trip unrolls
    assert text.count("tpu_custom_call") == 6 * 3 + 6 + 3
    assert len(re.findall(r"%kda_state_update[\w.]* = ", text)) == 6
    # 32 heads: a one-token run's tile, tiles of 32 rows, and the
    # expanded form's tile of a run's 512
    assert len(re.findall(r"%latent_attention_h(1|32|512)[\w.]* = ",
                          text)) == 3
    # a row of 576 values in five whole vectors of 128 lanes
    assert layer_bytes == 12289 * 64 * 640 * 2
    kd = cfg.kda_dims
    state_layer = 129 * kd.heads * kd.key_dim * kd.value_dim * 4
    moved = [m for m in _moves_of(text, min(layer_bytes, state_layer))
             if "dynamic-update-slice" not in m and "fusion" not in m]
    assert moved == [], moved
    mem = compiled.memory_analysis()
    print("ling-3.0-flash-d7 step:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("rows", [128, 512])
def test_latent_shortcut_serving_step_compiles_and_fits(one_chip, on_chip,
                                                        rows):
    """The whole serving step of ``longcat-flash-d4`` as the benchmark
    runs it, at both of its row counts (four shortcut-connected layers of
    two latent-attention sublayers each, 16 of 512 experts behind a
    router of 768 outputs, 4608 blocks of 64 latent rows in each of eight
    sublayers, 48 sequences a step, tables of 160 blocks): the grouped
    kernel's three projections a layer and the latent attention's two
    calls a sublayer (64 heads: tiles of one row and of 16) are the
    program's only Pallas calls; the latent pool rides the layer scan
    and is not copied whole; weights, pool and temporaries fit a 16 GB
    chip."""
    import json

    from benchmarks.lib.drivers.serve_latent_share import preset_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmarks/configs/longcat-flash-d4.json")) as f:
        cfg = preset_config(json.load(f))
    compiled, layer_bytes = _pstep_compiled(
        one_chip, cfg, False, T=rows, seqs=48, bs=64, mbs=160, blocks=4608)
    text = compiled.as_text()
    # four expert layers under a rolled scan: one body, three
    # projections, two sublayers of two attention calls, and where the
    # step holds a run long enough (``expand_from``: 224 rows) the
    # expanded form's
    calls = 2 + (rows == 512)
    assert text.count("tpu_custom_call") == 3 + 2 * calls
    assert len(re.findall(r"%latent_attention_h(1|16)[\w.]* = ", text)) == 4
    assert len(re.findall(r"%latent_attention_h512[\w.]* = ",
                          text)) == 2 * (rows == 512)
    assert layer_bytes == 4609 * 64 * 640 * 2
    moved = [m for m in _moves_of(text, layer_bytes)
             if "dynamic-update-slice" not in m and "fusion" not in m]
    assert moved == [], moved
    mem = compiled.memory_analysis()
    print("longcat-flash-d4 step:", rows, mem.argument_size_in_bytes,
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _docqa_kernels(order):
    """``serve-mla-docqa``'s step lowered at the row counts of ``order``,
    one behind another → {rows: [(sha256 of a kernel's serialised
    module, the files its locations name), a kernel]}.  For a process of
    its own: what it reads depends on what the process traced before."""
    import hashlib
    import json

    from benchmarks.lib.drivers.serve_latent_share import preset_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmarks/configs/longcat-flash-d4.json")) as f:
        cfg = preset_config(json.load(f))
    return {rows: [(hashlib.sha256(body.encode()).hexdigest(),
                    _kernel_source_files(body))
                   for body in _kernel_bodies(_pstep_lowered(
                       None, cfg, False, T=rows, seqs=48, bs=64, mbs=160,
                       blocks=4608)[0])] for rows in order}


# a process that lowers the step at the row counts of its arguments, in
# their order; ``--shallow``: after tracing jnp's jitted helpers for a
# scalar from its own top level, as a first caller of a few frames would
_DOCQA_KERNELS = """
import json, sys
sys.path[:0] = [{root!r}, {tests!r}]
import jax, jax.numpy as jnp
jax.default_backend = lambda: "tpu"     # the kernels' compiled path
if "--shallow" in sys.argv:
    jax.make_jaxpr(lambda a: (a // 512, a % 2, jnp.where(a > 0, a, 0),
                              jnp.maximum(a, 1)))(jnp.int32(3))
import test_tpu_compile
print(json.dumps(test_tpu_compile._docqa_kernels(
    [int(a) for a in sys.argv[1:] if a.isdigit()])))
"""


def test_latent_step_programs_do_not_depend_on_who_traced_first():
    """``serve-mla-docqa``'s step at both of its row counts (the three
    latent cells share the kernels' code; this one's set-up showed it),
    lowered in either order, each order in a process of its own (what a
    kernel's module holds must not depend on what a worker ran before
    this test either): the kernels' serialised modules are the same,
    byte for byte, and the latent kernels name this package's files
    alone.  jnp keeps a jitted function's traced body (``where``, and
    ``//`` and ``%`` through it) as its first caller left it, locations
    and all; the two row counts reach the folded calls by different
    paths (``latent_attend_tiles``, ``latent_attend_runs``) and an
    engine traces them on threads side by side, so a kernel that held
    such a body would give its program another key in the compile cache
    from one run to the next, and a warm set-up would compile it again
    (PERF.md section 6, PR 58)."""
    import json
    import subprocess
    import sys

    tests = os.path.dirname(os.path.abspath(__file__))
    code = _DOCQA_KERNELS.format(root=os.path.dirname(tests), tests=tests)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", code, *order],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env)
             for order in (("128", "512"), ("--shallow", "512", "128"))]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (_, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
    one, other = (json.loads(out.strip().splitlines()[-1])
                  for out, _ in outs)
    assert (len(one["128"]), len(one["512"])) == (3 + 2 * 2, 3 + 2 * 3)
    assert one == other
    latent = [files for kernels in one.values() for _, files in kernels
              if any(f.endswith("/deepspeed_tpu/ops/mla.py") for f in files)]
    assert len(latent) == 2 * 2 + 2 * 3
    assert all("/deepspeed_tpu/" in f for files in latent for f in files), \
        latent


@pytest.mark.parametrize("rows", [128, 512])
def test_latent_groups_serving_step_compiles_and_fits(one_chip, on_chip,
                                                      rows):
    """The whole serving step of ``deepseek-v2-d5`` as the benchmark runs
    it, at both of its row counts (a dense layer and four expert layers of
    latent attention at 128 heads, 40 of 160 experts in two device groups
    beside a shared MLP, 9216 blocks of 64 latent rows in each of five
    layers, 24 sequences a step, tables of 272 blocks): the grouped
    kernel's three projections an expert layer and the latent attention's
    two calls a layer (128 heads: tiles of one row and of 8) are the
    program's only Pallas calls; the latent pool rides the layer scan and
    is not copied whole; weights, pool and temporaries fit a 16 GB chip."""
    import json

    from benchmarks.lib.drivers.serve_latent_groups import preset_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmarks/configs/deepseek-v2-d5.json")) as f:
        cfg = preset_config(json.load(f))
    compiled, layer_bytes = _pstep_compiled(
        one_chip, cfg, False, T=rows, seqs=24, bs=64, mbs=272, blocks=9216)
    text = compiled.as_text()
    # the leading layer outside the scan and ONE body for the four
    # expert layers: two attention calls each, three projections in the
    # body
    # (and at 512 rows the expanded form's call for a run long enough)
    calls = 2 + (rows == 512)
    assert text.count("tpu_custom_call") == calls + calls + 3
    assert len(re.findall(r"%latent_attention_h(1|8)[\w.]* = ", text)) == 4
    assert len(re.findall(r"%latent_attention_h512[\w.]* = ",
                          text)) == 2 * (rows == 512)
    assert layer_bytes == 9217 * 64 * 640 * 2
    moved = [m for m in _moves_of(text, layer_bytes)
             if "dynamic-update-slice" not in m and "fusion" not in m]
    assert moved == [], moved
    mem = compiled.memory_analysis()
    print("deepseek-v2-d5 step:", rows, mem.argument_size_in_bytes,
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 14.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9


def test_moe_serving_step_compiles_and_fits(one_chip, on_chip):
    """The whole serving step of ``olmoe-1b-7b-d10`` as the benchmark
    runs it (10 layers, 768 blocks of 64, 512 tokens and 64 sequences a
    step): the paged kernel at one query head per kv head and the
    grouped kernel's three projections are in the layer scan; no layer's
    experts are sliced out of the stacked weights on their way into the
    kernel (each of the three leaves was a 268 MB copy a layer while
    they were scanned inputs); weights, pool and temporaries fit a 16 GB
    chip."""
    from deepspeed_tpu.models.presets import build_config

    cfg = build_config("olmoe-1b-7b", num_layers=10, max_seq_len=1024)
    compiled, layer_bytes = _pstep_compiled(
        one_chip, cfg, False, T=512, seqs=64, bs=64, mbs=16, blocks=768)
    text = compiled.as_text()
    # the paged kernel at its two heights, the grouped kernel's three
    assert text.count("tpu_custom_call") == 5
    # 16 kv heads: a block is 512 KB and eight fill the group's budget
    _tile_grid_conditions(compiled.jaxpr(), T=512, seqs=64, mbs=16,
                          groups=(8, 8))
    leaf_bytes = cfg.num_experts * cfg.d_model * cfg.d_ff * 2
    assert _moves_of(text, 1), "the reader no longer finds any copy"
    assert _moves_of(text, leaf_bytes) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < leaf_bytes // 8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_window_serving_step_compiles_fits_and_names_its_kernels(
        one_chip, on_chip):
    """The whole serving step of ``trinity-mini-d5`` as the benchmark runs
    it (a dense layer and a period of three window layers and a full one,
    6144 blocks of 64, 512 tokens and 64 sequences a step, tables of 192
    blocks): the dense layer's window kernel outside the scan, the
    period's three window calls, its full call and the grouped kernel's
    projections inside it; weights, pool and temporaries fit a 16 GB chip;
    and every Pallas call's JAX path is one that the configuration file's
    ``trace_groups`` keys name, the window layers' apart from the full
    layer's."""
    import json
    import re

    from benchmarks.lib.drivers.serve_routed import preset_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmarks/configs/trinity-mini-d5.json")) as f:
        config = json.load(f)
    cfg = preset_config(config)
    compiled, _ = _pstep_compiled(one_chip, cfg, False, T=512, seqs=64,
                                  bs=64, mbs=192, blocks=6144)
    text = compiled.as_text()
    paths = set(re.findall(r'op_name="([^"]*pallas_call)"', text))
    groups = {}
    for p in paths:
        tail = next(k.partition("@")[0] for k in config["trace_groups"]
                    if k.partition("@")[0] and p.endswith(k.partition("@")[0]))
        groups.setdefault(tail, set()).add(p)
    window = {t for t in groups if t.startswith("paged_attention_w_")}
    assert window == {"paged_attention_w_h8/pallas_call",
                      "paged_attention_w_h128/pallas_call"}
    # under their scope, outside the scan (the dense layer) and inside it
    for t in window:
        assert all("/attn/attn_window/" in p for p in groups[t])
        assert {"while/body" in p for p in groups[t]} == {True, False}
    assert all("attn_window" not in p and "/attn/paged_attention_h" in p
               for p in groups["pallas_call"] if "paged_attention" in p)
    # window: 2 heights x (1 dense layer + 3 in the period); full: 2;
    # grouped kernel: 3 projections x 4 expert layers
    assert text.count("tpu_custom_call") == 8 + 2 + 12
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert mem.temp_size_in_bytes < 256e6


@pytest.mark.parametrize("model,kv_quant", [
    ("mistral-7b", False), ("mistral-7b", True), ("gpt2", False)],
    ids=["mistral-7b-d16-bf16", "mistral-7b-d16-int8kv", "gpt2-bf16"])
def test_serving_step_keeps_the_pool_in_place(one_chip, on_chip, model,
                                              kv_quant):
    """The layer scan carries the paged cache and the kernels address
    ``(layer, block)`` in it: the compiled step holds no temporary of a
    layer's share of the pool (it held the whole pool, 4.0 GiB, while
    the cache was a scanned input and output), and neither its entry
    computation nor its ``while`` body copies, slices or update-slices
    that much.  So for an int8 cache's scales, a whole memory tile a
    block where they lie (their relayout was 76.8 MB a layer a step
    while the heads were their innermost axis), and for gpt2's pool,
    whose 12 x 64 slab is allocated as 16 x 128 (as 12 x 64 the TPU's
    compiler keeps the block axis innermost and every kernel call took
    a copy of the whole stack relaid)."""
    from deepspeed_tpu.models.presets import build_config

    compiled, layer_bytes = _pstep_compiled(
        one_chip, build_config(model, num_layers=16 if model != "gpt2"
                               else 12), kv_quant,
        T=512, seqs=64, bs=64, mbs=16, blocks=1024)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _moves_of(text, 1), "the reader no longer finds any copy"
    assert _moves_of(text, layer_bytes) == []
    if kv_quant:
        # nor a layer's share of the scales (8 x 128 a block) relaid,
        # cut or padded.  (XLA's memory-space assignment may still take
        # a stack of scales small enough for VMEM there and back around
        # the write's scatter: a pair of ``copy-start``/``copy-done``.)
        assert [m for m in _moves_of(text, 1025 * 8 * 128 * 4)
                if m.endswith(",8,128]")
                and not m.startswith(("copy-start", "copy-done"))] == []
    # the queries reach the kernel in batch order and the output leaves
    # it so: at most a relaid ``q`` and output (4 MB each at these
    # sizes), never a copy of them padded to tiles (68 tiles of 128 rows
    # would be 71 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
    # (gpt2's sixteen heads a block: 512 KB, eight blocks a group)
    _tile_grid_conditions(compiled.jaxpr(), T=512, seqs=64, mbs=16,
                          groups=(8, 8) if model == "gpt2" else (16, 16))


@pytest.mark.parametrize("rows", [128, 512])
def test_serving_step_reads_the_attention_projections_where_they_lie(
        one_chip, on_chip, rows):
    """Both rungs of ``serve-decode``'s step (128 and 512 rows): the
    engine holds ``wq``/``wk``/``wv`` as ``[L, d, H*D]`` and ``wo`` as
    ``[L, H*D, d]``, and the product's rows are reshaped into heads
    behind a barrier, so neither program cuts a layer's projection out
    of the stack into a temporary (``constant_dynamic-slice_fusion``) or
    transposes it (``copy``; 33.5 and 8.4 MB a layer a step at the
    parent of PR 45): the products read the stack as the MLP's do."""
    from deepspeed_tpu.models.presets import build_config

    cfg = build_config("mistral-7b", num_layers=16)
    compiled, _ = _pstep_compiled(one_chip, cfg, False, T=rows, seqs=64,
                                  bs=64, mbs=16, blocks=1024)
    text = compiled.as_text()
    assert _moves_of(text, 1), "the reader no longer finds any copy"
    # no bf16 array as large as a layer's ``wk`` (8.4 MB) is copied, cut
    # out or written back outside a fusion (a float32 ``q`` of 512 rows,
    # as large, is relaid once a layer)
    assert [m for m in _moves_of(
        text, cfg.d_model * cfg.num_kv_heads * cfg.head_dim * 2)
        if ": bf16[" in m] == []


# ------------------------------------------------ ZeRO-3 over four chips
def _zero3_step_compiled(topo, monkeypatch, layers, per_chip, seq):
    """The train step ``benchmarks/lib/drivers/train.py`` builds for
    ``train-zero3-4chip`` (pythia-1.4b widths, ``fsdp=4``, stage 3, bf16,
    each layer recomputed), compiled for the four described chips.  There
    is no device to hold a train state, so the engine is built over
    shapes: its state initialiser and the one ``device_put`` of its
    constructor are replaced here, in the test."""
    from deepspeed_tpu.comm import MeshTopology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.config.config import load_config
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.models.transformer import (_resolve_attention,
                                                  init_params, lm_loss_fn)
    from deepspeed_tpu.runtime.engine import Engine, TrainState

    cfg = build_config("pythia-1.4b", num_layers=layers, remat=True,
                       remat_policy="nothing")
    mesh = MeshTopology.build(MeshConfig(fsdp=4), devices=topo.devices)

    def shaped(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    def abstract_state(self, params):
        def init(p):
            master = jax.tree.map(lambda x: x.astype(jnp.float32), p)
            return master, self.optimizer.init(master)
        master, opt = jax.eval_shape(init, params)
        self.opt_shardings = self._opt_state_shardings(opt, master)
        scalar = lambda x: jax.ShapeDtypeStruct((), x.dtype,
                                                sharding=self.repl)
        return TrainState(
            step=scalar(jnp.zeros((), jnp.int32)),
            master=shaped(master, self.master_shardings),
            opt_state=shaped(opt, self.opt_shardings),
            loss_scale=jax.tree.map(scalar,
                                    jax.eval_shape(self.scaler.init)),
            skipped=scalar(jnp.zeros((), jnp.int32)))

    axes = {}

    def make(key):
        params, a = init_params(cfg, key)
        axes.update(a)
        return params

    params = jax.eval_shape(make, jax.random.PRNGKey(0))
    with monkeypatch.context() as m:
        m.setattr(Engine, "_init_state", abstract_state)
        m.setattr(jax, "device_put", lambda tree, sh: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree))
        eng = Engine(
            loss_fn=lm_loss_fn(cfg, _resolve_attention(cfg)), params=params,
            param_axes=axes, topology=mesh, config=load_config({
                "train_micro_batch_size_per_device": per_chip,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "gradient_clipping": 1.0, "steps_per_print": 1 << 30}))
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (per_chip * 4, seq), jnp.int32, sharding=eng.batch_sharding)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=eng.repl)
    return eng, cfg, eng._pick_train_step().lower(
        eng.state, batch, rng).compile()


@pytest.mark.parametrize("form", ["rolled", "unrolled"])
def test_zero3_step_gathers_parameters_and_fits(topo, on_chip, monkeypatch,
                                                form):
    """``train-zero3-4chip``'s step at 2 of 24 layers, with the layer scan
    rolled as the cell's 24 layers run it (the ceiling patched under the
    two) and unrolled as a model under the ceiling runs it; "inside the
    layer loop" is under the scan's scope in both.  What must hold by
    kind: no all-to-all (q/k/v resharded for the partial rotary, the
    logits resharded from vocabulary to batch); inside the layer loop no
    collective carries the batch (every projection all-gathered the whole
    batch's residual stream, 134 MB, before PR 29); each layer's weights
    arrive by all-gather in bf16; the weight gradients leave by
    reduce-scatter, which the TPU's compiler writes as a ring of
    collective-permutes of a quarter of the weight inside the backward
    matmul; the program's temporaries stay a third of what they were
    (6.04 GB at these sizes while each chip ran all 16 sequences)."""
    per_chip, seq = 4, 2048
    if form == "rolled":
        from deepspeed_tpu.models import transformer
        monkeypatch.setattr(transformer, "UNROLL_MAX_LAYERS", 0)
    eng, cfg, compiled = _zero3_step_compiled(topo, monkeypatch, 2,
                                              per_chip, seq)
    assert (" while(" in compiled.as_text()) == (form == "rolled")
    from tests.test_zero3_placement import collectives_of, in_layers
    # one entry a channel: XLA repeats an async collective's text
    found = {(kind, ch): (shapes, op) for kind, shapes, op, ch
             in collectives_of(compiled.as_text()) if ch}
    kinds = [k for k, _ in found]
    assert "all-to-all" not in kinds

    def carries_batch(shape):
        return len(shape) >= 3 and shape[0] in (per_chip, per_chip * 4) \
            and shape[1] == seq

    in_loop = {k: v for k, v in found.items() if in_layers(v[1])}
    moved = [(k, s, op) for k, (shapes, op) in found.items()
             for s in shapes if carries_batch(s)]
    # the one exception lies outside the loop: the gradient of the
    # vocabulary-sharded embedding table gathers the batch's cotangent
    # (134 MB) instead of reduce-scattering a table (206 MB)
    assert all("scatter-add" in op and not in_layers(op)
               for _, _, op in moved), moved
    assert len(moved) <= 1
    H, D, dm, ff = cfg.num_heads, cfg.head_dim, cfg.d_model, cfg.d_ff
    weights = {(dm, H, D), (H, D, dm), (dm, ff), (ff, dm)}
    # the forward gathers a slice of the stack, [1, ...]; the
    # recomputation gathers the slice the scan already cut
    gathered = {s[1:] if s[0] == 1 else s
                for (k, _), (shapes, _) in in_loop.items()
                if k == "all-gather" for s in shapes}
    assert weights <= gathered, gathered
    scattered = {s for (k, _), (shapes, op) in in_loop.items()
                 if k in ("reduce-scatter", "collective-permute")
                 and "transpose(jvp" in op for s in shapes if s}
    assert scattered, "no weight gradient leaves the loop reduced"
    quarters = {(dm // 4, H, D), (H, D, dm // 4), (dm, ff // 4),
                (ff // 4, dm)}
    assert scattered <= quarters | weights, scattered
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.6e9
    # 24 layers of state are 5.7 GB a chip: the whole cell fits 16 GB
    state = mem.argument_size_in_bytes * 24 / 2
    assert state + mem.temp_size_in_bytes < 15.75e9
    leaves, nbytes = eng._zero3_gather
    assert leaves == 8 and nbytes == 460_062_720
