"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives both halves of the framework through the entry points
a user calls, each at the full width of a model the repo ships, and
checks what comes out by the repo's own means:

* kernels  every Pallas entry point (flash attention fwd/bwd, paged
           decode attention bf16 and int8-KV, the int8 and packed-int4
           mixed GEMMs) once at GPT-2-small / llama3-8b widths against
           the XLA formulation it competes with, to bf16 tolerance;
           (``latent kernel``, a phase of its own: the latent layers'
           attention kernel at the two latent cells' shapes against
           ``ops/mla.py`` ``latent_attend``, each with its milliseconds);
* train    ``ds.initialize(model=build_model("gpt2"), ...)`` ->
           ``engine.train_batch`` on the whole GPT-2-small preset at
           seq 1024, bf16, ZeRO-1: loss finite on every step and falling
           on a repeated batch;
* serve    ``InferenceEngine`` on the llama3-8b preset at its published
           widths (depth cut, printed) with ``attn_impl="auto"``, the
           engine's own rule (the Pallas kernel on a TPU; its verdict is
           printed): ``generate`` in process, then the same engine
           behind an in-process ``Gateway`` answering
           ``POST /v1/completions`` over loopback.  First-token logits
           agree with a plain non-paged ``model.apply`` of the same
           weights; HTTP tokens equal the in-process ones;
* serve-int8  the same widths with int8 weights and ``mixed_gemm="on"``
           (the kernels phase times that kernel against XLA's dequant);
* serve-moe  ``InferenceEngine.generate`` on the olmoe-1b-7b preset at
           its published widths (64 experts, top-8, QK-norm, 16/16 heads;
           depth cut, printed): the grouped expert kernel against
           ``jax.lax.ragged_dot`` on its shapes, then first-token logits
           against the dropless ``model.apply`` of the same weights.

``--chips 4`` runs ONLY the sharded paths and what they are compared
with: ZeRO-3 over ``fsdp=4`` against a one-device topology (loss
parity), tensor-parallel serving over ``tensor=4`` against one chip
(token parity), and the proof that parameters, KV cache and memory are
really spread over four devices with collectives in the programs.

Without ``--rehearse`` any platform other than ``tpu`` is refused: the
script never sets ``JAX_PLATFORMS`` and never falls back.  ``--rehearse``
runs the same code at tiny sizes on whatever platform JAX reports
(kernels in interpret mode off-TPU); the last line then names that
platform, so a rehearsal cannot pass for a chip run.

Weights are random, from ``--seed``.  Every time printed here is an
observation on the device the last line names, not a benchmark result.
The last stdout line is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero without it.
"""
# tpulint: disable-file=print — the smoke's stdout IS its deliverable
# tpulint: disable-file=retrace-hazard — one jit per kernel case and
# implementation, built once and reused for that case's timed calls

import argparse
import collections
import contextlib
import gc
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

BF16_REL = 2e-2         # kernel vs XLA, relative to the reference's max
LOGIT_REL = 5e-2        # whole-model logits (8+ layers of bf16 rounding)
INT8_LOGIT_REL = 0.15   # int8 row-wise weights vs the dense forward
LOSS_REL = 2e-2         # ZeRO-3 on four devices vs one

REAL = dict(
    train=dict(overrides=dict(max_seq_len=1024, remat=False,
                              attention_impl="xla_flash"),
               seq=1024, batch=32, steps=5),
    serve=dict(overrides=dict(max_seq_len=512), layers=8, int8_layers=2,
               moe_layers=2, moe_overrides=dict(max_seq_len=512),
               token_budget=1024, max_seqs=8, block=64, blocks=128,
               prompt_lens=(311, 257, 203), new_tokens=16),
    flash={"gpt2": (4, 1024, 12, 12, 64), "llama3-8b": (1, 2048, 32, 8, 128)},
    paged=dict(T=1024, H=32, Hkv=8, D=128, block=64, blocks=128, nb=16),
    paged_long=dict(S=16, H=32, Hkv=4, D=128, block=64, blocks=2560, nb=192,
                    window=2048, ctx=(4100, 12200)),
    gemm=dict(K=4096, N=14336, Ms=(8, 1024)),
    # the one-token state update at the two cells' shapes (serve-ssm-chat,
    # serve-kda-reason): a 2 MiB row a slot a layer
    state=dict(L=2, S=128, H=32, ssm=(128, 256, 2), kda=(128, 128)),
    # the chunked form at the two Mamba-2 cells' shapes (serve-ssm-chat,
    # serve-ssm-moe-rag): heads, head width, groups, state, chunk; a
    # 512-row step that holds decode rows, a prompt's run and a short one
    scan=dict(T=512, S=64, runs=((63, 300, 5), (400, 40, 7)), cases={
        "falcon-h1": (32, 128, 2, 256, 128),
        "granite-4.0-h": (128, 64, 1, 128, 256)}),
    # latent attention at the two cells' shapes (serve-mla-docqa: 64
    # heads, 47 one-token rows at contexts of 1k-10k beside a run of 464
    # rows; serve-kda-reason: 32 heads, 127 rows at 0.5k-6k, a run of 64)
    latent=dict(row=(512, 64), block=64, cases={
        "docqa": dict(H=64, S=48, T=512, ctx=(1024, 10000), run=(464, 2500),
                      chunk=64),
        "reason": dict(H=32, S=128, T=512, ctx=(512, 6000), run=(64, 2300),
                       chunk=64)},
        # the expanded form's call: a chunk of 512 rows behind a shared
        # document (serve-mla-shared-docs) and behind a long prompt
        # (serve-mla-docqa)
        expanded={"shared-docs": dict(H=128, T=512, run=(512, 16384)),
                  "docqa": dict(H=64, T=512, run=(512, 4096))}),
    barrier=dict(n=8192, iters=64),
)
TINY = dict(
    train=dict(overrides=dict(max_seq_len=64, num_layers=2, d_model=64,
                              num_heads=4, vocab_size=512),
               seq=64, batch=4, steps=4),
    serve=dict(overrides=dict(max_seq_len=128, d_model=64, num_heads=4,
                              num_kv_heads=4, d_ff=128, vocab_size=512),
               layers=2, int8_layers=2, moe_layers=2,
               moe_overrides=dict(max_seq_len=128, d_model=64, num_heads=4,
                                  num_kv_heads=4, d_ff=32, vocab_size=512,
                                  num_experts=8, moe_top_k=4),
               token_budget=64, max_seqs=4,
               block=8, blocks=64, prompt_lens=(21, 17, 9), new_tokens=6),
    flash={"gpt2": (1, 128, 4, 4, 32), "llama3-8b": (1, 128, 4, 2, 32)},
    paged=dict(T=16, H=4, Hkv=2, D=32, block=8, blocks=16, nb=4),
    paged_long=dict(S=4, H=16, Hkv=2, D=32, block=8, blocks=96, nb=24,
                    window=32, ctx=(70, 190)),
    gemm=dict(K=256, N=512, Ms=(8, 32)),
    state=dict(L=2, S=4, H=4, ssm=(8, 128, 2), kda=(16, 128)),
    scan=dict(T=32, S=4, runs=((3, 19, 1), (24, 6, 3)), cases={
        "falcon-h1": (4, 128, 2, 16, 8), "granite-4.0-h": (4, 64, 1, 16, 8)}),
    latent=dict(row=(96, 16), block=8, cases={
        "docqa": dict(H=4, S=4, T=32, ctx=(9, 70), run=(21, 30), chunk=8)},
        expanded={"docqa": dict(H=4, T=32, run=(21, 30))}),
    barrier=dict(n=256, iters=8),
)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def close(name, got, ref, rel):
    """max|got - ref| <= rel * max|ref|, everything finite; prints the
    measured ratio either way."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), f"{name}: non-finite values")
    check(got.shape == ref.shape, f"{name}: shape {got.shape} vs {ref.shape}")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    ok = err <= rel * scale
    print(f"    {name}: max|d|={err:.4g} of max|ref|={scale:.4g} "
          f"(ratio {err / max(scale, 1e-30):.3g}, bound {rel}) "
          f"{'ok' if ok else 'FAIL'}")
    check(ok, f"{name}: differs from its reference beyond {rel}")


# --------------------------------------------------------------------------
# compile accounting (jax.monitoring) and phases
# --------------------------------------------------------------------------

COMPILE = collections.Counter()


def _watch_compiles():
    import jax

    def on_duration(event, secs, **_):
        if event.endswith("backend_compile_duration"):
            COMPILE["compile_s"] += secs
        elif event.endswith("cache_retrieval_time_sec"):
            COMPILE["cache_read_s"] += secs

    def on_event(event, **_):
        if event.endswith("cache_hits"):
            COMPILE["cache_hits"] += 1
        elif event.endswith("cache_misses"):
            COMPILE["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def peak_bytes():
    """``memory_stats()["peak_bytes_in_use"]`` of the first device (0
    where the backend reports none), through the platform layer."""
    from deepspeed_tpu.platform import get_platform
    return get_platform().max_memory_allocated()


FAILED = []


@contextlib.contextmanager
def phase(name):
    """One named phase: prints its wall and compile seconds and the
    device's peak bytes; a failure is recorded and the run goes on, so
    one chip call reports every phase."""
    print(f"[{name}]", flush=True)
    t0, c0 = time.perf_counter(), COMPILE["compile_s"]
    try:
        yield
    except Exception:
        traceback.print_exc()
        FAILED.append(name)
        print(f"[{name}] FAILED", flush=True)
    gc.collect()
    print(f"[{name}] {time.perf_counter() - t0:.1f} s "
          f"(compile {COMPILE['compile_s'] - c0:.1f} s), "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)


def timed(fn, *args, reps=5):
    """(result, seconds per call) after one untimed compile+settle call."""
    import jax
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return out, (time.perf_counter() - t0) / reps


# --------------------------------------------------------------------------
# device behaviour the measurements of later PRs rest on
# --------------------------------------------------------------------------

def barrier_phase(sz):
    """Is ``block_until_ready`` a completion barrier here?  A matmul chain
    long enough to time: if the barrier is real, the wait takes the
    compute time and the value fetch after it takes next to nothing."""
    import jax
    import jax.numpy as jnp

    n, iters = sz["barrier"]["n"], sz["barrier"]["iters"]
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, iters, lambda _, c: c @ x, x)

    float(chain(x)[0, 0])                       # compile + settle
    t0 = time.perf_counter()
    float(chain(x)[0, 0])
    fetch_only = time.perf_counter() - t0
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    real = (t3 - t2) < 0.1 * (t2 - t0) and (t2 - t0) > 0.5 * fetch_only
    print(f"    dispatch returned after {1e3 * (t1 - t0):.2f} ms, "
          f"block_until_ready after {1e3 * (t2 - t0):.2f} ms, value fetch "
          f"took a further {1e3 * (t3 - t2):.2f} ms; dispatch+fetch alone "
          f"{1e3 * fetch_only:.2f} ms -> block_until_ready is "
          f"{'a real barrier' if real else 'NOT a barrier'}")


def profiler_phase(sz):
    """A short profiler window: do device events carry the kernel and
    ``jax.named_scope`` names every later per-layer metric is read by?"""
    import glob

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.mixed_gemm import mixed_matmul
    from deepspeed_tpu.ops.quant import quantize_rowwise
    from tools.tracemerge import load_device_events

    K, N = sz["gemm"]["K"], sz["gemm"]["N"]
    w = quantize_rowwise(jax.random.normal(jax.random.PRNGKey(0), (K, N)))
    x = jnp.ones((8, K), jnp.bfloat16)

    @jax.jit
    def step(x, w):
        with jax.named_scope("smoke_scope_gemm"):
            return mixed_matmul(x, w)

    jax.block_until_ready(step(x, w))
    # the named scope around the call, and the kernel's jitted entry
    # point (the name its custom call carries in the HLO text)
    scopes = ("smoke_scope_gemm", "mixed_matmul_2d")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as out:
        with jax.profiler.trace(out):
            for _ in range(3):
                jax.block_until_ready(step(x, w))
        pb = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
        check(pb, "the profiler wrote no xplane")
        planes = list(jax.profiler.ProfileData.from_file(pb[-1]).planes)
        dev = [p for p in planes if p.name.startswith("/device:")]
        names = collections.Counter(
            ev.name for p in dev for ln in p.lines for ev in ln.events)
        by_name = sum(c for n, c in names.items()
                      if any(s in n for s in scopes))
        by_op = sum(
            1 for e in load_device_events(out, 0)
            if isinstance(e, dict) and e.get("ph") == "X"
            and any(s in str((e.get("args") or {}).get("op_name", ""))
                    for s in scopes))
        print(f"    planes={[p.name for p in planes]}")
        print(f"    device events={sum(names.values())}, carrying "
              f"{scopes}: by event name {by_name}, by args.op_name after "
              f"tools/tracemerge {by_op}; most common device event names "
              f"{[n for n, _ in names.most_common(6)]}")
        if jax.devices()[0].platform == "tpu":
            check(names, "no operation showed on a device plane")


# --------------------------------------------------------------------------
# kernels vs the XLA formulations they compete with
# --------------------------------------------------------------------------

def kernels_phase(sz, seed):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.model import (_paged_attention,
                                               _paged_attention_pallas,
                                               _pool_scales,
                                               _quantize_kv)
    from deepspeed_tpu.inference.ragged.state import RaggedBatch
    from deepspeed_tpu.models.layers import causal_attention
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.ops.mixed_gemm import (dequant_matmul_reference,
                                              mixed_matmul)
    from deepspeed_tpu.ops.quant import quantize_rowwise, quantize_rowwise4

    k_flash, k_paged, k_gemm = jax.random.split(jax.random.PRNGKey(seed), 3)
    ms = lambda s: f"{1e3 * s:.3f} ms"    # noqa: E731

    # --- flash attention, forward and backward
    for name, (B, S, H, Hkv, D) in sz["flash"].items():
        kq, kk, kv, kw = jax.random.split(jax.random.fold_in(k_flash, S), 4)
        q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, S, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, S, Hkv, D), jnp.bfloat16)
        w = jax.random.normal(kw, (B, S, H, D), jnp.float32)
        print(f"  flash attention {name} B{B} S{S} H{H}/{Hkv} D{D}")
        outs, grads = {}, {}
        for impl, fn in (("pallas", flash_attention),
                         ("xla", causal_attention)):
            # w is an ARGUMENT: closed over, it is baked into the
            # executable as a constant (a 114 MB compile-cache entry)
            def loss(q, k, v, w, _fn=fn):
                return (_fn(q, k, v).astype(jnp.float32) * w).sum()
            outs[impl], t_f = timed(jax.jit(fn), q, k, v)
            grads[impl], t_b = timed(
                jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, k, v, w)
            print(f"    {impl}: fwd {ms(t_f)}, fwd+bwd {ms(t_b)}")
        close("fwd", outs["pallas"], outs["xla"], BF16_REL)
        for n, a, b in zip("qkv", grads["pallas"], grads["xla"]):
            close(f"d{n}", a, b, BF16_REL)

    # --- paged attention over a full block table, on a batch as the
    # scheduler stages them: two decode tokens and a two-token verify
    # window (the kernel's short tiles), a chunk of T/4 rows that starts
    # in the middle of a block and one of T/2 rows that ends at the
    # context's end (its long tiles), the rest budget padding
    c = sz["paged"]
    T, H, Hkv, D, bs, nb = (c[k] for k in ("T", "H", "Hkv", "D", "block",
                                            "nb"))
    S = 8
    k_short, k_long, k_state = jax.random.split(k_paged, 3)
    kq, kc = jax.random.split(k_short)
    q = jax.random.normal(kq, (T, H, D), jnp.bfloat16)
    cache = jax.random.normal(kc, (c["blocks"] + 1, bs, 2, Hkv, D),
                              jnp.bfloat16)
    r = np.random.RandomState(seed)
    tables = np.stack([r.permutation(c["blocks"])[:nb] for _ in range(S)])
    ctx = nb * bs
    runs = [(int(r.randint(0, ctx)), 1), (int(r.randint(0, ctx)), 1),
            (int(r.randint(0, ctx - 2)), 2),
            (min(bs // 2 + 5, ctx - T // 4), T // 4),
            (ctx - T // 2 - 3, T // 2)]
    n_real = sum(n for _, n in runs)
    positions, seq_slot = np.zeros(T, np.int32), np.zeros(T, np.int32)
    row = 0
    for slot, (start, n) in enumerate(runs):
        positions[row:row + n] = np.arange(start, start + n)
        seq_slot[row:row + n] = slot
        row += n
    batch = RaggedBatch(
        token_ids=jnp.zeros(T, jnp.int32),
        positions=jnp.asarray(positions), seq_slot=jnp.asarray(seq_slot),
        token_valid=jnp.asarray(np.arange(T) < n_real),
        block_tables=jnp.asarray(tables, jnp.int32),
        context_lens=jnp.zeros(S, jnp.int32),
        logits_idx=jnp.full(S, -1, jnp.int32), n_tokens=n_real,
        n_seqs=len(runs))
    def quantized(cache):
        """``cache`` as an int8 pool: codes, and scales as the pool
        lays them (a head a row of a block's)."""
        codes, scales = _quantize_kv(cache, jnp.int8)
        return codes, _pool_scales(scales)

    for name, kvl in (("bf16", cache), ("int8-KV", quantized(cache))):
        print(f"  paged attention {name} T{T} H{H}/{Hkv} D{D} "
              f"block {bs} x{nb}")
        got = {}
        for impl, fn in (("pallas", _paged_attention_pallas),
                         ("xla", _paged_attention)):
            got[impl], t = timed(
                jax.jit(lambda kvl, q, _fn=fn: _fn(kvl, q, batch, bs, nb,
                                                   D ** -0.5)), kvl, q)
            print(f"    {impl}: {ms(t)}")
        close("out", got["pallas"][:n_real], got["xla"][:n_real], BF16_REL)
        check(not np.asarray(got["pallas"][n_real:], np.float32).any(),
              "paged attention wrote into budget padding")

    # --- the same at long contexts: one decode token a sequence, eight
    # query heads a kv head, contexts past 4k (dozens of the short
    # call's groups of KV blocks a tile), a full layer and a window layer
    c = sz["paged_long"]
    S, H, Hkv, D, bs, nb = (c[k] for k in ("S", "H", "Hkv", "D", "block",
                                            "nb"))
    kq, kc = jax.random.split(k_long)
    q = jax.random.normal(kq, (S, H, D), jnp.bfloat16)
    cache = jax.random.normal(kc, (c["blocks"] + 1, bs, 2, Hkv, D),
                              jnp.bfloat16)
    ctx = r.randint(*c["ctx"], size=S)
    need = ctx // bs + 1
    check(need.sum() <= c["blocks"] and need.max() <= nb,
          "the long-context sample does not fit its pool")
    order, tables = r.permutation(c["blocks"]), np.full((S, nb), -1)
    for i, n in enumerate(need):
        tables[i, :n] = order[need[:i].sum():need[:i].sum() + n]
    batch = RaggedBatch(
        token_ids=jnp.zeros(S, jnp.int32),
        positions=jnp.asarray(ctx, jnp.int32),
        seq_slot=jnp.arange(S, dtype=jnp.int32),
        token_valid=jnp.ones(S, bool),
        block_tables=jnp.asarray(tables, jnp.int32),
        context_lens=jnp.zeros(S, jnp.int32),
        logits_idx=jnp.full(S, -1, jnp.int32), n_tokens=S, n_seqs=S)
    for name, kvl in (("bf16", cache),
                      ("int8-KV", quantized(cache))):
        for window in (None, c["window"]):
            print(f"  paged attention {name} decode x{S} H{H}/{Hkv} D{D} "
                  f"contexts {ctx.min()}-{ctx.max()} "
                  f"{'full' if window is None else f'window {window}'}")
            got = {}
            for impl, fn in (("pallas", _paged_attention_pallas),
                             ("xla", _paged_attention)):
                got[impl], t = timed(jax.jit(
                    lambda kvl, q, _fn=fn, _w=window: _fn(
                        kvl, q, batch, bs, nb, D ** -0.5, window=_w)),
                    kvl, q)
                print(f"    {impl}: {ms(t)}")
            close("out", got["pallas"], got["xla"], BF16_REL)

    # --- the recurrent mixers' one-token state update, a layer of the
    # stack in place against XLA's over the layer cut out of it; beside
    # each its rows read once and written once at the HBM's peak
    from deepspeed_tpu.ops import kda as kda_ops
    from deepspeed_tpu.ops import ssm as ssm_ops

    c = sz["state"]
    L, S, H = c["L"], c["S"], c["H"]
    ks = jax.random.split(k_state, 8)
    active = jnp.arange(S) != 1
    replay, fresh = jnp.arange(S) == 2, jnp.arange(S) == 3
    P, N, G = c["ssm"]
    dims = ssm_ops.SSMDims(H * P, H, P, G, N, 4, 128)
    ssm_args = (
        jax.random.normal(ks[0], (S, H, P), jnp.bfloat16),
        jax.random.normal(ks[1], (S, G, N), jnp.bfloat16),
        jax.random.normal(ks[2], (S, G, N), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(ks[3], (S, H)) - 3.0),
        -jnp.exp(jax.random.normal(ks[4], (H,))),
        jax.random.normal(ks[5], (H,)), active, replay, fresh, dims)
    Kd, V = c["kda"]
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    kda_args = (
        unit(jax.random.normal(ks[0], (S, H, Kd))),
        unit(jax.random.normal(ks[1], (S, H, Kd))),
        jax.random.normal(ks[2], (S, H, V)),
        -jax.nn.softplus(jax.random.normal(ks[3], (S, H, Kd))),
        jax.nn.sigmoid(jax.random.normal(ks[4], (S, H))),
        active, replay, fresh)
    for name, ops, tile, store, args in (
            ("ssm_state_update", ssm_ops, (P, N), jnp.bfloat16, ssm_args),
            ("kda_state_update", kda_ops, (Kd, V), jnp.float32, kda_args)):
        stack = 0.1 * jax.random.normal(ks[6], (L, S + 1, H) + tile, store)
        moved = 2 * S * H * tile[0] * tile[1] * stack.dtype.itemsize
        print(f"  {name} x{S} H{H} {list(tile)} {stack.dtype.name}: "
              f"{moved / 1e6:.0f} MB read and written, "
              f"{moved / 819e9 * 1e3:.3f} ms at 819 GB/s")

        def xla(stack, li, _ops=ops, _args=args):
            pool = jax.lax.dynamic_index_in_dim(stack, li, keepdims=False)
            out, new = _ops.state_update(pool[:S], *_args)
            return out, jax.lax.dynamic_update_slice(
                stack, new[None], (li, 0, 0, 0, 0))

        got = {}
        for impl, fn in (
                ("pallas", lambda st, li, _ops=ops, _args=args:
                 _ops.state_update_in_place(st, li, *_args)),
                ("xla", xla)):
            # the stack donated and carried from call to call, as the
            # served step carries it: without that each call copies it
            step = jax.jit(fn, donate_argnums=0)
            got[impl] = jax.block_until_ready(step(jnp.copy(stack), 1))
            st, t0 = jnp.copy(stack), time.perf_counter()
            for _ in range(10):
                _, st = step(st, 1)
            jax.block_until_ready(st)
            print(f"    {impl}: {ms((time.perf_counter() - t0) / 10)}")
        close("out", got["pallas"][0], got["xla"][0], 1e-4)
        close("rows", got["pallas"][1][1, :S], got["xla"][1][1, :S],
              2e-2 if store == jnp.bfloat16 else 1e-5)
        check(bool((got["pallas"][1][0] == stack[0]).all()
                   and (got["pallas"][1][1, S] == stack[1, S]).all()),
              f"{name} moved another layer's rows or the trash row")

    # --- the Mamba-2 chunked form over the chunks a step holds, ONE
    # kernel in place on the stack, against XLA's ``chunk_scan`` as the
    # serving forward composes it off the TPU
    c = sz["scan"]
    T, S = c["T"], c["S"]
    for name, (H, P, G, N, Q) in c["cases"].items():
        dims = ssm_ops.SSMDims(H * P, H, P, G, N, 4, Q)
        table = [(start + at, min(Q, n - at), slot, at == 0, at + Q >= n)
                 for start, n, slot in c["runs"] for at in range(0, n, Q)]
        NC = -(-T // Q) + 4
        chunks = jnp.asarray(table + [(0, 0, S, 1, 0)] * (NC - len(table)),
                             jnp.int32)
        fresh = jnp.arange(NC) == len(table) - 1     # the short run's
        ins = (jax.random.normal(ks[0], (T, H, P), jnp.bfloat16),
               0.3 * jax.random.normal(ks[1], (T, G, N), jnp.bfloat16),
               0.3 * jax.random.normal(ks[2], (T, G, N), jnp.bfloat16),
               jax.nn.softplus(jax.random.normal(ks[3], (T, H)) - 2.0),
               -jnp.exp(0.5 * jax.random.normal(ks[4], (H,))),
               jax.random.normal(ks[5], (H,)))
        stack = 0.5 * jax.random.normal(ks[6], (2, S + 1, H, P, N),
                                        jnp.bfloat16)
        print(f"  ssm_chunk_scan {name} H{H} [{P}, {N}] chunk {Q}: "
              f"{len(table)} of {NC} chunks hold "
              f"{sum(n for _, n, _ in c['runs'])} of {T} rows")

        def xla(stack, li, x, b, cc, dt, a, d, _dims=dims, _Q=Q):
            start, n, slot, first, last = (chunks[:, i] for i in range(5))
            q = jnp.arange(_Q)[None, :]
            there = q < n[:, None]
            rows = jnp.minimum(start[:, None] + q, T - 1)
            init = jnp.where(fresh[:, None, None, None], 0,
                             stack[li][slot].astype(jnp.float32))
            y_run, left = ssm_ops.chunk_scan(
                x[rows], b[rows], cc[rows],
                jnp.where(there[..., None], dt[rows], 0.0), a, d,
                first.astype(bool), init, _dims)
            to = jnp.where((n > 0) & (last != 0), slot, S)
            for i in range(NC):
                stack = stack.at[li, to[i]].set(left[i].astype(stack.dtype))
            y = jnp.zeros((T, H, P), jnp.float32).at[
                jnp.where(there, rows, T).reshape(-1)].set(
                y_run.reshape(-1, H, P), mode="drop")
            return y, stack

        got = {}
        for impl, fn in (
                ("pallas", lambda st, li, x, b, cc, *a, _dims=dims:
                 ssm_ops.chunk_scan_in_place(
                     st, li, jnp.concatenate(
                         [t.reshape(T, -1) for t in (x, b, cc)], axis=1),
                     *a, chunks, fresh, _dims)),
                ("xla", xla)):
            step = jax.jit(fn, donate_argnums=0)
            got[impl] = jax.block_until_ready(step(jnp.copy(stack), 1, *ins))
            st, t0 = jnp.copy(stack), time.perf_counter()
            for _ in range(10):
                _, st = step(st, 1, *ins)
            jax.block_until_ready(st)
            print(f"    {impl}: {ms((time.perf_counter() - t0) / 10)}")
        # (both feed the MXU what XLA's default precision feeds it, one
        # bfloat16 pass; a frame is not a chunk, so the roundings differ)
        close("out", got["pallas"][0], got["xla"][0], 1e-2)
        ends = [slot for _, _, slot in c["runs"]]
        close("rows", got["pallas"][1][1, jnp.asarray(ends)],
              got["xla"][1][1, jnp.asarray(ends)], 2e-2)
        check(bool((got["pallas"][1][0] == stack[0]).all()
                   and (got["pallas"][1][1, S] == stack[1, S]).all()),
              f"{name}: the chunk kernel moved another layer's rows or "
              "the trash row")

    # --- mixed-input GEMMs
    K, N = sz["gemm"]["K"], sz["gemm"]["N"]
    kw, kx = jax.random.split(k_gemm)
    wd = jax.random.normal(kw, (K, N), jnp.float32) / np.sqrt(K)
    for bits, qt in ((8, quantize_rowwise(wd)), (4, quantize_rowwise4(wd))):
        for M in sz["gemm"]["Ms"]:
            x = jax.random.normal(jax.random.fold_in(kx, M), (M, K),
                                  jnp.bfloat16)
            print(f"  mixed GEMM int{bits} {M}x{K}x{N}")
            y, t = timed(jax.jit(mixed_matmul), x, qt)
            ref, t_ref = timed(jax.jit(dequant_matmul_reference), x, qt)
            print(f"    pallas: {ms(t)}\n    xla dequant+matmul: {ms(t_ref)}")
            close("out", y, ref, BF16_REL)


def latent_kernel_phase(sz, seed):
    """The latent layers' attention by the Pallas kernel
    (``ops/mla.py`` ``latent_attend_tiles``) against the XLA formulation
    (``latent_attend``, called as ``inference/model.py`` calls it: the
    one-token rows a group a slot, the run by its chunks), on a step as
    the scheduler stages it, layer 1 of a pool of two; beside each the
    cached rows it reads at the HBM's peak."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import mla as A

    c = sz["latent"]
    (kv_rank, rope), bs = c["row"], c["block"]
    ms = lambda s: f"{1e3 * s:.3f} ms"    # noqa: E731
    for name, g in c["cases"].items():
        H, S, T = g["H"], g["S"], g["T"]
        dims = A.MLADims(heads=H, kv_rank=kv_rank, nope_dim=128,
                         rope_dim=rope, value_dim=128)
        rng = np.random.default_rng(seed + H)
        width = -(-dims.row // 128) * 128
        ctx = rng.integers(*g["ctx"], size=S - 1)
        n_run, seen = g["run"]
        ends = np.append(ctx, seen + n_run)         # rows cached behind the step
        nb = -(-int(ends.max()) // bs)
        need = -(-ends // bs)
        rows = int(need.sum()) + 1
        tables = np.full((S, nb), -1, np.int32)
        order = rng.permutation(rows - 1)
        at = 0
        for i, n in enumerate(need):
            tables[i, :n] = order[at:at + n]
            at += n
        k_pool, k_q = jax.random.split(jax.random.PRNGKey(seed + H))
        pool = jnp.zeros((2 * rows, bs, width), jnp.bfloat16).at[
            ..., :dims.row].set(jax.random.normal(
                k_pool, (2 * rows, bs, dims.row), jnp.bfloat16))
        q = (jax.random.normal(k_q, (T, H, dims.row), jnp.float32)
             * dims.row ** -0.25).astype(jnp.bfloat16)
        slot = np.zeros(T, np.int32)
        pos = np.zeros(T, np.int32)
        slot[:S - 1], pos[:S - 1] = np.arange(S - 1), ctx - 1
        slot[S - 1:S - 1 + n_run] = S - 1
        pos[S - 1:S - 1 + n_run] = seen + np.arange(n_run)
        valid = np.arange(T) < S - 1 + n_run
        layer = (rows, rows)
        read = (int(ctx.sum()) + seen + n_run) * width * 2
        pairs = int(ctx.sum()) + n_run * seen + n_run * (n_run + 1) // 2
        flops = 2 * pairs * H * (width + kv_rank)
        print(f"  latent attention {name} H{H} rows of {width}: {S - 1} "
              f"one-token rows at contexts {ctx.min()}-{ctx.max()}, a run of "
              f"{n_run} behind {seen}: {read / 1e6:.0f} MB of rows, "
              f"{read / 819e9 * 1e3:.3f} ms at 819 GB/s; {flops / 1e9:.0f} G "
              f"operations, {flops / 197e12 * 1e3:.3f} ms at 197 T/s")
        j = jnp.asarray

        def kernel(pool, q):
            tiles = A.latent_tiles(j(slot), j(pos), j(valid), j(tables), bs,
                                   nb, rows - 1, H)
            return A.latent_attend_tiles(pool, q, tiles, dims, layer)

        Q = g["chunk"]
        NC = -(-n_run // Q)
        crow = np.minimum(S - 1 + np.arange(NC * Q), T - 1).reshape(NC, Q)
        there = (np.arange(NC * Q) < n_run).reshape(NC, Q)
        lt = np.where(tables < 0, rows - 1, tables) + rows

        def xla(pool, q):
            one = A.latent_attend(pool, q[:S - 1][:, None],
                                  j(pos[:S - 1])[:, None], j(lt[:S - 1]),
                                  dims, 8)[:, 0]
            run = A.latent_attend(
                pool, q[j(crow)], j(np.where(there, pos[crow], -1)),
                j(np.broadcast_to(lt[S - 1], (NC, nb))), dims, 4)
            return jnp.concatenate([one, run.reshape(
                (NC * Q,) + run.shape[2:])[:n_run]])

        got, t_k = timed(jax.jit(kernel), pool, q)
        ref, t_x = timed(jax.jit(xla), pool, q)
        print(f"    pallas: {ms(t_k)}\n    xla: {ms(t_x)}")
        n = S - 1 + n_run
        close("out", got[:n], ref, BF16_REL)
        check(not np.asarray(got[n:], np.float32).any(),
              "latent attention wrote rows of no tile")
        # the decode rows alone: what a step without a prompt chunk runs
        only = valid & (np.arange(T) < S - 1)

        def decode(pool, q):
            tiles = A.latent_tiles(j(slot), j(pos), j(only), j(tables), bs,
                                   nb, rows - 1, H)
            return A.latent_attend_tiles(pool, q, tiles, dims, layer)

        rows_mb = int(ctx.sum()) * width * 2
        dec, t_d = timed(jax.jit(decode), pool, q)
        print(f"    pallas, the one-token rows alone: {ms(t_d)} "
              f"({rows_mb / 819e9 * 1e3:.3f} ms at 819 GB/s)")
        close("one-token rows", dec[:S - 1], ref[:S - 1], BF16_REL)

    # --- the expanded form's call beside the folded run call, one run
    for name, g in c["expanded"].items():
        H, T = g["H"], g["T"]
        n_run, seen = g["run"]
        dims = A.MLADims(heads=H, kv_rank=kv_rank, nope_dim=128,
                         rope_dim=rope, value_dim=128)
        width = -(-dims.row // 128) * 128
        nb = -(-(seen + n_run) // bs)
        rows = nb + 1
        rng = np.random.default_rng(seed + H)
        tables = rng.permutation(nb).astype(np.int32)[None]
        keys = jax.random.split(jax.random.PRNGKey(seed + H), 4)
        pool = jnp.zeros((2 * rows, bs, width), jnp.bfloat16).at[
            ..., :dims.row].set(jax.random.normal(
                keys[0], (2 * rows, bs, dims.row), jnp.bfloat16))
        q_n, q_r = ((jax.random.normal(k, (T, H, d), jnp.float32)
                     * dims.row ** -0.25).astype(jnp.bfloat16)
                    for k, d in ((keys[1], 128), (keys[2], rope)))
        ap = {"w_kvb": (jax.random.normal(keys[3], (kv_rank, H, 256),
                                          jnp.float32)
                        * kv_rank ** -0.5).astype(jnp.bfloat16)}
        pos = np.where(np.arange(T) < n_run, seen + np.arange(T), 0)
        valid = np.arange(T) < n_run
        layer = (rows, rows)
        pairs = n_run * seen + n_run * (n_run + 1) // 2
        least = 2 * H * (pairs * (128 + rope + 128)
                         + (seen + n_run) * kv_rank * 256)
        print(f"  latent attention, expanded, {name} H{H}: a run of {n_run} "
              f"behind {seen}: folded {2 * pairs * H * (width + kv_rank) / 1e9:.0f}"
              f" G operations, expanded {least / 1e9:.0f} G "
              f"({least / 197e12 * 1e3:.3f} ms at 197 T/s)")
        j = jnp.asarray

        def tiles_of(wide):
            return A.latent_tiles(j(np.zeros(T, np.int32)), j(pos), j(valid),
                                  j(tables), bs, nb, rows - 1, H, wide=wide)

        def folded(pool, q_n, q_r, ap):
            qf = A.fold_query(ap, q_n, q_r, dims)
            return A.unfold_output(ap, A.latent_attend_tiles(
                pool, qf, tiles_of(None), dims, layer), dims, jnp.bfloat16)

        def expanded(pool, q_n, q_r, ap):
            return A.latent_attend_expanded(
                pool, q_n, q_r, A.w_kvb(ap, dims),
                tiles_of((min(n_run, A.expand_from(dims)), A.WIDE)).wide,
                jnp.zeros((T, H, 128), jnp.bfloat16), dims, layer)

        Q = min(64, T)
        NC = -(-n_run // Q)
        crow = np.minimum(np.arange(NC * Q), T - 1).reshape(NC, Q)
        there = (np.arange(NC * Q) < n_run).reshape(NC, Q)
        lt = np.broadcast_to(tables + rows, (NC, nb))

        def xla(pool, q_n, q_r, ap):
            qf = A.fold_query(ap, q_n, q_r, dims)
            run = A.latent_attend(pool, qf[j(crow)],
                                  j(np.where(there, pos[crow], -1)), j(lt),
                                  dims, 4)
            return A.unfold_output(ap, run.reshape(
                (NC * Q,) + run.shape[2:])[:n_run], dims, jnp.bfloat16)

        ins = (pool, q_n, q_r, ap)
        got, t_e = timed(jax.jit(expanded), *ins)
        fold, t_f = timed(jax.jit(folded), *ins)
        ref, t_x = timed(jax.jit(xla), *ins)
        print(f"    pallas, expanded: {ms(t_e)}\n    pallas, folded (with "
              f"its two products around it): {ms(t_f)}\n    xla: {ms(t_x)}")
        close("expanded", got[:n_run], ref, BF16_REL)
        close("folded", fold[:n_run], ref, BF16_REL)
        check(not np.asarray(got[n_run:], np.float32).any(),
              "the expanded call wrote rows of no tile")


# --------------------------------------------------------------------------
# trainer
# --------------------------------------------------------------------------

def train_run(sz, seed, devices, mesh, stage, label):
    """A few steps of GPT-2-small on one repeated batch; returns
    (losses, engine) with the engine still holding its state."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm import MeshTopology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime.dataloader import synthetic_lm_data

    t = sz["train"]
    model = build_model("gpt2", seed=seed, **t["overrides"])
    topo = MeshTopology.build(MeshConfig(**mesh), devices=devices)
    engine = ds.initialize(model=model, topology=topo, config={
        "train_micro_batch_size_per_device": t["batch"] // len(devices),
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "seed": seed,
    })
    check(engine.train_batch_size == t["batch"],
          f"global batch {engine.train_batch_size} != {t['batch']}")
    batch = synthetic_lm_data(model.config.vocab_size, t["batch"], t["seq"],
                              seed=seed)
    losses, times = [], []
    for _ in range(t["steps"]):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)["loss"]))
        times.append(time.perf_counter() - t0)
    cfg = model.config
    print(f"  {label}: gpt2 L{cfg.num_layers} d{cfg.d_model} "
          f"vocab {cfg.vocab_size} seq {t['seq']} batch {t['batch']} bf16 "
          f"ZeRO-{stage} mesh {mesh}")
    print(f"    losses {[round(x, 4) for x in losses]}")
    print(f"    step seconds {[round(x, 3) for x in times]} "
          "(the first includes the compile)")
    check(np.isfinite(losses).all(), f"{label}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall on a repeated batch: {losses}")
    return losses, engine, batch


# --------------------------------------------------------------------------
# server
# --------------------------------------------------------------------------

def random_model(cfg, seed):
    """The preset's own initializer, cast to bf16 inside one jit so the
    fp32 tensors never all exist at once (a dense fp32 init of these
    widths does not fit beside its bf16 copy)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import Model, init_params

    axes = {}

    def init(key):
        params, axes["axes"] = init_params(cfg, key)
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    params = jax.jit(init)(jax.random.PRNGKey(seed))
    return Model.from_params(cfg, params, param_axes=axes["axes"])


def serve_config(sz, **kw):
    from deepspeed_tpu.inference import InferenceConfig

    s = sz["serve"]
    return InferenceConfig(token_budget=s["token_budget"],
                           max_seqs=s["max_seqs"], kv_block_size=s["block"],
                           num_kv_blocks=s["blocks"], attn_impl="auto",
                           **kw)


def make_prompts(sz, vocab, seed):
    r = np.random.RandomState(seed)
    return {uid: [int(t) for t in r.randint(0, vocab, n)]
            for uid, n in enumerate(sz["serve"]["prompt_lens"])}


def prefill_logits(eng, step, prompts):
    """Last-token logits of each prompt through the engine's paged
    prefill — the logits-returning sibling of the serving step
    (``InferenceEngine._build_step``), the idiom of
    tests/test_inference_tp.py.  Leaves the engine as it found it."""
    eng.state.reset_prefix_cache()      # a full prefill, not cache hits
    for uid, toks in prompts.items():
        eng.put(uid, toks)
    sched = eng._schedule()
    check(sorted(u for u, _ in sched) == sorted(prompts),
          "the prompts did not fit one prefill step")
    batch = eng._stage(eng.state.build_batch(sched, eng.icfg.token_budget))
    logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv, batch)
    rows = {uid: np.asarray(logits[eng.state.slot(uid)], np.float32)
            for uid in prompts}
    for uid in prompts:
        eng.flush(uid)
    return rows


def reference_logits(model, prompts):
    """Plain non-paged forward of the same parameters: prompts right-
    padded to one length (causal, so the padding changes nothing before
    it), logits read at each prompt's last token."""
    import jax
    import jax.numpy as jnp

    width = max(len(p) for p in prompts.values())
    ids = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts.values()):
        ids[i, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts.values()])

    @jax.jit
    def fwd(params, ids):
        logits = model.apply(params, ids)
        return logits[jnp.arange(len(prompts)), last].astype(jnp.float32)

    out = np.asarray(fwd(model.params, jnp.asarray(ids)))
    return dict(zip(prompts, out))


def report_path(eng):
    import jax

    print(f"    engine path: attn_impl {eng.icfg.attn_impl!r} -> "
          f"{eng.attn_impl!r} on backend {jax.default_backend()!r}, "
          f"mixed_gemm {eng.icfg.mixed_gemm!r}")


def check_first_tokens(label, tokens, logits, ref, rel):
    """Logits to tolerance; tokens wherever the reference's top-2 margin
    is wider than the measured logit difference could bridge."""
    for uid in tokens:
        close(f"{label} prompt {uid} first-token logits", logits[uid],
              ref[uid], rel)
        check(tokens[uid][0] == int(np.argmax(logits[uid])),
              f"{label}: prompt {uid}'s first token is not the argmax of "
              "the engine's own prefill logits")
        top2 = np.sort(ref[uid])[-2:]
        margin = float(top2[1] - top2[0])
        reach = 2 * float(np.abs(logits[uid] - ref[uid]).max())
        if margin > reach:
            check(tokens[uid][0] == int(np.argmax(ref[uid])),
                  f"{label}: prompt {uid}'s first token differs from the "
                  f"reference argmax at margin {margin:.4g} > {reach:.4g}")
            print(f"    prompt {uid}: first token {tokens[uid][0]} is the "
                  f"reference's argmax (top-2 margin {margin:.4g})")
        else:
            print(f"    prompt {uid}: top-2 margin {margin:.4g} is within "
                  f"twice the logit difference; token not compared")


def serve_phase(sz, seed):
    from deepspeed_tpu.gateway import GatewayConfig, spawn_gateway
    from deepspeed_tpu.inference import InferenceEngine, SamplingParams
    from deepspeed_tpu.models.presets import build_config
    from tools.loadgen import http_completion

    s = sz["serve"]
    cfg = build_config("llama3-8b", num_layers=s["layers"], **s["overrides"])
    t0 = time.perf_counter()
    model = random_model(cfg, seed)
    print(f"  llama3-8b widths d{cfg.d_model} H{cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim} ff{cfg.d_ff} "
          f"vocab {cfg.vocab_size}, DEPTH {cfg.num_layers} of 32, bf16; "
          f"weights made in {time.perf_counter() - t0:.1f} s")
    eng = InferenceEngine(model, serve_config(sz))
    prompts = make_prompts(sz, cfg.vocab_size, seed)
    n = s["new_tokens"]
    greedy = SamplingParams(temperature=0.0, max_new_tokens=n)

    t0 = time.perf_counter()
    local = eng.generate({u: list(p) for u, p in prompts.items()}, greedy)
    t_cold = time.perf_counter() - t0
    report_path(eng)
    check(all(len(v) == n and all(0 <= t < cfg.vocab_size for t in v)
              for v in local.values()), f"bad in-process tokens {local}")
    print(f"    in-process generate: {len(prompts)} prompts of "
          f"{[len(p) for p in prompts.values()]} tokens -> {n} new each, "
          f"{t_cold:.1f} s with compiles")

    logits = prefill_logits(eng, eng._build_step(), prompts)
    check_first_tokens("bf16", local, logits, reference_logits(model, prompts),
                       LOGIT_REL)

    # the same engine behind the gateway, over loopback (last: stopping
    # the gateway drains the engine for good)
    h = spawn_gateway(eng, GatewayConfig(
        sampling=SamplingParams(temperature=0.0, max_new_tokens=1 << 30)))
    try:
        t0 = time.perf_counter()
        wire = {u: http_completion(h.host, h.port,
                                   {"uid": 1000 + u, "prompt": p,
                                    "max_tokens": n, "stream": True},
                                   timeout=600.0)
                for u, p in prompts.items()}
        t_http = time.perf_counter() - t0
    finally:
        h.stop()
    for u, res in wire.items():
        check(res["code"] == 200, f"HTTP {res['code']} for prompt {u}")
        check(res["tokens"] == local[u],
              f"prompt {u}: HTTP tokens {res['tokens']} != in-process "
              f"{local[u]}")
    print(f"    POST /v1/completions x{len(wire)} over loopback: tokens "
          f"equal the in-process ones, {t_http:.2f} s, wire TTFT ms "
          f"{[round(r['ttft_ms'], 1) for r in wire.values()]}")



def serve_int8_phase(sz, seed):
    """int8 weights at the same widths, the projections through the
    Pallas VMEM-dequant kernel (``kernels_phase`` times it against XLA's
    fused dequant)."""
    from deepspeed_tpu.inference import InferenceEngine, SamplingParams
    from deepspeed_tpu.models.presets import build_config

    s = sz["serve"]
    cfg = build_config("llama3-8b", num_layers=s["int8_layers"],
                       **s["overrides"])
    model = random_model(cfg, seed + 1)
    eng = InferenceEngine(model, serve_config(sz, weight_quant="int8",
                                              mixed_gemm="on"))
    prompts = make_prompts(sz, cfg.vocab_size, seed + 1)
    print(f"  llama3-8b widths, DEPTH {cfg.num_layers} of 32, int8 weights")
    out = eng.generate({u: list(p) for u, p in prompts.items()},
                       SamplingParams(temperature=0.0,
                                      max_new_tokens=s["new_tokens"]))
    report_path(eng)
    check(all(len(v) == s["new_tokens"] for v in out.values()),
          f"bad tokens {out}")
    logits = prefill_logits(eng, eng._build_step(), prompts)
    check_first_tokens("int8", out, logits, reference_logits(model, prompts),
                       INT8_LOGIT_REL)


def serve_moe_phase(sz, seed):
    """Sparse experts served dropless through the grouped kernel, at the
    widths of olmoe-1b-7b, by the entry point the dense model used."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import InferenceEngine, SamplingParams
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul

    s = sz["serve"]
    # moe_dispatch is the TRAINING forward's: the plain ``model.apply``
    # below must drop nothing either; serving never reads it
    cfg = build_config("olmoe-1b-7b", num_layers=s["moe_layers"],
                       moe_dispatch="ragged", **s["moe_overrides"])
    E, rows = cfg.num_experts, s["token_budget"] * cfg.moe_top_k
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    x = jax.random.normal(kx, (rows, cfg.d_model), jnp.bfloat16)
    w = jax.random.normal(kw, (E, cfg.d_model, cfg.d_ff), jnp.bfloat16) \
        / cfg.d_model ** 0.5
    # a decode step's share of the bucket: a quarter of the rows are real
    sizes = jnp.bincount(jax.random.randint(kg, (rows // 4,), 0, E),
                         length=E).astype(jnp.int32)
    outs = {}
    for impl, fn in (("pallas", grouped_matmul),
                     ("xla", jax.lax.ragged_dot)):
        outs[impl], t = timed(jax.jit(fn), x, w, sizes)
        print(f"    grouped matmul [{rows}, {cfg.d_model}] x [{E}, "
              f"{cfg.d_model}, {cfg.d_ff}], {rows // 4} rows routed, "
              f"{impl}: {1e3 * t:.3f} ms")
    close("grouped matmul pallas vs ragged_dot",
          outs["pallas"][:rows // 4], outs["xla"][:rows // 4], BF16_REL)

    model = random_model(cfg, seed + 2)
    print(f"  olmoe-1b-7b widths d{cfg.d_model} H{cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim}, {E} experts of "
          f"{cfg.d_ff}, top-{cfg.moe_top_k}, QK-norm, DEPTH "
          f"{cfg.num_layers} of 16, bf16")
    eng = InferenceEngine(model, serve_config(sz))
    prompts = make_prompts(sz, cfg.vocab_size, seed + 2)
    out = eng.generate({u: list(p) for u, p in prompts.items()},
                       SamplingParams(temperature=0.0,
                                      max_new_tokens=s["new_tokens"]))
    report_path(eng)
    check(all(len(v) == s["new_tokens"] for v in out.values()),
          f"bad tokens {out}")
    snap = eng.metrics_snapshot()
    print(f"    {snap['serving_moe_assignments_total']:.0f} assignments "
          "computed, fullest expert over the mean "
          f"{snap['serving_moe_expert_load_max_over_mean']:.2f}")
    logits = prefill_logits(eng, eng._build_step(), prompts)
    check_first_tokens("moe", out, logits, reference_logits(model, prompts),
                       LOGIT_REL)


def serve_trinity_phase(sz, seed):
    """The cell serve-window-longgen's model (benchmarks/configs/
    trinity-mini-d5.json, at its published widths) through the engine's
    paged path against the benchmark's plain reference, by the cell's own
    comparison (benchmarks/lib/drivers/serve_routed.py: the reference
    follows the experts the engine took) and under the file's own limits:
    the cell's three sample sequences, and a prompt of a window and a
    half prefilled in the engine's ordinary chunks, then 8 fed tokens.
    Then the same logits against every wrong forward the reference knows:
    each has to FAIL the limit the true forward passes (the missing
    window past the window only)."""
    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib.drivers import serve_routed as R
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    _, _, config, mix = common.load_cell("serve-window-longgen")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = R.preset_config(config)
    model = make_model(cfg, seed + 3, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]), "trinity_ref")
    tol = config["reference"]["tolerance"]
    limit, short_limit = tol["followed_rel"], tol["routing_short"]
    sample = config["reference"]["sample"]
    k_dec = int(sample["decode_tokens"])
    print(f"  {config['name']}: d{cfg.d_model} H{cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim}, layers {cfg.layer_kinds}, "
          f"window {cfg.attn_window}, {cfg.num_experts} experts of "
          f"{cfg.moe_d_ff} top-{cfg.moe_top_k}, bf16, limit {limit} with "
          f"the routing followed, {short_limit} on a taken expert's score")
    rng = np.random.RandomState(seed + 3)
    seqs = {f"sample{i}": rng.randint(0, cfg.vocab_size, n + k_dec).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    seqs["past_window"], n_long = R.past_window(config, cfg, seed + 3)
    n_prompt = {u: len(s) - k_dec for u, s in seqs.items()}
    block = int(mix["engine"]["kv_block_size"])
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(mix["engine"]["token_budget"]),
        max_seqs=int(mix["engine"]["max_seqs"]), kv_block_size=block,
        num_kv_blocks=2 * (-(-(n_long + k_dec) // block)) + 16,
        max_seq_len=int(mix["engine"]["max_seq_len"]),
        **config.get("engine_options", {})))
    report_path(eng)
    system = R.system_side(eng, seqs, n_prompt)
    steps = system["past_window"][2]
    check(steps >= -(-n_long // eng.icfg.token_budget) + k_dec,
          f"the long prompt took {steps} steps, not its chunks")
    print(f"    long prompt: {n_long} tokens in chunks of "
          f"{eng.icfg.token_budget}, then {k_dec} fed: {steps} steps")

    def readings(wrong):
        """(sample prefill, sample decode, past-window prefill,
        past-window decode, the routing's shortfall)."""
        read = R.follow(ref, model.params, config, seqs, system, wrong=wrong)
        far = read.pop("past_window")
        return (max(r["prefill"] for r in read.values()),
                max(r["decode"] for r in read.values()),
                far["prefill"], far["decode"],
                max([far["short"]] + [r["short"] for r in read.values()]))

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in zip(
            ("sample prefill", "sample decode", "past-window prefill",
             "past-window decode", "routing short"), got))

    true = readings(None)
    print("    true forward: " + line(true))
    check(max(true[:4]) <= limit, f"the engine differs from the reference "
          f"that follows its routing beyond {limit}: {true}")
    check(true[4] <= short_limit, f"the engine took an expert whose score "
          f"the reference has {true[4]} under its eighth")
    for wrong in ref.WRONG:
        got = readings(wrong)
        fails = max(got[:4]) > limit
        print(f"    reference with {wrong}: " + line(got)
              + ("  (fails)" if fails else "  (PASSES)"))
        if wrong == "no_window":
            check(max(got[2:4]) > limit, "a reference without the window "
                  "agrees with the system past the window")
            check(max(got[:2]) <= limit or sz is TINY, "the sample's "
                  "contexts reach the window after all")
        elif sz is not TINY and wrong != "bias_in_weights":
            # the bias moves a weight by 3%: under bfloat16's own
            # reading whatever follows the routing (PERF.md, PR 38);
            # float32 tells it (tests/test_trinity.py)
            check(fails, f"a reference with {wrong} agrees with the system")


def serve_falcon_h1_phase(sz, seed):
    """The cell serve-ssm-chat's model (benchmarks/configs/
    falcon-h1-34b-d6.json, at its published widths) through the engine's
    paged path against the benchmark's plain reference, by the cell's own
    three comparisons (benchmarks/lib/drivers/serve_recurrent.py) and
    under the file's own limit: the cell's three sample sequences
    prefilled in one step, a long prompt prefilled over several steps,
    and the sample once more in the slots the others left; 8 fed tokens
    each.  Then the same logits against every wrong forward the
    reference knows: each has to FAIL the limit the true forward passes,
    in the comparison that can see it."""
    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib import traffic as T
    from benchmarks.lib.drivers import serve
    from benchmarks.lib.drivers import serve_recurrent as R
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    _, _, config, mix = common.load_cell("serve-ssm-chat")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = R.preset_config(config)
    model = make_model(cfg, seed + 5, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]),
        "falcon_h1_ref")
    limit = config["reference"]["tolerance"]["logits_rel"]
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    sd = cfg.ssm_dims
    print(f"  {config['name']}: d{cfg.d_model} H{cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim}, {cfg.num_layers} layers of "
          f"{cfg.layer_pattern}, mixer {sd.heads}x{sd.head_dim} state "
          f"{sd.state} groups {sd.groups} chunk {sd.chunk}, bf16, limit "
          f"{limit}")
    rng = np.random.RandomState(seed + 5)
    seqs = {900000 + i: rng.randint(0, cfg.vocab_size, n + k).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k for u, s in seqs.items()}
    n_long = int(sample["long_prompt"])
    long_seq = T.rng_for(seed + 5, 11).integers(
        0, cfg.vocab_size, n_long + k).tolist()
    sizes = mix["engine"]
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(sizes["token_budget"]),
        max_seqs=int(sizes["max_seqs"]),
        kv_block_size=int(sizes["kv_block_size"]),
        num_kv_blocks=int(sizes["num_kv_blocks"]),
        max_seq_len=int(sizes["max_seq_len"]),
        **config.get("engine_options", {})))
    report_path(eng)
    mbs = eng.max_blocks_per_seq
    first = serve.engine_logits(eng, seqs, n_prompt, mbs)
    R.left_slots_first(eng)
    far, steps = R.paged_logits(eng, long_seq, n_long)
    check(steps >= -(-n_long // eng.icfg.token_budget) + k,
          f"the long prompt took {steps} steps, not its chunks")
    R.left_slots_first(eng)
    again = serve.engine_logits(eng, seqs, n_prompt, mbs)
    print(f"    long prompt: {n_long} tokens in steps of "
          f"{eng.icfg.token_budget}, then {k} fed: {steps} steps")
    u0 = min(seqs)

    def want(tokens, **kw):
        return np.asarray(ref.logits(model.params, np.asarray(tokens),
                                     config, last=k + 1, **kw), np.float32)

    def pair(got, ref_rows):
        got = np.stack(got) if isinstance(got, list) else got
        return R.rel(got[:1], ref_rows[:1]), R.rel(got[1:], ref_rows[1:])

    def readings(wrong, **kw):
        """(sample prefill, sample decode, chunked prefill, chunked
        decode, reused-slots prefill, reused-slots decode); the sample's
        are the largest over its sequences.  ``at`` defaults to the
        prompt's end of each sequence."""
        def at(n):
            return dict(kw, at=kw.get("at", n)) if wrong in (
                "state_reset", "tail_cut") else kw
        refs = {u: want(s, wrong=wrong, **at(n_prompt[u]))
                for u, s in seqs.items()}
        one = [pair(first[u], refs[u]) for u in seqs]
        two = [pair(again[u], refs[u]) for u in seqs]
        return (max(p for p, _ in one), max(d for _, d in one),
                *pair(far, want(long_seq, wrong=wrong, **at(n_long))),
                max(p for p, _ in two), max(d for _, d in two))

    names = ("sample prefill", "sample decode", "chunked prefill",
             "chunked decode", "reused prefill", "reused decode")

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in zip(names, got))

    true = readings(None)
    print("    true forward: " + line(true))
    check(max(true) <= limit, f"the engine differs from the reference "
          f"beyond {limit}: {true}")
    # where each fault shows: a state or a tail lost at the prompt's end
    # shows on the fed tokens; a stale state in the slot's next sequence
    sees = {"state_reset": (1, 3, 5), "tail_cut": (1, 3, 5),
            "stale_state": (4, 5)}
    for wrong in ref.WRONG:
        kw = {"before": np.asarray(long_seq)} if wrong == "stale_state" \
            else {}
        got = readings(wrong, **kw)
        seen = [got[i] for i in sees.get(wrong, range(6))]
        fails = min(seen) > limit
        print(f"    reference with {wrong}: " + line(got)
              + ("  (fails)" if fails else "  (PASSES)"))
        if sz is not TINY:
            check(fails, f"a reference with {wrong} agrees with the "
                  "system where the fault would show")
    if sz is not TINY:
        # a tail cut where the prompt's last step began, far from the
        # rows compared: for the record, not a check
        cut = (n_long // eng.icfg.token_budget) * eng.icfg.token_budget
        got = pair(far, want(long_seq, wrong="tail_cut", at=cut))
        print(f"    reference with tail_cut at {cut} (the last step's "
              f"first token): chunked prefill {got[0]:.4g}, decode "
              f"{got[1]:.4g}")


def serve_ling_phase(sz, seed):
    """The cell serve-kda-reason's model (benchmarks/configs/
    ling-3.0-flash-d7.json, at its published widths: delta-rule layers
    with a state row a sequence, a latent layer over a latent pool, 128
    of 512 group-routed experts) through the engine's paged path against
    the benchmark's plain reference that follows the engine's routing,
    by the cell's own comparisons (benchmarks/lib/drivers/
    serve_hybrid_share.py) and under the file's own limits: the sample,
    a long prompt over several steps, a sequence decoded for thousands
    of fed tokens, the sample again in the slots the others left.  Then
    the same logits against every wrong forward the reference knows:
    each has to FAIL a limit the true forward passes (the group limit
    left out: the routing's)."""
    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib import traffic as T
    from benchmarks.lib.drivers import serve_hybrid_share as H
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    _, _, config, mix = common.load_cell("serve-kda-reason")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = H.preset_config(config)
    model = make_model(cfg, seed + 7, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]), "ling_ref")
    tol = config["reference"]["tolerance"]
    limit, short_limit = tol["followed_rel"], tol["routing_short"]
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    kd, md = cfg.kda_dims, cfg.mla_dims
    print(f"  {config['name']}: d{cfg.d_model}, layers {cfg.layer_kinds}, "
          f"KDA {kd.heads}x{kd.key_dim}x{kd.value_dim} chunk {kd.chunk}, "
          f"MLA {cfg.num_heads} heads over rows of {md.row}, experts "
          f"{cfg.experts_held} of {cfg.num_experts} top-{cfg.moe_top_k}, "
          f"bf16, limit {limit} with the routing followed, {short_limit} "
          "on a taken expert's score")
    rng = T.rng_for(seed + 7, 9)
    seqs = {900000 + i: rng.integers(0, cfg.vocab_size, n + k).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k for u, s in seqs.items()}
    sizes = mix["engine"]
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(sizes["token_budget"]),
        max_seqs=int(sizes["max_seqs"]),
        kv_block_size=int(sizes["kv_block_size"]),
        num_kv_blocks=int(sizes["num_kv_blocks"]),
        max_seq_len=int(sizes["max_seq_len"]),
        **config.get("engine_options", {})))
    report_path(eng)
    named, prompts, system = H.system_side(eng, config, seqs, n_prompt,
                                           seed + 7)
    budget = eng.icfg.token_budget
    print(f"    long prompt: {prompts['chunked']} tokens in steps of "
          f"{budget}, then {k} fed: {system['chunked'][2]} steps; long "
          f"decode: {system['long_decode'][2]} steps")

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in got.items())

    def failing(got):
        return [n for n, v in got.items()
                if v > (short_limit if n == "routing_shortfall" else limit)]

    true = H.readings(ref, model.params, config, named, prompts, system,
                      budget)
    print("    true forward: " + line(true))
    # every wrong forward against one sequence of each comparison; all
    # of them are read before any is judged
    few = [n for n in named if not n.endswith(("1", "2"))]
    agree = []
    for wrong in ref.WRONG:
        got = H.readings(ref, model.params, config,
                         {n: named[n] for n in few}, prompts,
                         {n: system[n] for n in few}, budget, wrong=wrong)
        fails = failing(got)
        print(f"    reference with {wrong}: " + line(got)
              + (f"  (fails {fails})" if fails else "  (PASSES)"),
              flush=True)
        seen = ["routing_shortfall"] if wrong == "no_group_limit" else [
            f for f in got if f != "routing_shortfall"]
        # the one latent layer's attention averages hundreds of seeded
        # values: its shared key or its latent's norm left out reads
        # 3.4e-2 and 3.6e-2 to 4.6e-2 where the true forward reads up to
        # 3.0e-2 (PERF.md, PR 44); float32 tells them (tests/test_ling.py)
        if not set(seen) & set(fails) and wrong not in ("no_rope_key",
                                                        "no_c_norm"):
            agree.append(wrong)
    check(not failing(true), f"the engine differs from the reference "
          f"that follows its routing: {failing(true)} of {true}")
    check(sz is TINY or not agree, f"a reference with {agree} agrees with "
          "the system under the limit that would have to tell it")


def serve_longcat_phase(sz, seed):
    """The cell serve-mla-docqa's model (benchmarks/configs/
    longcat-flash-d4.json, at its published widths: latent attention
    with a query latent in both sublayers of four shortcut-connected
    layers, the latent pool as the only cache, 16 of 512 experts and 256
    that compute nothing behind a router of 768 softmax outputs) through
    the engine's paged path against the benchmark's plain reference that
    follows the engine's routing, by the cell's own comparisons
    (benchmarks/lib/drivers/serve_latent_share.py) and under the file's
    own limits.  Then the same logits against every wrong forward the
    reference knows, each of which has to FAIL a limit the true forward
    passes; then the SYSTEM in a lower precision than the file states
    (the router's softmax and top-k in bfloat16; the online softmax's
    scores and accumulators in bfloat16), read and reported: at bfloat16
    weights no limit tells them."""
    import gc
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib import traffic as T
    from benchmarks.lib.drivers import serve_latent_share as D
    from benchmarks.lib.drivers.serve_hybrid_share import routing_step
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
    from deepspeed_tpu.ops import mla
    from deepspeed_tpu.parallel import moe

    _, _, config, mix = common.load_cell("serve-mla-docqa")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = D.preset_config(config)
    model = make_model(cfg, seed + 7, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]),
        "longcat_ref")
    tol = config["reference"]["tolerance"]
    limit, short_limit = tol["followed_rel"], tol["routing_short"]
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    md = cfg.mla_dims
    print(f"  {config['name']}: d{cfg.d_model}, {cfg.expert_layers} layers "
          f"of two sublayers, MLA {cfg.num_heads} heads over rows of "
          f"{md.row}, query latent {md.q_rank} x{md.q_scale:.3g}, latent "
          f"x{md.kv_scale:.4g}, experts {cfg.experts_held} of "
          f"{cfg.num_experts} + {cfg.moe_zero_experts} that compute "
          f"nothing, top-{cfg.moe_top_k}, bf16, limit {limit} with the "
          f"routing followed, {short_limit} on a taken expert's score")
    rng = T.rng_for(seed + 7, 9)
    seqs = {900000 + i: rng.integers(0, cfg.vocab_size, n + k).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k for u, s in seqs.items()}
    sizes = mix["engine"]

    def system(**over):
        eng = InferenceEngine(model, InferenceConfig(
            token_budget=int(sizes["token_budget"]),
            max_seqs=int(sizes["max_seqs"]),
            kv_block_size=int(sizes["kv_block_size"]),
            num_kv_blocks=int(sizes["num_kv_blocks"]),
            max_seq_len=int(sizes["max_seq_len"]),
            **{**config.get("engine_options", {}), **over}))
        out = D.system_side(eng, routing_step(eng), config, seqs, n_prompt,
                            seed + 7)
        return eng.icfg.token_budget, out

    budget, (named, prompts, true_system) = system()
    gc.collect()
    print(f"    long prompt: {prompts['chunked']} tokens in steps of "
          f"{budget}, then {k} fed: {true_system['chunked'][2]} steps")

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in got.items())

    def failing(got):
        return [n for n, v in got.items()
                if v > (short_limit if n == "routing_shortfall" else limit)]

    true = D.readings(ref, model.params, config, named, prompts, true_system,
                      budget)
    print("    true forward: " + line(true))
    # every wrong forward against one sample and the long prompt; all of
    # them are read before any is judged
    few = [n for n in named if not n.endswith(("1", "2"))]
    agree = []
    # (the rehearsal proves the control flow on two of them: each is
    # six programs compiled at two lengths)
    for wrong in ref.WRONG[:None if sz is REAL else 2]:
        got = D.readings(ref, model.params, config,
                         {n: named[n] for n in few}, prompts,
                         {n: true_system[n] for n in few}, budget,
                         wrong=wrong)
        fails = failing(got)
        print(f"    reference with {wrong}: " + line(got)
              + (f"  (fails {fails})" if fails else "  (PASSES)"),
              flush=True)
        if not fails:
            agree.append(wrong)

    # the system in a lower precision than the file states, against the
    # true reference
    route = moe.route

    def bf16_route(logits, *a, **kw):
        return route(logits.astype(jnp.bfloat16), *a, **kw)

    attend, fori = mla.latent_attend, jax.lax.fori_loop

    def bf16_loop(lo, hi, body, init):
        # every pass leaves its running maximum, sum and values in
        # bfloat16, whatever type the pass's products came in
        return fori(lo, hi, lambda i, c: jax.tree.map(
            lambda o, t: o.astype(t.dtype), body(i, c), init), init)

    def bf16_attend(*a, **kw):
        with mock.patch.object(mla, "F32", jnp.bfloat16), \
                mock.patch.object(jax.lax, "fori_loop", bf16_loop):
            return attend(*a, **kw).astype(jnp.float32)

    for name, patch, over in (
            ("the router's softmax and top-k in bfloat16",
             mock.patch.object(moe, "route", bf16_route), {}),
            # (the XLA formulation's: the one whose types a patch can
            # lower; the kernel's are float32 in its body)
            ("the online softmax's scores and accumulators in bfloat16",
             mock.patch.object(mla, "latent_attend", bf16_attend),
             {"attn_impl": "xla"})
    )[None if sz is REAL else 1:]:     # (the rehearsal: the second alone)
        with patch:
            _, (_, _, lowered) = system(**over)
        gc.collect()
        got = D.readings(ref, model.params, config,
                         {n: named[n] for n in few}, prompts,
                         {n: lowered[n] for n in few}, budget)
        fails = failing(got)
        # read and reported, not judged: at bfloat16 weights neither is
        # told by a limit that every true run passes (the hidden state's
        # own rounding moves a score by ten times a bfloat16 score's;
        # the configuration's tolerance.why, PERF.md section 7)
        print(f"    system with {name}: " + line(got)
              + (f"  (fails {fails})" if fails
                 else "  (inside the true forward's range)"), flush=True)
    check(not failing(true), f"the engine differs from the reference "
          f"that follows its routing: {failing(true)} of {true}")
    check(sz is TINY or not agree, f"{agree} agree(s) with the true "
          "forward under the limit that would have to tell it")


def serve_deepseek_v2_phase(sz, seed):
    """The cell serve-mla-shared-docs' model (benchmarks/configs/
    deepseek-v2-d5.json, at its published widths: latent attention at 128
    heads under YaRN in a dense layer and four expert layers, 40 of 160
    experts in two of eight device groups behind the group-limited greedy
    router, the latent pool and its prefix cache) through the engine's
    paged path against the benchmark's plain reference that follows the
    engine's routing, by the cell's own comparisons
    (benchmarks/lib/drivers/serve_latent_groups.py: a long prompt past
    YaRN's original context, a second sequence onto its indexed blocks)
    and under the file's own limits.  Then the same logits against every
    wrong forward the reference knows, each of which has to FAIL a limit
    the true forward passes."""
    import gc

    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib import traffic as T
    from benchmarks.lib.drivers import serve_latent_groups as D
    from benchmarks.lib.drivers.serve_hybrid_share import routing_step
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    _, _, config, mix = common.load_cell("serve-mla-shared-docs")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = D.preset_config(config)
    model = make_model(cfg, seed + 7, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]), "dsv2_ref")
    tol = config["reference"]["tolerance"]
    limit, short_limit = tol["followed_rel"], tol["routing_short"]
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    md = cfg.mla_dims
    print(f"  {config['name']}: d{cfg.d_model}, {cfg.num_layers} layers "
          f"({cfg.num_dense_layers} dense), MLA {cfg.num_heads} heads over "
          f"rows of {md.row}, query latent {md.q_rank}, softmax scale "
          f"x{md.score_scale:.5g} ({cfg.rope_yarn}), experts "
          f"{cfg.experts_held} of {cfg.num_experts} (groups "
          f"{cfg.held_groups} of {cfg.moe_groups}, {cfg.moe_groups_kept} "
          f"open by {cfg.moe_group_score}), top-{cfg.moe_top_k}, bf16, "
          f"limit {limit} with the routing followed, {short_limit} on a "
          "taken expert's score")
    rng = T.rng_for(seed + 7, 9)
    seqs = {900000 + i: rng.integers(0, cfg.vocab_size, n + k).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k for u, s in seqs.items()}
    sizes = mix["engine"]
    block = int(sizes["kv_block_size"])
    # the cell's engine over a pool ONE block larger than the long prompt
    # and its fed tokens take: the long prompt's own blocks push out what
    # the samples left indexed, and the second sequence, admitted onto
    # the long prompt's indexed blocks, takes the blocks of its own
    # tokens from indexed ones (an aliased hit while the pool evicts)
    pool = -(-(int(sample["long_prompt"]) + k) // block) + 1
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(sizes["token_budget"]),
        max_seqs=int(sizes["max_seqs"]),
        kv_block_size=block, num_kv_blocks=pool,
        max_seq_len=int(sizes["max_seq_len"]),
        **config.get("engine_options", {})))
    budget = eng.icfg.token_budget
    evicted = {}
    named, prompts, true_system = D.system_side(
        eng, routing_step(eng), config, seqs, n_prompt, seed + 7,
        ready=lambda name, *_: evicted.update(
            {name: eng.state.prefix_evictions}))
    del eng
    gc.collect()
    before, after = evicted["chunked"], evicted["shared"]
    print(f"    long prompt: {prompts['chunked']} tokens in steps of "
          f"{budget}, then {k} fed: {true_system['chunked'][2]} steps; the "
          f"second sequence onto {sample['shared_prefix_tokens']} of its "
          f"tokens: {true_system['shared'][2]} steps; a pool of {pool} "
          f"blocks: {before} indexed blocks taken back before the second "
          f"sequence, {after - before} while it ran")
    check(after > before, "the second sequence took no indexed block "
          "back: it was not admitted into a pool that was evicting")

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in got.items())

    def failing(got):
        return [n for n, v in got.items()
                if v > (short_limit if n == "routing_shortfall" else limit)]

    true = D.readings(ref, model.params, config, named, prompts, true_system,
                      budget)
    print("    true forward: " + line(true))
    # every wrong forward against one sample, the long prompt and the
    # sequence on its blocks; all are read before any is judged (the
    # rehearsal proves the control flow on two of them)
    few = [n for n in named if not n.endswith(("1", "2"))]
    agree = []
    for wrong in ref.WRONG[:None if sz is REAL else 2]:
        got = D.readings(ref, model.params, config,
                         {n: named[n] for n in few}, prompts,
                         {n: true_system[n] for n in few}, budget,
                         wrong=wrong)
        fails = failing(got)
        print(f"    reference with {wrong}: " + line(got)
              + (f"  (fails {fails})" if fails else "  (PASSES)"),
              flush=True)
        if not fails:
            agree.append(wrong)
    check(not failing(true), f"the engine differs from the reference "
          f"that follows its routing: {failing(true)} of {true}")
    check(sz is TINY or not agree, f"{agree} agree(s) with the true "
          "forward under the limit that would have to tell it")


# --------------------------------------------------------------------------
# four chips: the sharded paths and what they are compared with
# --------------------------------------------------------------------------

def serve_granite_phase(sz, seed):
    """The cell serve-ssm-moe-rag's model (benchmarks/configs/
    granite-4.0-h-small-d10.json, at its published widths: a Mamba-2
    mixer alone in nine layers with a state row a sequence, one attention
    layer without positions over the block pool, 36 of 72 softmax-routed
    experts beside a shared MLP in every layer) through the engine's
    paged path against the benchmark's plain reference that follows the
    engine's routing, by the cell's own comparisons (benchmarks/lib/
    drivers/serve_state_share.py) and under the file's own limits: the
    sample, a long prompt over several steps, the sample again in the
    slot the others left.  Then the same logits against every wrong
    forward the reference knows: each has to FAIL a limit the true
    forward passes."""
    import jax.numpy as jnp

    from benchmarks.lib import common
    from benchmarks.lib import traffic as T
    from benchmarks.lib.drivers import serve_state_share as D
    from benchmarks.lib.drivers.serve_hybrid_share import routing_step
    from benchmarks.lib.weights import make_model
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    _, _, config, mix = common.load_cell("serve-ssm-moe-rag")
    if sz is TINY:
        common.apply_rehearsal(config, mix)
    cfg = D.preset_config(config)
    model = make_model(cfg, seed + 7, dtype=jnp.bfloat16)
    ref = common.load_module(
        os.path.join(common.ROOT, config["reference"]["file"]),
        "granite_ref")
    tol = config["reference"]["tolerance"]
    limit, short_limit = tol["followed_rel"], tol["routing_short"]
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    sd = cfg.ssm_dims
    print(f"  {config['name']}: d{cfg.d_model}, layers {cfg.layer_kinds}, "
          f"Mamba-2 {sd.heads}x{sd.head_dim}x{sd.state} chunk {sd.chunk}, "
          f"attention {cfg.num_heads}/{cfg.num_kv_heads} heads of "
          f"{cfg.head_dim} without positions, experts {cfg.experts_held} of "
          f"{cfg.num_experts} top-{cfg.moe_top_k}, bf16, limit {limit} with "
          f"the routing followed, {short_limit} on a taken expert's logit")
    rng = T.rng_for(seed + 7, 9)
    seqs = {900000 + i: rng.integers(0, cfg.vocab_size, n + k).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k for u, s in seqs.items()}
    sizes = mix["engine"]
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(sizes["token_budget"]),
        max_seqs=int(sizes["max_seqs"]),
        kv_block_size=int(sizes["kv_block_size"]),
        num_kv_blocks=int(sizes["num_kv_blocks"]),
        max_seq_len=int(sizes["max_seq_len"]),
        **config.get("engine_options", {})))
    report_path(eng)
    named, prompts, system = D.system_side(
        eng, routing_step(eng), config, seqs, n_prompt, seed + 7)
    budget = eng.icfg.token_budget
    print(f"    long prompt: {prompts['chunked']} tokens in steps of "
          f"{budget}, then {k} fed: {system['chunked'][2]} steps")

    def line(got):
        return ", ".join(f"{n} {v:.4g}" for n, v in got.items())

    def failing(got):
        return [n for n, v in got.items()
                if v > (short_limit if n == "routing_shortfall" else limit)]

    true = D.readings(ref, model.params, config, named, prompts, system,
                      budget)
    print("    true forward: " + line(true))
    # every wrong forward against one sequence of each comparison; all
    # of them are read before any is judged
    few = [n for n in named if not n.endswith(("1", "2"))]
    agree = []
    # (a rehearsal proves the control flow with the first of them: a wrong
    # forward is a program of its own a layer kind and a length)
    for wrong in ref.WRONG[:1] if sz is TINY else ref.WRONG:
        got = D.readings(ref, model.params, config,
                         {n: named[n] for n in few}, prompts,
                         {n: system[n] for n in few}, budget, wrong=wrong)
        fails = [f for f in failing(got) if f != "routing_shortfall"]
        print(f"    reference with {wrong}: " + line(got)
              + (f"  (fails {fails})" if fails else "  (PASSES)"),
              flush=True)
        if not fails:
            agree.append(wrong)
    check(not failing(true), f"the engine differs from the reference "
          f"that follows its routing: {failing(true)} of {true}")
    check(sz is TINY or not agree, f"a reference with {agree} agrees with "
          "the system under the limit that would have to tell it")


def spread(name, tree, n):
    """Every sharded array of ``tree`` has shards on ``n`` distinct
    devices at 1/n of its size; returns the sharded share of the bytes."""
    import jax

    total = sharded = 0
    for x in jax.tree.leaves(tree):
        if not isinstance(x, jax.Array):
            continue
        total += x.nbytes
        if x.sharding.is_fully_replicated:
            continue
        shards = x.addressable_shards
        check(len({s.device for s in shards}) == n,
              f"{name}: {x.shape} has shards on "
              f"{len({s.device for s in shards})} devices")
        check(all(s.data.size * n == x.size for s in shards),
              f"{name}: {x.shape} shard {shards[0].data.shape} is not 1/{n}")
        sharded += x.nbytes
    print(f"    {name}: {sharded / max(total, 1):.1%} of {total / 1e6:.1f} MB "
          f"is sharded over {n} devices, each shard 1/{n} of its array")
    return sharded / max(total, 1)


def all_hold_bytes(devs):
    """``bytes_in_use`` is non-zero on every device (where the backend
    reports memory at all: the CPU rehearsal's does not)."""
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    print(f"    bytes_in_use per device: {used}")
    if devs[0].platform == "tpu":
        check(all(used), "a device holds nothing")


def collectives_in(compiled_text):
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {op: compiled_text.count(f" {op}(") + compiled_text.count(
        f" {op}-start(") for op in ops if f" {op}" in compiled_text}


def four_chip_phases(sz, seed):
    import jax

    from deepspeed_tpu.comm import MeshTopology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference import InferenceEngine, SamplingParams
    from deepspeed_tpu.models.presets import build_config

    devs = jax.devices()[:4]
    with phase("train ZeRO-3 fsdp=4 vs one device"):
        sharded, eng4, batch = train_run(sz, seed, devs, {"fsdp": 4}, 3,
                                         "fsdp=4")
        share = spread("ZeRO-3 train state", eng4.state, 4)
        check(share > 0.9, f"only {share:.1%} of the train state is sharded")
        step = eng4._pick_train_step()
        rng = jax.random.PRNGKey(0)
        batch = eng4.shard_batch(batch)
        coll = collectives_in(
            step.lower(eng4.state, batch, rng).compile().as_text())
        print(f"    collectives in the compiled ZeRO-3 step: {coll}")
        check(coll, "the ZeRO-3 step compiled without a collective")
        all_hold_bytes(devs)
        del eng4, step, batch
        gc.collect()
        single, eng1, _ = train_run(sz, seed, devs[:1], {"data": 1}, 3,
                                    "one device")
        del eng1
        for i, (a, b) in enumerate(zip(sharded, single)):
            check(abs(a - b) <= LOSS_REL * abs(b),
                  f"step {i}: fsdp=4 loss {a} vs one-device {b}")
        print(f"    losses agree within {LOSS_REL}: max relative difference "
              f"{max(abs(a - b) / abs(b) for a, b in zip(sharded, single)):.3g}")

    with phase("serve tensor=4 vs one chip"):
        s = sz["serve"]
        cfg = build_config("llama3-8b", num_layers=s["layers"],
                           **s["overrides"])
        model = random_model(cfg, seed)
        prompts = make_prompts(sz, cfg.vocab_size, seed)
        greedy = SamplingParams(temperature=0.0,
                                max_new_tokens=s["new_tokens"])
        print(f"  llama3-8b widths, DEPTH {cfg.num_layers} of 32, bf16")
        one = InferenceEngine(model, serve_config(sz))
        ref = one.generate({u: list(p) for u, p in prompts.items()}, greedy)
        report_path(one)
        topo = MeshTopology.build(MeshConfig(tensor=4), devices=devs)
        tp = InferenceEngine(model, serve_config(sz), topology=topo)
        out = tp.generate({u: list(p) for u, p in prompts.items()}, greedy)
        report_path(tp)
        share = spread("TP weights", tp.params, 4)
        check(share > 0.9, f"only {share:.1%} of the weights is sharded")
        check(spread("TP KV cache", tp.state.kv, 4) == 1.0,
              "the KV cache is not sharded")
        all_hold_bytes(devs)
        step1, step4 = one._build_step(), tp._build_step()
        lg4 = prefill_logits(tp, step4, prompts)
        lg1 = prefill_logits(one, step1, prompts)
        for u in prompts:
            close(f"prompt {u} first-token logits tp4 vs one chip",
                  lg4[u], lg1[u], LOGIT_REL)
        tp.put(0, prompts[0])
        b = tp._stage(tp.state.build_batch(tp._schedule(),
                                           tp.icfg.token_budget))
        coll = collectives_in(step4.lower(tp.params, tp._quant, tp.state.kv,
                                          b).compile().as_text())
        tp.flush(0)
        print(f"    collectives in the compiled TP step: {coll}")
        check(coll, "the TP step compiled without a collective")
        for u in prompts:
            if out[u] == ref[u]:
                print(f"    prompt {u}: {len(out[u])} greedy tokens equal")
                continue
            i = next(i for i, (a, b) in enumerate(zip(out[u], ref[u]))
                     if a != b)
            prefix = {u: prompts[u] + ref[u][:i]}
            a = prefill_logits(tp, step4, prefix)[u]
            b = prefill_logits(one, step1, prefix)[u]
            print(f"    prompt {u}: parts at token {i} "
                  f"({out[u][i]} vs {ref[u][i]})")
            close(f"prompt {u} logits at the divergence", a, b, LOGIT_REL)
            check(abs(float(b[out[u][i]] - b[ref[u][i]]))
                  <= 2 * float(np.abs(a - b).max()),
                  f"prompt {u}: the two tokens are further apart than the "
                  "logit difference could bridge")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths (ZeRO-3 over fsdp, "
                    "tensor-parallel serving) and what they are compared "
                    "with, in one process driving four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform JAX reports (kernels in "
                    "interpret mode off-TPU); the last line names the "
                    "platform it really ran on")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=None,
                    help="run this one-chip phase alone (its name as the "
                    "log prints it, e.g. serve-trinity)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    from deepspeed_tpu.platform.compile_cache import enable_compile_cache

    devices = jax.devices()         # raises when the backend cannot start
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX reports {dev.platform!r} "
              "(--rehearse runs the tiny-size rehearsal)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX reports {len(devices)}", file=sys.stderr)
        return 2
    # XLA:CPU executables are tied to the host's CPU features and cost
    # little to rebuild: the rehearsal leaves the cache alone off-TPU
    cache_dir = enable_compile_cache() if dev.platform == "tpu" else None
    _watch_compiles()
    sz = TINY if args.rehearse else REAL
    print(f"chip_smoke: {'REHEARSAL (tiny sizes) ' if args.rehearse else ''}"
          f"on {dev.platform} / {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, seed {args.seed}, compile cache "
          f"{cache_dir}", flush=True)

    if args.chips == 4:
        four_chip_phases(sz, args.seed)
    else:
        one_chip = (
            ("block_until_ready", lambda: barrier_phase(sz)),
            ("profiler window", lambda: profiler_phase(sz)),
            ("kernels vs XLA", lambda: kernels_phase(sz, args.seed)),
            ("latent kernel", lambda: latent_kernel_phase(sz, args.seed)),
            ("train", lambda: train_run(sz, args.seed, devices[:1],
                                        {"data": 1}, 1, "trainer")),
            ("serve", lambda: serve_phase(sz, args.seed)),
            ("serve-int8", lambda: serve_int8_phase(sz, args.seed)),
            ("serve-moe", lambda: serve_moe_phase(sz, args.seed)),
            ("serve-trinity", lambda: serve_trinity_phase(sz, args.seed)),
            ("serve-falcon-h1",
             lambda: serve_falcon_h1_phase(sz, args.seed)),
            ("serve-ling", lambda: serve_ling_phase(sz, args.seed)),
            ("serve-longcat", lambda: serve_longcat_phase(sz, args.seed)),
            ("serve-granite", lambda: serve_granite_phase(sz, args.seed)),
            ("serve-deepseek-v2",
             lambda: serve_deepseek_v2_phase(sz, args.seed)))
        if args.only and args.only not in dict(one_chip):
            ap.error(f"--only {args.only!r}: no such phase; have "
                     f"{[n for n, _ in one_chip]}")
        for name, run in one_chip:
            if args.only in (None, name):
                with phase(name):
                    run()

    print(f"total {time.perf_counter() - t_start:.1f} s; compile "
          f"{COMPILE['compile_s']:.1f} s over {COMPILE['cache_misses']} "
          f"cache misses, {COMPILE['cache_hits']} cache hits read in "
          f"{COMPILE['cache_read_s']:.1f} s; "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    if FAILED:
        print(f"chip_smoke: FAILED phases: {FAILED}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices) if args.chips == 1 else args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
