"""Collective-sweep microbenchmark CLI — the ``ds_bench`` analog.

Reference: ``bin/ds_bench`` driving the communication benchmark suite
(all_reduce/all_gather/reduce_scatter/all_to_all/broadcast over a
doubling message-size sweep, reporting latency + algbw/busbw per size —
the pod-bringup tool).  TPU-native: collectives run as jitted ``psum``/
``all_gather``/``psum_scatter``/``all_to_all`` over a named mesh axis,
so the sweep measures exactly the XLA collectives training uses, on ICI
when the axis spans a slice and on DCN when it spans hosts.

Usage (single host, all local devices)::

    python -m deepspeed_tpu.comm.bench --ops all_reduce,all_gather \
        --maxsize 28 --trials 20

Multi-host: launch one process per host with the runner
(``python -m deepspeed_tpu.launcher.runner --hostfile ...``); the mesh
then spans the pod and the sweep exercises the cross-host fabric.

Timing barrier: ``block_until_ready``, which on the TPU v5e is a real
completion barrier (``chip_smoke.py`` measures it every run: the wait
takes the compute time, a value fetch after it a further ~2 ms) — so no
scalar fetch rides inside a timed window.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from .comms_logging import calc_bw_log, convert_size

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
       "broadcast")


def _build_op(op: str, mesh, axis: str):
    """One jitted collective over ``axis``; input sharded on dim 0 for
    the scatter/gather family, replicated for all_reduce/broadcast."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axis))

    def wrap(body, in_spec):
        f = shard_map(body, mesh=mesh, in_specs=in_spec,
                          out_specs=in_spec, check_vma=False)
        return jax.jit(f), (repl if in_spec == P() else shard)

    if op == "all_reduce":
        def body(x):
            with jax.named_scope(f"bench_all_reduce_{axis}"):
                return jax.lax.psum(x, axis)
        return wrap(body, P())
    if op == "all_gather":
        # per-device shard -> full tensor, then keep the local slice so
        # input/output specs match (steady-state ZeRO gather shape)
        def body(x):
            with jax.named_scope(f"bench_all_gather_{axis}"):
                g = jax.lax.all_gather(x, axis, tiled=True)
            return jax.lax.dynamic_slice_in_dim(
                g, jax.lax.axis_index(axis) * x.shape[0], x.shape[0])
        return wrap(body, P(axis))
    if op == "reduce_scatter":
        def body(x):
            with jax.named_scope(f"bench_reduce_scatter_{axis}"):
                s = jax.lax.psum_scatter(x, axis, scatter_dimension=0,
                                         tiled=True)
            return jnp.concatenate([s] * n, axis=0)
        return wrap(body, P(axis))
    if op == "all_to_all":
        def body(x):
            with jax.named_scope(f"bench_all_to_all_{axis}"):
                return jax.lax.all_to_all(
                    x.reshape(n, -1), axis, split_axis=0, concat_axis=0,
                    tiled=False).reshape(x.shape)
        return wrap(body, P(axis))
    if op == "broadcast":
        def body(x):
            with jax.named_scope(f"bench_broadcast_{axis}"):
                root = jnp.where(jax.lax.axis_index(axis) == 0, x,
                                 jnp.zeros_like(x))
                return jax.lax.psum(root, axis)
        return wrap(body, P())
    raise ValueError(f"unknown op {op!r} (choose from {OPS})")


def sweep(ops: List[str], min_pow: int = 12, max_pow: int = 26,
          trials: int = 10, warmups: int = 3, dtype: str = "bfloat16",
          axis: str = "x", mesh=None,
          print_table: bool = True) -> List[Dict]:
    """Run the sweep; returns one record per (op, size) with latency
    and algbw/busbw in Gbps (NCCL-style accounting)."""
    dt = jnp.dtype(dtype)
    if mesh is None:
        devs = np.asarray(jax.devices())
        mesh = jax.sharding.Mesh(devs, (axis,))
    n = mesh.shape[axis]
    out: List[Dict] = []
    for op in ops:
        fn, in_sh = _build_op(op, mesh, axis)
        if print_table:  # tpulint: disable-file=print — bench CLI table output
            print(f"\n---- {op} over {n} devices "
                  f"({jax.devices()[0].platform}) ----")
            print(f"{'size':>10} {'latency':>12} {'algbw Gbps':>12} "
                  f"{'busbw Gbps':>12}")
        for p in range(min_pow, max_pow + 1):
            nbytes = 1 << p
            elems = max(n * n, nbytes // dt.itemsize)
            # reduce_scatter/all_to_all split the LOCAL shard n ways
            # again, so round to a multiple of n^2 (matters on
            # non-power-of-two meshes)
            elems = (elems // (n * n)) * (n * n)
            x = jax.device_put(
                jnp.ones((elems,), dt), in_sh)
            for _ in range(warmups):
                x = fn(x)
            jax.block_until_ready(x)
            t0 = time.perf_counter()
            for _ in range(trials):
                x = fn(x)
            jax.block_until_ready(x)
            lat = (time.perf_counter() - t0) / trials
            size_bytes = elems * dt.itemsize
            algbw, busbw = calc_bw_log(op, size_bytes, lat, n)
            # 4 decimals: sub-0.01 Gbps links (emulated meshes) must
            # not quantize to a 0.0 record
            rec = dict(op=op, bytes=size_bytes, latency_us=lat * 1e6,
                       algbw_gbps=round(algbw, 4),
                       busbw_gbps=round(busbw, 4), devices=n)
            out.append(rec)
            if print_table:
                print(f"{convert_size(size_bytes):>10} "
                      f"{lat * 1e6:>10.1f}us {algbw:>12.2f} "
                      f"{busbw:>12.2f}")
    return out


def overlap_bench(mesh=None, axis: str = "x", rows: int = 256,
                  k: int = 4096, nmodel: int = 1024, tiles: int = 4,
                  trials: int = 20, warmups: int = 3,
                  dtype: str = "float32",
                  profile_dir: Optional[str] = None) -> Dict:
    """Overlapped-vs-serial matmul+allreduce microbench — the T3 leg
    (arxiv 2401.16677) the multichip driver records.

    One row-parallel GEMM ([rows, k] x [k, nmodel], contraction sharded
    over ``axis``) under four comm plans: serial psum (the GSPMD
    shape), tile-decomposed psum (``tiles`` tiles — exact, bitwise),
    tile-decomposed ppermute ring, and tile-decomposed + int8 quantized
    wire (EQuARX, arxiv 2506.17615).  Values are cross-checked before
    timing (exact plans bitwise vs serial; the quantized plan within
    its error bound), so a bench capture that would publish wrong
    numerics fails instead.

    Returns metrics with a direction (``*_ms`` down-is-better,
    ``*_speedup`` up) plus the modeled wire-byte halving.  With
    ``profile_dir``, the timed overlapped run executes inside a
    ``jax.profiler`` trace so ``tools/tracemerge`` can render the tile
    scopes against the GEMM device activity."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .overlap import (overlapped_matmul_allreduce, wire_bytes)

    dt = jnp.dtype(dtype)
    if mesh is None:
        devs = np.asarray(jax.devices())
        mesh = jax.sharding.Mesh(devs, (axis,))
    n = mesh.shape[axis]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, k), dt)
    w = jnp.asarray(rng.randn(k, nmodel), dt)
    x = jax.device_put(x, NamedSharding(mesh, P(None, axis)))
    w = jax.device_put(w, NamedSharding(mesh, P(axis, None)))

    def build(fn):
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(None, axis), P(axis, None)),
            out_specs=P(), check_vma=False))

    def serial_body(a, b):
        with jax.named_scope("serial_mm_ar"):
            return jax.lax.psum((a @ b).astype(dt), axis)

    plans = {
        "serial": build(serial_body),
        "overlapped": build(lambda a, b: overlapped_matmul_allreduce(
            a, b, axis, tiles=tiles)),
        "ring": build(lambda a, b: overlapped_matmul_allreduce(
            a, b, axis, tiles=tiles, strategy="ring")),
        "quant": build(lambda a, b: overlapped_matmul_allreduce(
            a, b, axis, tiles=tiles, quant_bits=8)),
    }
    # numerics gate before timing — EVERY rung: exact plans bitwise,
    # the ring close (same summands, rotated rounding order), quant
    # inside its error bound
    ref = np.asarray(plans["serial"](x, w))
    if not np.array_equal(np.asarray(plans["overlapped"](x, w)), ref):
        raise AssertionError("overlapped plan is not bitwise-equal to "
                             "the serial all-reduce")
    if not np.allclose(np.asarray(plans["ring"](x, w)), ref,
                       rtol=1e-4, atol=1e-4):
        raise AssertionError("ring plan diverged from the serial "
                             "all-reduce beyond rounding order")
    bound = n * np.abs(ref).max() / 127.0 + 1e-6
    if np.abs(np.asarray(plans["quant"](x, w)) - ref).max() > bound:
        raise AssertionError("quantized plan exceeded its error bound")

    out: Dict = {"devices": int(n), "rows": rows, "k": k, "n": nmodel,
                 "tiles": tiles, "dtype": str(dt)}
    for name, fn in plans.items():
        y = fn(x, w)
        for _ in range(warmups):
            y = fn(x, w)
        jax.block_until_ready(y)
        prof = (jax.profiler.trace(profile_dir)
                if profile_dir and name == "overlapped" else None)
        if prof is not None:
            prof.__enter__()
        t0 = time.perf_counter()
        for _ in range(trials):
            y = fn(x, w)
        jax.block_until_ready(y)
        ms = (time.perf_counter() - t0) / trials * 1e3
        if prof is not None:
            prof.__exit__(None, None, None)
        out[f"comm_{name}_ms"] = round(ms, 4)
    for name, metric in (("overlapped", "comm_overlap_speedup"),
                         ("ring", "comm_ring_speedup"),
                         ("quant", "comm_quant_speedup")):
        out[metric] = round(
            out["comm_serial_ms"] / max(out[f"comm_{name}_ms"], 1e-9), 4)
    out["wire_bytes_exact"] = wire_bytes(
        "all_reduce", rows * nmodel, dt.itemsize, n)
    out["wire_bytes_quant"] = wire_bytes(
        "all_reduce", rows * nmodel, dt.itemsize, n, quant_bits=8)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="deepspeed_tpu.comm.bench",
        description="collective sweep microbenchmark (ds_bench analog)")
    ap.add_argument("--ops", default="all_reduce",
                    help=f"comma list from {','.join(OPS)} or 'all'")
    ap.add_argument("--minsize", type=int, default=12,
                    help="log2 of smallest message bytes")
    ap.add_argument("--maxsize", type=int, default=26,
                    help="log2 of largest message bytes")
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--warmups", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line per record instead of a table")
    ap.add_argument("--multihost", action="store_true",
                    help="call jax.distributed.initialize() first "
                         "(under the launcher/runner env)")
    ap.add_argument("--overlap", action="store_true",
                    help="run the overlapped-vs-serial matmul+allreduce "
                         "leg (T3) instead of the op sweep")
    ap.add_argument("--tiles", type=int, default=4)
    ap.add_argument("--profile-dir", default=None,
                    help="with --overlap: jax.profiler trace dir for "
                         "the overlapped timed run")
    args = ap.parse_args(argv)
    if args.multihost:
        jax.distributed.initialize()
    if args.overlap:
        rec = overlap_bench(tiles=args.tiles, trials=args.trials,
                            warmups=args.warmups, dtype=args.dtype,
                            profile_dir=args.profile_dir)
        print(json.dumps(rec))  # tpulint: disable=print — the leg's one JSON line
        return 0
    ops = list(OPS) if args.ops == "all" else args.ops.split(",")
    recs = sweep(ops, args.minsize, args.maxsize, args.trials,
                 args.warmups, args.dtype, print_table=not args.json)
    if args.json:
        for r in recs:
            print(json.dumps(r))
    return 0


if __name__ == "__main__":
    from ..platform.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
