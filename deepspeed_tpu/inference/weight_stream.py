"""Per-layer NVMe weight streaming for serving.

TPU-native analog of the reference's NVMe parameter path
(``runtime/swap_tensor/partitioned_param_swapper.py:290`` — layer
parameters live on NVMe and stream through host DRAM just-in-time; the
ZeRO-Inference "20x bigger model" NVMe leg).  XLA cannot do file I/O
mid-graph, so the layer scan fetches each layer's payload with
``jax.experimental.io_callback``: the compiled forward blocks on a host
callback that reads that layer's file(s) via the C++ aio pool and
returns the arrays — HBM ever holds ONE layer's weights (plus the KV
cache), host DRAM holds none persistently.

Layout: one ``.npy`` file per (layer, leaf).  With ZeRO-Inference
quantization the QUANTIZED payloads are what's spilled, so the stream is
int8/int4-sized; dequantization happens on device after the fetch.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class NVMeWeightStore:
    """Spill a stacked per-layer pytree to per-layer files and fetch one
    layer at a time from inside a compiled scan."""

    # set by the engine at spill time when every quantized payload is a
    # layout the mixed-input GEMM family consumes (row-wise int8 or
    # packed row-wise int4)
    mixed_gemm_eligible = False
    qmeta = None
    # set by the engine for SPMD serving: the fetch callback must pin to
    # ONE device (XLA's partitioner rejects replicated side-effecting
    # HLOs and ordered tokens inside sharded loops); the fetched layer
    # is broadcast to the mesh by GSPMD at its first partitioned use
    spmd_device = None

    def __init__(self, path: str, num_layers: int):
        self.dir = path
        self.num_layers = num_layers
        os.makedirs(path, exist_ok=True)
        self._treedef = None
        self._shapes: Tuple[jax.ShapeDtypeStruct, ...] = ()
        self._offsets: Dict[Tuple[int, int], int] = {}
        from ..ops.aio import AsyncIOHandle
        self._aio = AsyncIOHandle(thread_count=2)

    # ---- spill -----------------------------------------------------------
    def spill(self, stacked_tree: Any) -> None:
        """``stacked_tree``: pytree whose array leaves have a leading
        ``num_layers`` dim.  Writes layer slices; frees nothing itself —
        the caller drops its references."""
        leaves, self._treedef = jax.tree.flatten(stacked_tree)
        shapes = []
        for j, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            assert arr.shape[0] == self.num_layers, (
                f"leaf {j} has no leading layer dim: {arr.shape}")
            shapes.append(jax.ShapeDtypeStruct(arr.shape[1:], arr.dtype))
            for li in range(self.num_layers):
                path = self._file(li, j)
                np.save(path, arr[li])
                # payload offset cached ONCE: the per-token fetch path
                # must not reopen/parse headers (or lean on numpy's
                # private header API)
                with open(path, "rb") as f:
                    f.seek(0, 2)
                    self._offsets[(li, j)] = f.tell() - arr[li].nbytes
        self._shapes = tuple(shapes)

    def _file(self, li: int, j: int) -> str:
        return os.path.join(self.dir, f"layer{li:04d}_leaf{j:03d}.npy")

    # ---- fetch -----------------------------------------------------------
    def result_shapes(self):
        """Pytree of ShapeDtypeStructs for one layer's payload."""
        return jax.tree.unflatten(self._treedef, list(self._shapes))

    def _fetch_host(self, li) -> Tuple[np.ndarray, ...]:
        li = int(li)
        out = []
        for j, sds in enumerate(self._shapes):
            buf = np.empty(sds.shape, sds.dtype)
            # the aio pool reads the payload region (offset cached at
            # spill) in parallel chunks
            self._aio.sync_pread(buf.view(np.uint8).reshape(-1),
                                 self._file(li, j),
                                 offset=self._offsets[(li, j)])
            out.append(buf)
        return tuple(out)

    def restore_stacked(self) -> Any:
        """Read every layer back through the aio pool and rebuild the
        stacked pytree RESIDENT — the scale-up cold-start path
        (docs/SERVING.md "Disaggregated pools & elasticity"): a new
        replica materializes its block weights from the store spilled
        once at deploy instead of re-tracing checkpoint load, and
        because the weights end resident (``icfg.weight_stream`` unset
        on the new engine) the mode streaming forces off —
        speculative decode — is not forced on it."""
        assert self._treedef is not None, "restore before spill"
        leaves = []
        for j, sds in enumerate(self._shapes):
            arr = np.empty((self.num_layers,) + tuple(sds.shape),
                           sds.dtype)
            for li in range(self.num_layers):
                self._aio.sync_pread(
                    arr[li].view(np.uint8).reshape(-1),
                    self._file(li, j),
                    offset=self._offsets[(li, j)])
            leaves.append(jnp.asarray(arr))
        return jax.tree.unflatten(self._treedef, leaves)

    def fetch_layer(self, li):
        """In-graph: returns this layer's payload pytree (device arrays
        materialized from the host callback)."""
        if self.spmd_device is not None:
            from jax.sharding import SingleDeviceSharding
            flat = jax.experimental.io_callback(
                self._fetch_host, self._shapes, li,
                sharding=SingleDeviceSharding(self.spmd_device),
                ordered=False)   # pure idempotent reads: order-free
        else:
            flat = jax.experimental.io_callback(
                self._fetch_host, self._shapes, li, ordered=True)
        return jax.tree.unflatten(self._treedef, list(flat))
