"""Platform abstraction — the accelerator interface, TPU-native.

Analog of the reference's ``DeepSpeedAccelerator`` ABC
(``accelerator/abstract_accelerator.py:10``, ~70 methods) and
``get_accelerator()`` singleton (``accelerator/real_accelerator.py:51``).
Most of the ABC's surface (streams, events, graphs) has no TPU meaning —
XLA owns scheduling — so this interface keeps the parts that do: device
identity/count, memory stats, dtype support, RNG seeding, host ("pinned")
memory placement, and synchronization.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


class TPUPlatform:
    """Singleton returned by :func:`get_platform`."""

    _name = "tpu"

    # ---- identity --------------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        devs = jax.local_devices()
        if device_index is None:
            return self.platform_kind()
        return str(devs[device_index])

    def platform_kind(self) -> str:
        return jax.devices()[0].platform

    def is_available(self) -> bool:
        return len(jax.devices()) > 0

    def device_count(self) -> int:
        return jax.device_count()

    def local_device_count(self) -> int:
        return jax.local_device_count()

    def process_index(self) -> int:
        return jax.process_index()

    def process_count(self) -> int:
        return jax.process_count()

    def communication_backend_name(self) -> str:
        # XLA emits collectives directly; there is no separate comm library
        # (reference: abstract_accelerator.py:202 returns 'nccl').
        return "xla"

    # ---- synchronization -------------------------------------------------
    def synchronize(self) -> None:
        jax.effects_barrier()

    # ---- memory ----------------------------------------------------------
    def memory_stats(self, device_index: int = 0) -> Dict[str, Any]:
        try:
            return jax.local_devices()[device_index].memory_stats() or {}
        # capability probe on a hot path (polled per step by monitors)
        except Exception:  # tpulint: disable=silent-except
            return {}

    def memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("peak_bytes_in_use", 0))

    def total_memory(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: int = 0) -> int:
        s = self.memory_stats(device_index)
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    # ---- host memory ("pinned") placement -------------------------------
    def host_sharding(self, sharding):
        """Host-DRAM variant of a sharding (for offloaded states)."""
        return sharding.with_memory_kind("pinned_host")

    def to_host(self, x):
        """Move an array to pinned host memory, keeping its layout."""
        return jax.device_put(
            x, jax.sharding.SingleDeviceSharding(
                jax.local_devices()[0], memory_kind="pinned_host"))

    def supports_host_offload(self) -> bool:
        try:
            dev = jax.local_devices()[0]
            return "pinned_host" in [m.kind for m in dev.addressable_memories()]
        except Exception:  # tpulint: disable=silent-except — capability probe
            return False

    # ---- dtypes ----------------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True  # every TPU generation we target

    def is_fp16_supported(self) -> bool:
        return True  # storage/compute dtype; MXU accumulates fp32 anyway

    def supported_dtypes(self) -> List[Any]:
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def preferred_dtype(self):
        return jnp.bfloat16

    # ---- RNG -------------------------------------------------------------
    def rng_key(self, seed: int) -> jax.Array:
        return jax.random.key(seed)

    # ---- misc ------------------------------------------------------------
    def on_tpu(self) -> bool:
        return self.platform_kind() == "tpu"

    def visible_devices_env(self) -> str:
        return os.environ.get("JAX_VISIBLE_DEVICES", "")


@functools.lru_cache(None)
def get_platform() -> TPUPlatform:
    """The ``get_accelerator()`` analog (reference: real_accelerator.py:51)."""
    return TPUPlatform()
