"""Test harness configuration.

The reference tests "multi-node" logic by spawning N local processes over NCCL
loopback (tests/unit/common.py:117).  The TPU analog (SURVEY.md §4): run
single-process with a **virtual 8-device CPU mesh** via
``--xla_force_host_platform_device_count``, so every sharding/collective path
compiles and executes without hardware.
"""

import os

# must be set before jax import; force CPU regardless of ambient settings so
# the suite always sees the 8-device virtual mesh
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
    # the concurrency-optimized CPU thunk scheduler may issue independent
    # collectives in divergent orders across the virtual devices and
    # deadlock the in-process rendezvous (seen with pipeline x seq
    # programs); a real TPU core issues in program order and is unaffected
    flags += " --xla_cpu_enable_concurrency_optimized_scheduler=false"
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# JAX_PLATFORMS=cpu above is enough when jax is first imported here; the
# config update also covers a plugin that imported jax before this file
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def mesh8():
    """A data=2 × fsdp=2 × tensor=2 mesh on 8 virtual devices."""
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.comm import MeshTopology

    return MeshTopology.build(MeshConfig(data=2, fsdp=2, tensor=2))


@pytest.fixture
def fsdp8():
    """A pure fsdp=8 mesh (ZeRO-style)."""
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.comm import MeshTopology

    return MeshTopology.build(MeshConfig(data=1, fsdp=8))


# the suite's longest files, longest first (seconds of PR 49's whole run
# on six workers: 576, 557, 484, 470, 440, 381, 350, 284, 273, 272, 257,
# 244, 234, 224, 219, 197, 196, 195)
HEAVY_FILES = (
    "test_tpu_compile", "test_paged_attention", "test_zero3_placement",
    "test_ling", "test_trinity", "test_chip_smoke",
    "test_paged_attention_step", "test_pipeline", "test_loadgen",
    "test_data_efficiency", "test_trinity_serving", "test_served_ahead",
    "test_inference", "test_xla_attention", "test_falcon_h1",
    "test_comm_overlap", "test_olmoe", "test_layer_unroll")


def pytest_addoption(parser):
    parser.addoption("--nightly", action="store_true", default=False,
                     help="also run tests marked nightly (slow/spawning)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "nightly: slow tests excluded from the quick suite "
        "(run with --nightly)")


def pytest_collection_modifyitems(config, items):
    # the driver runs ``--dist loadfile``: a file is one worker's, handed
    # out in collection order, and a long file handed out last (the
    # alphabet put ``test_zero3_placement.py``, eight minutes, there)
    # runs behind the suite instead of beside it.  The longest files
    # first, by their seconds in the last whole run (PR 49: 8,930 s
    # summed over six workers that took 1,777 s, 1,488 s each if even)
    rank = {"tests/%s.py" % name: i for i, name in enumerate(HEAVY_FILES)}
    items.sort(key=lambda item: rank.get(item.nodeid.split("::")[0],
                                         len(rank)))
    if config.getoption("--nightly"):
        return
    skip = pytest.mark.skip(reason="nightly-only (pass --nightly)")
    for item in items:
        if "nightly" in item.keywords:
            item.add_marker(skip)
