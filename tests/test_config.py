"""Config system tests (reference analog: tests exercising runtime/config.py
batch triangulation + sub-config validation)."""

import json

import pytest

from deepspeed_tpu.config import Config, ConfigError, load_config


def test_defaults():
    cfg = load_config({"train_micro_batch_size_per_device": 4})
    assert cfg.zero_optimization.stage == 0
    assert cfg.precision == "fp32"
    assert cfg.optimizer.type == "adamw"


def test_deepspeed_alias_micro_batch():
    cfg = load_config({"train_micro_batch_size_per_gpu": 2})
    assert cfg.train_micro_batch_size_per_device == 2


@pytest.mark.parametrize("cfg", [
    {"train_batch_sizes": 8},
    # accepted and consumed nowhere until PR 40 took the field away
    {"wall_clock_breakdown": True},
])
def test_unknown_key_rejected(cfg):
    with pytest.raises(ConfigError, match="Unknown key"):
        load_config(cfg)


def test_duplicate_json_key_rejected(tmp_path):
    p = tmp_path / "ds.json"
    p.write_text('{"train_batch_size": 8, "train_batch_size": 16}')
    with pytest.raises(ConfigError, match="Duplicate"):
        load_config(str(p))


def test_batch_triangulation_infer_gas():
    cfg = load_config({"train_batch_size": 32,
                       "train_micro_batch_size_per_device": 2})
    train, micro, gas = cfg.resolve_batch_sizes(dp_world_size=4)
    assert (train, micro, gas) == (32, 2, 4)


def test_batch_triangulation_infer_train():
    cfg = load_config({"train_micro_batch_size_per_device": 2,
                       "gradient_accumulation_steps": 3})
    train, micro, gas = cfg.resolve_batch_sizes(dp_world_size=4)
    assert (train, micro, gas) == (24, 2, 3)


def test_batch_triangulation_inconsistent():
    cfg = load_config({"train_batch_size": 30,
                       "train_micro_batch_size_per_device": 2,
                       "gradient_accumulation_steps": 4})
    with pytest.raises(ConfigError, match="Inconsistent"):
        cfg.resolve_batch_sizes(dp_world_size=4)


def test_precision_exclusive():
    cfg = load_config({"train_micro_batch_size_per_device": 1,
                       "fp16": {"enabled": True}, "bf16": {"enabled": True}})
    with pytest.raises(ConfigError):
        _ = cfg.precision


def test_zero_config():
    cfg = load_config({
        "train_micro_batch_size_per_device": 1,
        "zero_optimization": {
            "stage": 3,
            "offload_optimizer": {"device": "cpu"},
            "zero_quantized_weights": True,
        },
    })
    assert cfg.zero_optimization.stage == 3
    assert cfg.zero_optimization.offload_optimizer.device == "cpu"
    assert cfg.zero_optimization.zero_quantized_weights


def test_bad_zero_stage():
    with pytest.raises(ConfigError):
        load_config({"train_micro_batch_size_per_device": 1,
                     "zero_optimization": {"stage": 5}})


def test_roundtrip():
    d = {"train_batch_size": 8, "bf16": {"enabled": True},
         "mesh": {"fsdp": 4, "data": 2}}
    cfg = load_config(d)
    d2 = cfg.to_dict()
    assert d2["bf16"]["enabled"] is True
    assert d2["mesh"]["fsdp"] == 4
    # round-trip through json
    cfg2 = load_config(json.loads(json.dumps(d2)))
    assert cfg2.mesh.fsdp == 4


def test_pipeline_interleaved_rejected():
    """Advertising-but-ignoring a schedule is worse than rejecting it."""
    from deepspeed_tpu.config.config import ConfigError, load_config

    import pytest
    with pytest.raises(ConfigError, match="1f1b"):
        load_config({"train_micro_batch_size_per_device": 1,
                     "pipeline": {"stages": 2, "schedule": "interleaved"}})
