"""``serve-ssm-moe-rag`` rehearsed on the CPU, traced and untraced: the
cases ``test_rehearse.py`` would hold if a PR that adds a cell could edit
it."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(*argv):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "benchmarks/run.py", *argv],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_rag_cell_rehearses(trace):
    p = run("--workload", "serve-ssm-moe-rag", "--seed", str(2 ** 31 + 52),
            "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    checks = last["compared_with_reference"]["checks"]
    assert set(checks) == {"logits_prefill", "logits_decode",
                           "followed_prefill", "followed_decode",
                           "chunked_prefill", "chunked_decode",
                           "reused_slots_prefill", "reused_slots_decode",
                           "routing_shortfall"}
    assert all(c["ok"] for c in checks.values())
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    assert p.stderr.strip().splitlines()[-1].startswith(
        "compared routing_shortfall:")
    # the long prompt went over several of the engine's steps, and the
    # first sample again through the slot the others left
    read = next(n for n in lines
                if n.get("note") == "reference_state_share")["read"]
    assert read["chunked"]["steps"] >= 3 + 3
    assert read["again_sample0"]["prefill"] == read["sample0"]["prefill"]
    # the division of memory, from the program's own gauges: state rows
    # for the five Mamba layers, blocks for the one attention layer
    mem = next(n for n in lines if n.get("note") == "state_share_memory")
    assert (mem["state_layers"], mem["block_layers"]) == (5, 1)
    assert mem["state_rows_bytes"] == 5 * 9 * 2 * (8 * 16 * 16 + 4 * 160)
    assert mem["block_pool_bytes"] == 97 * 16 * 2 * 2 * 16 * 2
    times = next(n for n in lines
                 if n.get("note") == "state_share_times")["seconds"]
    assert {"routing_step", "reference.chunked",
            "reference.waited_for"} <= set(times)
    values = next(n for n in lines
                  if n.get("note") == "rehearsal_values")["values"]
    if trace:
        # the per-layer readers that need no device: the program's own;
        # the step's parts have no device trace to read on the CPU
        assert values["moe.serve_window_compiles"]["value"] == 0
        assert values["batch_tokens_per_step"]["value"] > 0
        assert values["moe_expert_load_max_over_mean"]["value"] >= 1.0
        assert next(n for n in lines
                    if n.get("note") == "ragmoe_step_parts") \
            == {"note": "ragmoe_step_parts"}
    else:
        assert values["setup_s"]["value"] > 0
        assert values["out_tokens_per_s"]["value"] > 0
