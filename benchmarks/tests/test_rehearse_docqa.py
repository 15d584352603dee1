"""``serve-mla-docqa`` rehearsed on the CPU, traced and untraced: the
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
def test_docqa_cell_rehearses(trace):
    p = run("--workload", "serve-mla-docqa", "--seed", str(2 ** 31 + 49),
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
                           "routing_shortfall"}
    assert all(c["ok"] for c in checks.values())
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    assert p.stderr.strip().splitlines()[-1].startswith(
        "compared routing_shortfall:")
    # the long prompt went over several of the engine's steps
    read = next(n for n in lines if n.get("note") == "reference_latent")
    assert read["read"]["chunked"]["steps"] >= 3 + 3
    values = next(n for n in lines
                  if n.get("note") == "rehearsal_values")["values"]
    if trace:
        # the per-layer readers that need no device: the program's own
        assert values["moe.serve_window_compiles"]["value"] == 0
        assert values["batch_tokens_per_step"]["value"] > 0
        assert values["moe_expert_load_max_over_mean"]["value"] >= 1.0
    else:
        assert values["setup_s"]["value"] > 0
        assert values["out_tokens_per_s"]["value"] > 0
