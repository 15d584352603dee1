"""``serve-mla-shared-docs`` rehearsed on the CPU, traced and untraced: the
cases ``test_rehearse.py`` would hold if a PR that adds a cell could edit
it, and the traffic's hit share counted."""

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
def test_shared_docs_cell_rehearses(trace):
    p = run("--workload", "serve-mla-shared-docs", "--seed",
            str(2 ** 31 + 56), "--seconds", "2", "--trace", str(trace),
            "--rehearse")
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
                           "shared_prefix_prefill", "shared_prefix_decode",
                           "reused_slots_prefill", "reused_slots_decode",
                           "routing_shortfall"}
    assert all(c["ok"] for c in checks.values())
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    assert p.stderr.strip().splitlines()[-1].startswith(
        "compared routing_shortfall:")
    # the long prompt went over several of the engine's steps, the second
    # sequence onto its blocks in ONE (and its three fed tokens)
    read = next(n for n in lines
                if n.get("note") == "reference_latent_groups")["read"]
    assert read["chunked"]["steps"] >= 3 + 3
    assert read["shared"]["steps"] == 1 + 3
    assert read["again_sample0"]["prefill"] == read["sample0"]["prefill"]
    # the reference says where the shortfall's first form read most, and
    # how far apart its last open and first closed group lay there
    groups = [n for n in lines if n.get("note") == "reference_groups"]
    assert len(groups) == len(read)
    assert all(g["first_form"] >= g["shortfall_there"] >= 0
               and 0 <= g["widest_gap_swapped"] <= 1 and g["swaps"] >= 0
               and 0 <= g["group_gap_there"] <= 1 for g in groups)
    times = next(n for n in lines if n.get("note") == "latent_groups_times")
    # the traffic's two documents of four blocks of 16 were prefilled
    # and left indexed
    assert times["documents_blocks"] == 2 * 4
    assert {"routing_step", "documents", "reference.shared",
            "reference.waited_for"} <= set(times["seconds"])
    # three admissions in four alias a document of 64 of their 72-104
    # tokens: the engine's own counters over the window
    window = next(n for n in lines if n.get("note") == "window")["engine"]
    share = window["cached_tokens"] / window["prompt_tokens"]
    assert 0.75 * 64 / 104 - 0.08 < share < 0.75 * 64 / 72 + 0.08
    values = next(n for n in lines
                  if n.get("note") == "rehearsal_values")["values"]
    if trace:
        # the per-layer readers that need no device: the program's own;
        # the step's parts have no device trace to read on the CPU
        assert values["moe.serve_window_compiles"]["value"] == 0
        assert values["batch_tokens_per_step"]["value"] > 0
        assert values["moe_expert_load_max_over_mean"]["value"] >= 1.0
        assert next(n for n in lines
                    if n.get("note") == "shareddocs_step_parts") \
            == {"note": "shareddocs_step_parts"}
    else:
        assert values["setup_s"]["value"] > 0
        assert values["out_tokens_per_s"]["value"] > 0
