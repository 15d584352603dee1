"""``serve-window-longgen`` rehearsed on the CPU, traced and untraced
(``benchmarks/run.py --rehearse``).  Under ``tests/`` so that tier-1
counts it: ``benchmarks/tests/`` is not on the driver's line."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(*argv):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "benchmarks/run.py", *argv],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_longgen_cell_rehearses(trace):
    p = run("--workload", "serve-window-longgen", "--seed", str(2 ** 31 + 29),
            "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    checks = last["compared_with_reference"]["checks"]
    # serve.py's two, and the driver's own with the routing followed
    assert set(checks) == {"logits_prefill", "logits_decode",
                           "followed_prefill", "followed_decode",
                           "past_window_prefill", "past_window_decode",
                           "routing_shortfall"}
    assert all(c["ok"] for c in checks.values())
    followed = next(n for n in lines
                    if n.get("note") == "reference_followed")
    # a window and a half in one chunk of the toy budget, three fed
    assert followed["past_window"]["steps"] == 4
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    # the reference says how close the chosen and the next expert stood,
    # in the four expert layers behind the dense one
    routers = [n for n in lines if n.get("note") == "reference_router"]
    assert len(routers) == 2 and all(n["layers"] == 4 for n in routers)
    values = next(n for n in lines
                  if n.get("note") == "rehearsal_values")["values"]
    if trace:
        # the per-layer readers that need no device: the program's own
        assert values["longgen.serve_window_compiles"]["value"] == 0
        assert values["longgen.batch_tokens_per_step"]["value"] > 0
        assert values["longgen.moe_expert_load_max_over_mean"]["value"] >= 1.0
    else:
        assert values["setup_s"]["value"] > 0
        assert values["out_tokens_per_s"]["value"] > 0
