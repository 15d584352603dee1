"""``--rehearse`` ends in one well-formed line and no device metric."""

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


@pytest.mark.parametrize("cell,trace", [("train-1chip", 0), ("serve-decode", 1),
                                        ("serve-prefill", 0),
                                        ("train-zero3-4chip", 1)])
def test_rehearsal_is_one_well_formed_line_without_metrics(cell, trace):
    p = run("--workload", cell, "--seed", str(2 ** 31 + 17), "--seconds", "2",
            "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    checks = last["compared_with_reference"]["checks"]
    assert all(c["ok"] for c in checks.values())
    assert_cpu_form_of_the_self_check(p, last, trace)


def assert_cpu_form_of_the_self_check(p, last, trace):
    """run.py checked this line before it printed it: off the chip it
    carries no busy_s; the numbers compared come last in it and are the
    last lines on standard error."""
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    assert list(last)[-1] == "compared_with_reference"
    checks = last["compared_with_reference"]["checks"]
    tail = p.stderr.strip().splitlines()[-len(checks):]
    assert [ln.split(":")[0] for ln in tail] \
        == ["compared " + name for name in checks]
    assert all(f"limit={c['tol']!r}" in ln
               for ln, c in zip(tail, checks.values()))


def test_no_cpu_fallback_for_a_measurement():
    p = run("--workload", "train-1chip", "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip() or "correct" not in p.stdout.splitlines()[-1]


def test_unknown_workload_is_refused():
    p = run("--workload", "no-such-cell", "--seed", "1", "--rehearse")
    assert p.returncode != 0
