"""Two-process CPU multi-host test.

Spawns two ``jax.distributed`` CPU processes (Gloo collectives, 2
virtual devices each), trains two steps, round-trips a checkpoint, and
asserts resumed-vs-continued step parity.  The child lives in
``tests/multihost_child.py``.
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.nightly


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_train_and_checkpoint(tmp_path):
    child = os.path.join(os.path.dirname(__file__), "multihost_child.py")
    port = str(_free_port())
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, child, str(pid), port, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd="/root/repo") for pid in (0, 1)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    assert all(p.returncode == 0 for p in procs), "\n---\n".join(outs)
    assert "RANK0 OK" in outs[0] and "RANK1 OK" in outs[1]
    # the psum'd loss is identical on both hosts
    l0 = [ln for ln in outs[0].splitlines() if "LOSSES" in ln][0].split()
    l1 = [ln for ln in outs[1].splitlines() if "LOSSES" in ln][0].split()
    assert l0[2:] == l1[2:], (l0, l1)
