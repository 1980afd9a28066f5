"""chip_smoke.py stays runnable: its CPU rehearsal drives every phase
at tiny sizes, and without a TPU it refuses to run at all."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args],
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=600)


@pytest.mark.parametrize("chips, phases", [
    ("1", ["a_deep_replay", "b_lane_packed", "c_service_rebuild",
           "d_server"]),
    ("4", ["four_chip"]),
])
def test_cpu_rehearsal_runs_every_phase(chips, phases):
    r = _run("--cpu-rehearsal", "--chips", chips)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    got = [ln for ln in lines if "phase" in ln]
    assert [ln["phase"] for ln in got] == phases
    for ln in got:
        assert ln["mismatches"] == 0, ln
        assert ln["rows_compared"] > 0, ln
        for key in ("sizes", "compile_s", "wall_s", "peak_bytes_in_use"):
            assert key in ln, ln
    last = lines[-1]
    assert last["ok"] is True and last["rehearsal"] == "cpu"
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": int(chips)}
    assert '"platform": "tpu"' not in r.stdout


def test_refuses_to_run_without_a_tpu():
    r = _run()
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr
