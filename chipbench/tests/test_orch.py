"""The orch driver's window and ``correct`` path at smoke size on the CPU,
called below the command's look for a chip; and the command itself, which
refuses the CPU."""
import json
import os
import subprocess
import sys

from chipbench import harness
from chipbench.tests import smoke


def test_the_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(harness.HERE / "run.py"),
                        "--workload", smoke.CELL, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics():
    res = smoke.run_cell(traced=False)
    assert json.loads(json.dumps(res)) == res
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] % 3 == 0
    assert res["attempted"] >= 3
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_a_traced_run_reports_span_metrics_and_a_breakdown():
    res = smoke.run_cell(traced=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    # the CPU has no device plane: trace metrics find nothing and are left
    # out, never reported as 0
    for name in ("train_s_per_round", "publish_s_per_round",
                 "score_s_per_round", "merge_s_per_round",
                 "engine_s_per_round", "round_mfu"):
        assert got[name]["value"] > 0, name
    assert "wsum_q8_roofline" not in got and "device_idle_share" not in got
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
