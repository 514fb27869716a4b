"""The harness's own arithmetic, and a cell that exists only as new files."""
import json
import time
import types

import pytest

from chipbench import harness


def test_window_starts_rounds_until_the_seconds_are_spent():
    w = harness.Window(10.0)
    w.open(100.0)
    assert w.round_ended(104.0)          # 4 s in: start another
    assert w.round_ended(109.5)          # 9.5 s in: still under 10
    assert not w.round_ended(113.0)      # 13 s: the window closes here
    assert w.rounds == 3
    assert w.window_s == pytest.approx(13.0)
    assert w.walls == pytest.approx([4.0, 5.5, 3.5])
    run = harness.Run(window=w)
    assert run.round_s == pytest.approx(13.0 / 3)


def test_a_window_holds_at_least_one_round():
    w = harness.Window(1.0)
    w.open(0.0)
    assert not w.round_ended(30.0)
    assert w.rounds == 1 and w.window_s == 30.0


def test_spans_count_self_time_per_layer():
    spans = harness.Spans()
    clock = iter([0.0, 1.0, 3.0, 10.0, 20.0, 21.0])
    real = time.perf_counter
    time.perf_counter = lambda: next(clock)
    try:
        inner = spans.wrap("publish", lambda: None)
        outer = spans.wrap("train", lambda: inner())
        spans.recording = True
        outer()                      # train 0..10 holding publish 1..3
        spans.recording = False
        spans.wrap("train", lambda: None)()   # 20..21, not recorded
    finally:
        time.perf_counter = real
    assert spans.self_s == {"train": 8.0, "publish": 2.0}
    assert spans.calls == {"train": 1, "publish": 1}


def test_patches_are_undone():
    obj = types.SimpleNamespace(f=1)
    with harness.Patches() as p:
        p.set(obj, "f", 2)
        p.set(obj, "f", 3)
        assert obj.f == 3
    assert obj.f == 1


def test_decide_needs_every_number_within_its_limit():
    ok, checks = harness.decide({"a": 0.1, "b": 0.0, "x": 9.0},
                                {"a": 0.2, "b": 0})
    assert ok and set(checks) == {"a", "b"}
    assert checks["a"] == {"value": 0.1, "limit": 0.2}
    assert not harness.decide({"a": 0.3}, {"a": 0.2})[0]
    assert not harness.decide({"a": float("nan")}, {"a": 0.2})[0]


MANIFEST = {
    "end_to_end": [
        {"name": "round_s", "unit": "s"},
        {"name": "setup_s", "unit": "s"},
        {"name": "host_gb", "unit": "GB", "workloads": ["c.a"]}],
    "per_layer": [
        {"name": "every", "moves": "round_s"},
        {"name": "only_b", "moves": "round_s", "workloads": ["c.b"]},
        {"name": "host_layer", "moves": "host_gb"}],
}


def test_metrics_of_follows_workloads_and_moves():
    names = lambda cell, traced: [m["name"] for m in
                                  harness.metrics_of(MANIFEST, cell, traced)]
    assert names("c.a", False) == ["round_s", "setup_s", "host_gb"]
    assert names("c.b", False) == ["round_s", "setup_s"]
    assert names("c.a", True) == ["every", "host_layer"]
    assert names("c.b", True) == ["every", "only_b"]


def test_a_cell_made_of_new_files_is_found(tmp_path):
    """A later change adds a configuration, a traffic mix, a driver and a
    metric as files, and an entry in BENCHMARK.json: the harness finds
    each by name and edits nothing."""
    base = tmp_path / "chipbench"
    for d in ("configs", "traffic", "drivers", "metrics", "checks"):
        (base / d).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "toy-1.burst", "config": "toy-1",
                       "traffic": "burst", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "toy_rate", "unit": "ops/s"}],
        "per_layer": [{"name": "toy_share", "moves": "toy_rate"}]}))
    (base / "configs" / "toy-1.json").write_text('{"width": 8}')
    (base / "configs" / "toy-1.py").write_text(
        "def round_flops(work):\n    return 2.0 * work['n']\n")
    (base / "traffic" / "burst.json").write_text('{"driver": "toy"}')
    (base / "drivers" / "toy.py").write_text("FAULTS = ()\n")
    (base / "checks" / "toy-1.burst.json").write_text('{"limits": {"x": 0}}')
    (base / "metrics" / "toy_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    man = harness.manifest(tmp_path)
    cell = harness.find_cell(man, "toy-1.burst")
    assert harness.load_json("configs", cell["config"], base) == {"width": 8}
    assert harness.load_module("configs", cell["config"], base
                               ).round_flops({"n": 3}) == 6.0
    traffic = harness.load_json("traffic", cell["traffic"], base)
    assert harness.load_module("drivers", traffic["driver"], base).FAULTS == ()
    assert harness.load_json("checks", cell["name"], base)["limits"] == {"x": 0}
    [m] = harness.metrics_of(man, cell["name"], True)
    assert harness.load_module("metrics", m["name"], base).read(None) == 42.0
    with pytest.raises(KeyError):
        harness.find_cell(man, "toy-1.other")
    with pytest.raises(ValueError):
        harness.load_json("configs", "../escape", base)
