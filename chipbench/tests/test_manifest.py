"""BENCHMARK.json against the benchmark's rules: names, units, keys, bounds,
and every file a cell or metric names is there."""
import re

import pytest

from chipbench import harness

MAN = harness.manifest()
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["chipbench"]
    assert 1 <= len(MAN["command"]) <= 32
    for word in MAN["command"]:
        assert TEXT.match(word) and not word.startswith("/") and ".." not in word
    assert MAN["command"][1].startswith("chipbench/")
    assert (harness.REPO / MAN["command"][1]).is_file()


def test_run_seconds_fit_a_full_check_of_24_cells():
    s = MAN["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def _names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MAN[kind]:
            yield entry["name"]
    for cfg in MAN["configs"]:
        yield from cfg["reduced"]
    for w in MAN["workloads"]:
        yield w["config"]
        yield w["traffic"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_keep_to_the_charset(name):
    assert harness.NAME.match(name), name


def test_names_are_unique():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MAN[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_metrics_keys_units_sources_and_bounds():
    cells = {w["name"] for w in MAN["workloads"]}
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and TEXT.match(m["layer"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert harness.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in MAN["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(MAN, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(MAN, w["name"], True)


def test_files_each_entry_names_are_there():
    root = harness.HERE
    for cfg in MAN["configs"]:
        assert cfg["file"] == f"chipbench/configs/{cfg['name']}.json"
        assert (harness.REPO / cfg["file"]).is_file()
        assert (root / "configs" / f"{cfg['name']}.py").is_file()
        assert TEXT.match(cfg["source"]) and TEXT.match(cfg["why"])
        assert harness.load_json("configs", cfg["name"])["reduced"] \
            == cfg["reduced"]
    configs = {c["name"] for c in MAN["configs"]}
    four = 0
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert TEXT.match(w["why"])
        four += w["chips"] == 4
        traffic = harness.load_json("traffic", w["traffic"])
        assert (root / "drivers" / f"{traffic['driver']}.py").is_file()
        assert harness.load_json("checks", w["name"])["limits"]
    assert four <= max(1, len(MAN["workloads"]) // 2)
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert (root / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_every_configuration_is_used():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_the_peak_table_knows_the_v5e_and_refuses_the_unknown():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
