"""BENCHMARK.json against the benchmark's contract: names, units, files
and the metrics every cell reports."""

import json
import re
from pathlib import Path

import pytest

import bench
from bench import harness, load

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
LINE = re.compile(r"[^\t\n\r]{1,200}")
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench", "tests/bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units():
    names = []
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        names.append(c["name"])
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert LINE.fullmatch(c["source"]) and LINE.fullmatch(c["why"])
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert LINE.fullmatch(w["why"])
    for m in BENCH["per_layer"]:
        assert LINE.fullmatch(m["layer"])
    assert all(NAME.fullmatch(n) for n in names), names
    for group in ("end_to_end", "per_layer", "configs", "workloads"):
        group_names = [x["name"] for x in BENCH[group]]
        assert len(group_names) == len(set(group_names))


def test_entries_have_just_the_contract_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in BENCH["end_to_end"]}[
        "setup_s"] <= 0.25


def test_files_are_found_by_name():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E
        path = ROOT / "bench" / "metrics" / f"{m['name']}.py"
        text = path.read_text()
        assert f'MOVES = "{m["moves"]}"' in text, path
        assert callable(harness.reader(m["name"]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_enough(w):
    c = harness.cell(w["name"])
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"]
    assert w["chips"] in (1, 4) and c["config"]["chips"] == w["chips"]
    moved = {m["moves"] for m in c["per_layer"]}
    assert moved <= e2e
    for m in c["per_layer"]:
        assert m["moves"] in e2e


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def _mixes_and_generators():
    mixes = sorted((ROOT / "bench" / "traffic").glob("*.json"))
    kinds = {json.loads(p.read_text())["kind"] for p in mixes}
    gens = {json.loads(p.read_text())["generator"]["kind"]
            for p in (ROOT / "bench" / "configs").glob("*.json")}
    return sorted(kinds), sorted(gens)


@pytest.mark.parametrize("kind", _mixes_and_generators()[0])
def test_every_traffic_kind_is_a_file_of_its_own(kind):
    driver = harness.driver(kind)
    for name in ("window", "summary", "end_to_end", "free", "check"):
        assert callable(getattr(driver, name)), name
    assert callable(load("kinds", kind).control_windows)


@pytest.mark.parametrize("gen", _mixes_and_generators()[1])
def test_every_generator_is_a_file_of_its_own(gen):
    assert callable(load("gen", gen).generate)


def test_a_new_piece_needs_no_edit(tmp_path, monkeypatch):
    """A kind added as a file is found by its name, with nothing else
    changed; a name with no file is refused."""
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / "later_mix.py").write_text("class Kind:\n    pass\n")
    monkeypatch.setattr(bench, "HERE", tmp_path)
    assert harness.driver("later_mix").__name__ == "Kind"
    with pytest.raises(ValueError, match="no kinds 'absent'"):
        harness.driver("absent")
