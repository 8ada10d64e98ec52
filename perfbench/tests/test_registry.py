"""Cells, configurations, traffic and metrics are files found by name,
and BENCHMARK.json keeps to its contract."""

import json
import re
import shutil

import pytest

from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["perfbench"]
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell, entry, config, traffic = harness.lookup(bench, w["name"])
        assert entry["file"].startswith("perfbench/")
        assert (harness.HERE / "drivers" / f"{config['driver']}.py").is_file()
        assert {"trace_calls", "check_calls", "limits"} <= set(traffic)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and NAME.match(c["name"])
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]


def test_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        mod = harness.load_module(harness.HERE / "metrics"
                                  / f"{m['name']}.py")
        assert callable(mod.read)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert "bound" not in m
        target = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in target.get("workloads", cells), (m["name"], w)
    for w in cells:  # every cell: setup_s, another e2e, a per-layer metric
        got = harness.metrics_for(bench, w, "end_to_end")
        assert len(got) >= 2 and "setup_s" in [m["name"] for m in got]
        assert harness.metrics_for(bench, w, "per_layer")


def test_a_new_mix_is_files_only(tmp_path, monkeypatch, bench):
    """A later cell adds a traffic file and BENCHMARK.json entries, and
    edits no file of the harness."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    t = json.loads((harness.HERE / "traffic" / "cifar_b1024.json").read_text())
    t.update(batch=2048, distinct_batches=5)
    (root / "perfbench" / "traffic" / "cifar_b2048.json").write_text(
        json.dumps(t))
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [{
        "name": "resnet20.large", "config": "resnet20-cifar-paper",
        "traffic": "cifar_b2048", "chips": 1, "why": "larger batches"}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "HERE", root / "perfbench")
    b = harness.load_json(root / "BENCHMARK.json")
    cell, _, config, traffic = harness.lookup(b, "resnet20.large")
    assert traffic["batch"] == 2048
    assert config["name"] == "resnet20-cifar-paper"
    assert [m["name"] for m in harness.metrics_for(b, "resnet20.large",
                                                   "end_to_end")] == [
        "setup_s"]


def test_unknown_names_fail(bench):
    with pytest.raises(KeyError):
        harness.lookup(bench, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module(harness.HERE / "metrics" / "no_such_metric.py")


def test_sample_calls_from_seed():
    a = harness.sample_calls(2**33 + 7, 10, 2)
    assert a == harness.sample_calls(2**33 + 7, 10, 2)
    assert len(a) == 2 and all(0 <= i < 10 for i in a)
    assert harness.sample_calls(1, 1, 3) == [0]
