"""BENCHMARK.json against the benchmark's contract, and data found by name:
a later change adds a configuration, a traffic mix or a per-layer metric
as files, and the harness finds it without an edit."""

import json
import re
import shutil

import pytest

from bench import generator, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_shape():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
                      "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (spec.ROOT / c["file"]).exists()
        assert json.loads((spec.ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (spec.BENCH / "limits" / f"{w['config']}.json").exists()
        assert spec.rate(w["name"]) > 0
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert callable(spec.reader(m["name"]))
    for n in list(cells) + list(configs):
        assert NAME.match(n)


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for w in spec.benchmark()["workloads"]:
        e2e = {m["name"] for m in spec.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics_of(w["name"], "per_layer")
        # a per-layer metric moves an end-to-end metric its cell reports
        assert all(m["moves"] in e2e for m in spec.metrics_of(w["name"], "per_layer"))


def test_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs" / "qwen3-14b.json").read_text())
    cfg["name"] = "new-model"
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat-shared.json").read_text())
    mix["sessions"] = 8
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new.metric.py").write_text("def read(w):\n    return 42.0\n")
    (bench / "rates" / "new-model.new-mix.json").write_text('{"rate_rps": 1.5}')
    monkeypatch.setattr(spec, "BENCH", bench)
    assert spec.config("new-model")["name"] == "new-model"
    assert spec.traffic("new-mix")["sessions"] == 8
    assert spec.reader("new.metric")(None) == 42.0
    assert spec.rate("new-model.new-mix") == 1.5
    t = generator.Traffic(spec.traffic("new-mix"), 1000, 16, 0)
    assert len(t.sessions) == 8


def test_a_tagged_metric_is_read_by_its_base_unless_it_has_a_file(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "layer.thing_s.py").write_text("def read(w):\n    return 1.0\n")
    (tmp_path / "metrics" / "layer.thing_s.own.py").write_text("def read(w):\n    return 2.0\n")
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    assert spec.reader("layer.thing_s")(None) == 1.0
    assert spec.reader("layer.thing_s.tail")(None) == 1.0
    assert spec.reader("layer.thing_s.own")(None) == 2.0
    with pytest.raises(FileNotFoundError):
        spec.reader("layer.other_s")


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        spec.peaks("cpu")
