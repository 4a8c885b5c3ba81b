"""``BENCHMARK.json`` and the files it names: everything is found by
name, and a configuration, a cell and a per-layer metric can be added as
files alone."""
import json
import re

import bench_tiny
from bench.lib.registry import Registry

SPEC = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_and_file_keeps_the_contract():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (bench_tiny.ROOT / c["file"]).is_file()
        cfg = json.loads((bench_tiny.ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_each_per_layer_metric_has_its_reader():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    reg = Registry(bench_tiny.ROOT)
    layers = {}
    for m in SPEC["per_layer"]:
        mod = reg.metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE, mod.BETTER) == (
            m["layer"], m["unit"], m["moves"], m["source"], m["better"])
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        layers.setdefault(m["layer"], set()).add(m["name"])
    for w in SPEC["workloads"]:
        cell = reg.cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_the_harness_names_no_config_cell_or_metric():
    names = [e["name"] for key in ("configs", "workloads", "per_layer")
             for e in SPEC[key]] + [e["name"] for e in SPEC["end_to_end"]
                                    if e["name"] != "setup_s"]
    for path in [bench_tiny.ROOT / "bench" / "run.py",
                 *(bench_tiny.ROOT / "bench" / "lib").glob("*.py")]:
        text = path.read_text()
        assert not [n for n in names if n in text], path


def test_a_config_cell_and_metric_added_as_files(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench" / "configs" / "infmnist_k50.json")
                     .read_text())
    cfg.update(k=5, source="a deployment added as a file")
    (root / "bench" / "configs" / "added_k5.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "metrics" / "rows_per_fit.py").write_text(
        'LAYER = "host loop"\nUNIT = "rows"\nMOVES = "fit_s"\n'
        'SOURCE = "program_counter"\nBETTER = "higher"\n\n\n'
        'def read(obs):\n'
        '    return max(r.b for f in obs.driver.records\n'
        '               for r in f.telemetry)\n')
    spec["configs"].append({"name": "added_k5", "source": "x",
                            "file": "bench/configs/added_k5.json",
                            "reduced": [], "why": "added"})
    spec["workloads"].append({"name": "added_k5.fit", "config": "added_k5",
                              "traffic": "fit_back_to_back", "chips": 1,
                              "why": "added"})
    fit_s = next(m for m in spec["end_to_end"] if m["name"] == "fit_s")
    fit_s["workloads"].append("added_k5.fit")
    spec["per_layer"].append({"name": "rows_per_fit", "unit": "rows",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "host loop", "moves": "fit_s",
                              "workloads": ["added_k5.fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = Registry(root).cell("added_k5.fit")
    assert cell.config["k"] == 5
    assert "rows_per_fit" in {m["name"] for m in cell.per_layer}
    res = bench_tiny.run_cell(root, "added_k5.fit", trace=1)
    assert res["correct"] is True
    assert res["metrics"]["rows_per_fit"] == {"value": 4096,
                                              "unit": "rows"}
    # a metric without a ``workloads`` key is read in every cell that
    # reports what it moves; one with the key only in its cells
    assert "compile_s.setup" in res["metrics"]
    assert "rounds_per_fit" not in res["metrics"]
    res = bench_tiny.run_cell(root, "added_k5.fit", trace=0)
    assert set(res["metrics"]) == {"fit_s", "setup_s"}
    assert list(res)[-1] == "checks"
