"""Every name in BENCHMARK.json resolves to its files, and a cell is
added by adding files and a manifest entry alone."""
import json
import shutil

import pytest

from chip import harness as H


@pytest.fixture(scope="module")
def man():
    return H.manifest()


def test_every_cell_resolves(man):
    for w in man["workloads"]:
        cell = H.resolve(w["name"], man)
        assert cell.config["name"] == w["config"]
        assert H.driver_module(cell.driver).run
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_every_config_file_is_its_own(man):
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    for c in man["configs"]:
        cfg = H.load_json(H.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in man["workloads"])


def test_every_metric_has_a_reader(man):
    for m in man["per_layer"]:
        assert callable(H.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in man["end_to_end"]}
        for w in m.get("workloads", []):
            cell = H.resolve(w, man)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_a_cell_added_as_files_loads(man, tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(H.BENCH / "configs", bench / "configs")
    shutil.copytree(H.BENCH / "traffic", bench / "traffic")
    (bench / "traffic" / "fleet_wide.json").write_text(json.dumps(
        dict(H.load_json(H.BENCH / "traffic" / "fleet8.json"), workers=16)))
    for c in man["configs"]:
        dst = tmp_path / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(H.ROOT / c["file"], dst)
    new = dict(man, workloads=man["workloads"] + [{
        "name": "base-fleet-wide", "config": "costmodel-base",
        "traffic": "fleet_wide", "chips": 1, "why": "a wider fleet"}])
    cell = H.resolve("base-fleet-wide", new, bench=bench)
    assert cell.traffic["workers"] == 16 and cell.driver == "fleet"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    with pytest.raises(H.BenchError):
        H.resolve("no-such-cell", new, bench=bench)
