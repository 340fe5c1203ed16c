"""A configuration, mix, profile or metric file dropped into its directory
is found by name, with no edit to the harness."""
import json
import shutil

import pytest

from lib import harness


def test_new_files_are_found_by_name(tmp_path, small):
    bench, _ = small
    root = tmp_path / "bench"
    shutil.copy(root / "configs" / "lustre248x4096.json",
                root / "configs" / "newcfg.json")
    mix = json.loads((root / "traffic" / "replay_filebench.json").read_text())
    mix["profile"] = "newprof"
    (root / "traffic" / "newmix.json").write_text(json.dumps(mix))
    prof = json.loads((root / "profiles" / "filebench_iv.json").read_text())
    prof["in_flight_per_process"] = 8
    (root / "profiles" / "newprof.json").write_text(json.dumps(prof))
    (root / "metrics" / "new_metric.probe.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "newcfg", "source": "test",
                             "file": "bench/configs/newcfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "newcfg.newmix", "config": "newcfg",
                               "traffic": "newmix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric.probe", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "windows_per_s",
                               "workloads": ["newcfg.newmix"]})
    # a new cell joins the metrics it reports by name
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("windows_per_s", "device_idle_share.replay"):
            m["workloads"].append("newcfg.newmix")
    cell = harness.resolve(bench, "newcfg.newmix", repo=tmp_path, root=root)
    assert cell.config["n_ost"] == 8
    assert cell.traffic["profile"] == "newprof"
    assert cell.profile["in_flight_per_process"] == 8
    assert cell.traffic["mode"] == "replay"
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle_share.replay", "new_metric.probe"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "windows_per_s"]
    reader = harness.load_module("metrics", "new_metric.probe", root)
    assert reader.read(None) == 42.0
    # a metric with no file of its own is read by its base name's reader
    (root / "metrics" / "other_metric.py").write_text(
        "def read(run):\n    return 7.0\n")
    reader = harness.load_module("metrics", "other_metric.newmix", root)
    assert reader.read(None) == 7.0


def test_every_listed_file_exists():
    bench = harness.load_json(harness.REPO / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        harness.load_module("modes", cell.traffic["mode"])
        for m in cell.per_layer:
            assert callable(harness.load_module("metrics", m["name"]).read)


def test_unknown_names_are_refused(small):
    bench, resolve = small
    with pytest.raises(harness.BenchError):
        resolve("nope.nothing")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")
