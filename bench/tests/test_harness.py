"""What the harness finds by name, and the rules of BENCHMARK.json."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_dropped_files_are_found_without_an_edit(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(harness.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    w = json.loads((root / "workloads" / "p2p.nbr.32B.json").read_text())
    w.update(traffic="nbr_4K", params={**w["params"], "nbytes": 4096})
    (root / "workloads" / "p2p.nbr.4K.json").write_text(json.dumps(w))
    (root / "metrics" / "lat.extra_us.py").write_text(
        'UNIT = "us"\nBETTER = "lower"\nLAYER = "session fast path"\n'
        'SOURCE = "program_span"\nMOVES = "lat_p95_us"\n'
        'def read(x):\n    return None\n')

    assert "p2p.nbr.4K" in harness.cell_names(root)
    cell = harness.load_cell("p2p.nbr.4K", root)
    assert (cell.config_name, cell.chips, cell.params["nbytes"]) == (
        "omb_p2p", 4, 4096)
    assert hasattr(harness.load_driver(cell.driver, root), "setup")
    names = {m.name for m in harness.metrics_for(
        cell, harness.load_metrics(root))}
    assert names == {"lat.host_us", "lat.device_us", "lat.extra_us"}


def test_missing_or_malformed_parts_are_errors(tmp_path):
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.load_cell("../configs/omb_p2p")
    with pytest.raises(harness.BenchError):
        harness.load_driver("no_such_driver")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "half.py").write_text('UNIT = "ms"\n')
    with pytest.raises(harness.BenchError):
        harness.load_metrics(tmp_path)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["hbm_GBps"] == 819
    with pytest.raises(harness.BenchError):
        harness.load_peaks("cpu")


def test_no_tpu_exits_nonzero_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "jacobi.1chip.2M", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_cells_match_their_files():
    names = [w["name"] for w in SPEC["workloads"]]
    assert names and set(names) <= set(harness.cell_names())
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell.config_name, cell.traffic, cell.chips, cell.why) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        spec_cfg = configs[w["config"]]
        assert spec_cfg["file"] == f"bench/configs/{w['config']}.json"
        assert spec_cfg["reduced"] == cell.config["reduced"]
        assert set(cell.end_to_end) <= {e["name"] for e in SPEC["end_to_end"]}
        units = harness.load_driver(cell.driver).END_TO_END
        for e in SPEC["end_to_end"]:
            listed = e.get("workloads")
            reports = e["name"] == harness.SETUP or e["name"] in cell.end_to_end
            assert reports == (listed is None or w["name"] in listed)
            if e["name"] in cell.end_to_end:
                assert units[e["name"]] == e["unit"]
    assert set(configs) == {w["config"] for w in SPEC["workloads"]}


def test_every_per_layer_metric_is_read_where_its_moves_is_reported():
    metrics = harness.load_metrics()
    assert {m["name"] for m in SPEC["per_layer"]} <= set(metrics)
    for m in SPEC["per_layer"]:
        reader = metrics[m["name"]]
        assert (reader.unit, reader.better, reader.layer, reader.source,
                reader.moves) == (m["unit"], m["better"], m["layer"],
                                  m["source"], m["moves"])
        for name in m["workloads"]:
            assert m["moves"] in harness.load_cell(name).end_to_end
    for w in SPEC["workloads"]:
        read = {m.name for m in harness.metrics_for(
            harness.load_cell(w["name"]), metrics)}
        listed = {m["name"] for m in SPEC["per_layer"]
                  if w["name"] in m["workloads"]}
        assert read == listed and read


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_reservoir_is_drawn_from_the_seed():
    def draw(seed, n):
        r = harness.Reservoir(4, seed)
        for i in range(n):
            r.offer(i)
        return [i for i, _ in r.items]
    assert draw(7, 3) == [0, 1, 2]
    assert draw(7, 1000) == draw(7, 1000)
    assert draw(7, 1000) != draw(8, 1000)
    assert len(set(draw(7, 1000))) == 4


def test_result_line_puts_the_checks_last():
    line = harness.result_line(
        checks=[harness.Check("gap", float("inf"), 0.0)], attempted=3,
        failed=1, metrics={"step_ms": (1.5, "ms")},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    out = json.loads(line)
    assert out["correct"] is False
    assert list(out)[-1] == "checks"
    assert out["checks"]["gap"] == {"value": "inf", "limit": 0.0}


def test_percentile_is_nearest_rank():
    assert harness.percentile([5, 1, 4, 2, 3], 95) == 5
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile(list(range(1, 201)), 95) == 190
    assert harness.percentile([7], 95) == 7
    with pytest.raises(harness.BenchError):
        harness.percentile([], 95)
