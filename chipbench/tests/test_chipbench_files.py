"""Every configuration and traffic file loads, and the harness finds a new
cell, traffic mix and per-layer metric by name, from new files alone."""

import json
import shutil

import _paths  # noqa: F401
from _paths import ROOT
from harness import spec


def test_benchmark_files_load_and_name_their_source():
    bench = spec.load(ROOT)
    for c in bench["configs"]:
        cfg = spec.config(ROOT, bench, c["name"])
        assert cfg["name"] == c["name"]
        assert c["source"].startswith("https://")
        assert cfg["source"] == c["source"]
        assert cfg["precision"] == "float64"
        for k in c["reduced"]:
            assert k in cfg
    for w in bench["workloads"]:
        t = spec.traffic(ROOT, w["traffic"])
        assert t["kind"] in ("replay", "open_loop")
        assert t["process"]["kind"] in ("poisson", "mmpp")
    for m in bench["per_layer"]:
        assert callable(spec.reader(ROOT, m["name"]))


def test_every_cell_reports_setup_another_e2e_metric_and_a_layer():
    bench = spec.load(ROOT)
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.e2e_of(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.layers_of(bench, w["name"])


def test_new_cell_mix_and_metric_are_new_files_plus_entries(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    bench = spec.load(ROOT)
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "chipbench").rglob("*") if p.is_file()}
    (root / "chipbench" / "traffic" / "replay_fast.json").write_text(
        json.dumps({"kind": "replay", "process": {"kind": "poisson",
                    "rate_per_s": 40.0}, "chunk_rows": 4096,
                    "warm_rate_scale": [1.0, 1.0]}))
    (root / "chipbench" / "metrics" / "chunks_in_window.fast.py").write_text(
        "def read(ctx):\n    return len(ctx['window_chunks'])\n")
    bench["workloads"].append({"name": "fd19-mincost.fast",
                               "config": "fd19-mincost",
                               "traffic": "replay_fast", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "chunks_in_window.fast",
                               "unit": "chunks", "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "replay_rate",
                               "workloads": ["fd19-mincost.fast"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "replay_rate":
            m["workloads"].append("fd19-mincost.fast")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b2 = spec.load(root)
    assert spec.traffic(root, "replay_fast")["chunk_rows"] == 4096
    assert spec.traffic_driver(root, "replay_fast") is None
    (root / "chipbench" / "traffic" / "replay_fast.py").write_text(
        "def drive(*args):\n    return 'own', args\n")
    assert spec.traffic_driver(root, "replay_fast")(1, 2) == ("own", (1, 2))
    ctx = {"window_chunks": [3, 4, 5]}
    got = spec.per_layer(root, b2, "fd19-mincost.fast", ctx)
    assert got == {"chunks_in_window.fast": {"value": 3.0, "unit": "chunks"}}
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "chipbench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())
