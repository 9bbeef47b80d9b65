"""Every cell of ``BENCHMARK.json`` resolves to its files, a new cell
resolves from data alone, and the file keeps to the benchmark's format."""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from chipbench import spec  # noqa: E402

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(spec.load_reader(c.readers[m["name"]]))
    assert c.reference.is_file()
    assert c.config["replicas"] * c.config["slots"] >= 2
    assert {"ingest_max_abs_err", "admit_tie",
            "flag_disagreement"} <= set(c.limits)


def test_a_new_cell_resolves_from_data_alone(tmp_path):
    """A cell, its traffic mix and a per-layer metric added as files and
    entries only: no code of the harness changes."""
    bench_dir = tmp_path / "chip"
    for sub in ("traffic", "metrics", "references"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    mix = json.loads((bench_dir / "traffic" / "motion-25fps.json").read_text())
    mix["vehicles"] = 4
    (bench_dir / "traffic" / "motion-8fps.json").write_text(
        json.dumps(dict(mix, fps=8)))
    (bench_dir / "metrics" / "ticks.slow.py").write_text(
        "def read(run):\n    return float(len(run.rec.ticks))\n")
    bench = json.loads(json.dumps(B))
    bench["workloads"].append({"name": "eda192-motion-8fps",
                               "config": "eda-fleet-192",
                               "traffic": "motion-8fps", "chips": 1,
                               "why": "a slow open loop"})
    for m in bench["end_to_end"]:
        if m["name"] == "turnaround_p50_ms":
            m["workloads"].append("eda192-motion-8fps")
    bench["per_layer"].append({"name": "ticks.slow", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "gateway host",
                               "moves": "turnaround_p50_ms",
                               "workloads": ["eda192-motion-8fps"]})
    c = spec.resolve("eda192-motion-8fps", bench, root=ROOT,
                     bench_dir=bench_dir)
    assert c.traffic["fps"] == 8 and c.chips == 1
    assert [m["name"] for m in c.per_layer] == ["ticks.slow"]
    assert {m["name"] for m in c.end_to_end} == {"turnaround_p50_ms",
                                                 "setup_s"}
    rec = type("R", (), {"ticks": [1, 2, 3]})()
    view = type("V", (), {"rec": rec})()
    assert spec.load_reader(c.readers["ticks.slow"])(view) == 3.0


def test_an_unknown_cell_or_a_missing_reader_is_refused(tmp_path):
    with pytest.raises(KeyError, match="no workload"):
        spec.resolve("no-such-cell")
    bench_dir = tmp_path / "chip"
    for sub in ("traffic", "references"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    (bench_dir / "metrics").mkdir()
    with pytest.raises(FileNotFoundError, match="has no reader"):
        spec.resolve(B["workloads"][0]["name"], B, root=ROOT,
                     bench_dir=bench_dir)


def test_split_metrics_share_the_reader_of_their_base_name(tmp_path):
    """``x.sat`` and ``x.rate`` are read by ``metrics/x.py``; a reader of
    the full name, where there is one, comes first."""
    for m in B["per_layer"]:
        base = m["name"].split(".")[0]
        assert spec.find_reader(m["name"]) == BENCH / "metrics" / f"{base}.py"
    (tmp_path / "metrics").mkdir()
    for name in ("x.py", "x.rate.py"):
        (tmp_path / "metrics" / name).write_text("def read(run): ...\n")
    assert spec.find_reader("x.sat", tmp_path).name == "x.py"
    assert spec.find_reader("x.rate", tmp_path).name == "x.rate.py"
    with pytest.raises(FileNotFoundError, match="has no reader"):
        spec.find_reader("y.sat", tmp_path)


def test_benchmark_json_keeps_to_the_format():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmarks/chip"]
    assert B["command"][1].startswith("benchmarks/chip/")
    assert 1 <= B["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in B["configs"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        for k in c["reduced"]:
            assert NAME.match(k) and k in cfg["reduced"]
            assert not re.search(r"(_dim|_rank|hidden|width|channels)$", k)
    cells = B["workloads"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}
    for m in B["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # a full check of 24 cells at this window fits its time
    assert 2 + 14 * 24 * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
