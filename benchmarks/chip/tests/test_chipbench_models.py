"""A configuration names its own models: each key of its ``detector`` and
``pose`` sections reaches the engine's model configs or is refused, one
rule gives each camera's resolution to every reader of it, and a
configuration's reference, found by name, brings the operations that
``mfu`` counts."""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny

from chipbench import check, drive, fleet, harness, layers, peaks, spec, work

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
B = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAK = peaks.peaks("TPU v5 lite")


def _models(replica_res: int = 64):
    """A stand-in replica holding the program's default model configs."""
    from repro.configs.eda_vision import detector_config, pose_config
    return SimpleNamespace(dc=detector_config(replica_res),
                           pc=pose_config(replica_res))


def _config(**sections) -> dict:
    cfg = copy.deepcopy(spec.resolve("eda192-motion-sat").config)
    for name, extra in sections.items():
        cfg[name].update(extra)
    return cfg


@pytest.mark.parametrize("section,key", [("detector", "fpn_channels"),
                                         ("pose", "heads")])
def test_a_model_key_the_program_lacks_is_refused(section, key):
    cfg = _config(**{section: {key: [64, 64]}})
    with pytest.raises(ValueError, match=f"'eda-fleet-192'.*{key}"):
        fleet.set_models(cfg, _models())


SMALL = {"channels": [8, 16]}      # widths that keep a CPU set-up short


def test_a_model_key_the_program_lacks_fails_the_run_at_set_up():
    c = tiny.cell()
    c.config["detector"].update(SMALL)
    c.config["pose"].update(SMALL, backbone="mobilenet_v2")
    with pytest.raises(ValueError, match="backbone"):
        tiny.run(c)


def test_each_section_reaches_the_engines_models_field_by_field():
    cfg = _config(detector={"channels": [8, 16, 32], "num_classes": 7,
                            "num_anchors": 3, "input_res": 96},
                  pose={"channels": [8, 24], "num_keypoints": 5})
    r = _models()
    fleet.set_models(cfg, r)
    assert (r.dc.channels, r.dc.num_classes, r.dc.num_anchors,
            r.dc.input_res) == ((8, 16, 32), 7, 3, 96)
    assert (r.pc.channels, r.pc.num_keypoints, r.pc.input_res) == ((8, 24),
                                                                   5, 64)
    assert r.dc.task == "detect" and r.pc.task == "pose"


def _engine_and_params(cfg):
    ref = spec.load_module(BENCH / "references" / "eda_vision.py")
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                          ref.shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    return fleet.engines(dict(cfg, replicas=1))[0], shapes


def test_each_camera_is_checked_against_the_rows_its_ingest_writes():
    cfg = dict(_config(detector=SMALL, pose=SMALL), **tiny.SHRINK)
    eng, params = _engine_and_params(cfg)
    fleet.set_models(cfg, eng)
    fleet.check_shapes(cfg, eng, params)
    # the outer camera's model at 96 while the engine's ingest writes 64
    cfg["detector"]["input_res"] = 96
    eng, params = _engine_and_params(cfg)
    fleet.set_models(cfg, eng)
    with pytest.raises(ValueError, match=r"outer model input 96, ingest "
                                         r"rows \(64, 64\)"):
        fleet.check_shapes(cfg, eng, params)


def test_one_rule_gives_each_camera_its_resolution():
    cfg = _config(detector={"input_res": 96})
    assert spec.model_res(cfg, "outer") == 96
    assert spec.model_res(cfg, "inner") == cfg["input_res"] == 192


def test_the_ingest_bytes_count_each_camera_at_its_resolution():
    cfg = _config(detector={"input_res": 96}, pose={"input_res": 48})
    rec = drive.Record(active={"outer": 3, "inner": 2})
    red = SimpleNamespace(kernel_s=lambda part: 1.0)
    ref = SimpleNamespace(model_flops=lambda cfg, kind: 1)
    view = harness.run_view(cfg, ref, rec, red, PEAK, chips=1)
    nbytes = (work.ingest_bytes(3, 384, 96, 32)
              + work.ingest_bytes(2, 384, 48, 32))
    least, _ = work.least_time_s(nbytes, work.ingest_flops(5, 32), PEAK)
    assert layers.ingest_roofline(view, "x") == 100.0 * least


def test_the_comparison_ingests_each_camera_at_its_resolution():
    cfg = _config(detector={"input_res": 32}, pose={"input_res": 16})
    cfg.update(slots=2, gate_res=8, gate_block=4)
    seen = {}

    def ingest(frames, refs, *, model_res, gate_res, block, control):
        seen.setdefault(len(seen), model_res)
        S = frames.shape[0]
        return (np.zeros((S, model_res, model_res, 3), np.float32),
                np.zeros((S, gate_res, gate_res, 3), np.float32),
                np.zeros(S, np.float32))
    ref = SimpleNamespace(ingest=ingest)
    state = {k: {"batch": np.zeros((2, r, r, 3), np.float32),
                 "refs": np.zeros((2, 8, 8, 3), np.float32),
                 "thresh": np.ones(2, np.float32),
                 "has_ref": np.ones(2, bool)}
             for k, r in (("outer", 32), ("inner", 16))}
    sample = {"before": [state], "after": [state],
              "masks": np.zeros((4, 1, 2), bool),
              "lanes": [(0, "outer", 0, 1), (0, "inner", 1, 2)]}
    pools = {k: np.zeros((3, 64, 64, 3), np.float32) for k in drive.KINDS}
    got = check.compare([sample], pools, cfg, ref, params=None)
    assert [seen[i] for i in sorted(seen)] == [32, 16]
    assert got["frames_checked"] == 2 and got["ingest_max_abs_err"] == 0.0


SPY = '''
"""The benchmark's reference, with its own count of a frame's work, and
a log of the resolutions its calibration rows take."""
import json
from pathlib import Path

from chipbench import spec

_base = spec.load_module(Path({base!r}))
make_params, ingest, margins, shapes = (_base.make_params, _base.ingest,
                                        _base.margins, _base.shapes)


def downscale(frames, res):
    with open(Path(__file__).with_suffix(".log"), "a") as f:
        f.write(json.dumps(res) + "\\n")
    return _base.downscale(frames, res)


def model_flops(cfg, kind):
    return {{"outer": 1000003, "inner": 7}}[kind]
'''


def _new_configuration(tmp_path, **sections):
    """A configuration whose reference is a new file, in a bench
    directory of its own, added as files and entries only."""
    bench_dir = tmp_path / "chip"
    bench_dir.mkdir()
    for sub in ("traffic", "metrics"):
        (bench_dir / sub).symlink_to(BENCH / sub)
    (bench_dir / "references").mkdir(parents=True)
    (bench_dir / "references" / "spy.py").write_text(
        SPY.format(base=str(BENCH / "references" / "eda_vision.py")))
    cfg = dict(_config(**sections), name="spy-fleet", reference="spy")
    (bench_dir / "spy-fleet.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(B))
    bench["configs"].append({"name": "spy-fleet", "source": "-",
                             "file": str(bench_dir / "spy-fleet.json"),
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": "spy-sat", "config": "spy-fleet",
                               "traffic": "motion-sat", "chips": 1,
                               "why": "-"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "eda192-motion-sat" in m.get("workloads", []):
            m["workloads"].append("spy-sat")
    return spec.resolve("spy-sat", bench, root=ROOT, bench_dir=bench_dir)


def test_a_new_reference_brings_the_work_that_mfu_counts(tmp_path):
    c = _new_configuration(tmp_path)
    assert c.reference == tmp_path / "chip" / "references" / "spy.py"
    assert "mfu.sat" in {m["name"] for m in c.per_layer}
    ref = spec.load_module(c.reference)
    rec = drive.Record(ticks=[(0.02, 0.01), (0.02, 0.01)],
                       admitted={"outer": 5, "inner": 3})
    view = harness.run_view(c.config, ref, rec, None, PEAK, chips=1)
    mfu = spec.load_reader(c.readers["mfu.sat"])(view)
    assert mfu == 100.0 * (5 * 1000003 + 3 * 7) / (0.02 * PEAK["flops"])


def test_the_calibration_rows_take_each_cameras_resolution(tmp_path):
    """The run calibrates each camera at its own resolution, then stops at
    set-up: the program's engine writes one resolution for both."""
    c = _new_configuration(tmp_path, detector=dict(SMALL, input_res=96),
                           pose=SMALL)
    c.config.update(tiny.SHRINK)
    with pytest.raises(ValueError, match="outer model input 96"):
        tiny.run(c)
    log = (tmp_path / "chip" / "references" / "spy.log").read_text()
    assert [json.loads(x) for x in log.split()] == [96, 64]


# the mesh's hand-back gone wrong: each replica is handed the state of the
# next one, which ``shard_map`` keeps on another device for half of them
SHARDS_CROSSED = """
from repro.streams.fleet_step import FleetStep
_orig = FleetStep._per_replica
FleetStep._per_replica = lambda self, v: (lambda r: r[1:] + r[:1])(
    list(_orig(self, v)))
"""


@pytest.mark.parametrize("fault,correct", [("", True),
                                           (SHARDS_CROSSED, False)],
                         ids=["sound", "shards_crossed"])
def test_the_four_chip_cell_runs_correct_on_four_devices(fault, correct):
    """``eda192x4-motion-sat``, shrunk as the other cells are and with its
    8 replicas in ``shard_map``, two a device, runs whole and correct;
    with the replicas' shards handed back to the wrong replicas it is not
    correct."""
    code = fault + textwrap.dedent("""
        import json
        import chipbench_tiny as tiny
        c = tiny.cell("eda192x4-motion-sat")
        res = tiny.run(c, seconds=2.0)
        print(json.dumps({"correct": res["correct"],
                          "count": res["device"]["count"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}))
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(BENCH),
                                           str(BENCH / "tests")]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["count"] == 4 and res["attempted"] > 0
    assert res["correct"] is correct, res["checks"]
    mode = [ln for ln in out.stderr.splitlines() if ln.startswith("fleet:")]
    assert mode == ["fleet: mode=shard_map replicas=8 slots=4 vehicles=16"]
