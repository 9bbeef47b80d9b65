"""A whole run of a shrunk cell on the CPU (the look for a chip skipped):
sound, it is correct; with the timed path broken underneath, ``correct``
comes out false, once for each fault the cells can have."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny

from repro.kernels import vision_ops
from repro.models import vision as V
from repro.streams import fleet_step


@pytest.fixture(scope="module")
def sound():
    return tiny.run(tiny.cell(), control=True)


def test_a_sound_run_is_correct_and_reports_its_numbers(sound):
    assert sound["correct"] is True
    checks = sound["checks"]
    assert list(checks)[:3] == ["ingest_max_abs_err", "admit_errors",
                                "flag_disagreement"]
    assert checks["ingest_max_abs_err"]["value"] == 0.0
    assert checks["admit_errors"]["value"] == 0
    assert checks["frames_checked"]["value"] > 0
    assert sound["metrics"]["frames_per_s"]["value"] > 0
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_the_control_fails_the_ingest_limit(sound):
    """The reference one precision down, in the program's place on the
    same kept ticks, fails the configuration's limits."""
    ctl = sound["control"]
    assert ctl["correct"] is False
    lim = ctl["checks"]["ingest_max_abs_err"]
    assert lim["value"] > lim["limit"]


def _state_unchanged(monkeypatch):
    """The step returns its state unchanged: no admitted frame reaches
    the batch pool or the gate references."""
    monkeypatch.setattr(vision_ops, "scatter_admit",
                        lambda batch, model, refs, gate, admit, **kw:
                        (batch, refs))


def _half_batch(monkeypatch):
    """Half of the lanes left out of the ingest: their planes and scores
    come back empty."""
    orig = vision_ops.ingest_frame

    def half(frames, refs, **kw):
        model, gate, scores = orig(frames, refs, **kw)
        keep = jnp.arange(frames.shape[0]) < frames.shape[0] // 2
        m = keep[:, None, None, None]
        return (jnp.where(m, model, 0.0), jnp.where(m, gate, 0.0),
                jnp.where(keep, scores, 0.0))
    monkeypatch.setattr(vision_ops, "ingest_frame", half)


def _state_exchanged(monkeypatch):
    """Each replica is handed another replica's state back from the
    fused call (the replica mesh's hand-back gone wrong)."""
    monkeypatch.setattr(fleet_step.FleetStep, "_per_replica",
                        lambda self, v: tuple(v[1:]) + tuple(v[:1]))


def _answer_altered(monkeypatch):
    """Both cameras' flags inverted where the models produce them."""
    outer, inner = V.analyse_outer, V.analyse_inner

    def flip(fn):
        return lambda cfg, p, x: (lambda f, d: (~f, d))(*fn(cfg, p, x))
    monkeypatch.setattr(V, "analyse_outer", flip(outer))
    monkeypatch.setattr(V, "analyse_inner", flip(inner))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _state_exchanged, _answer_altered])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = tiny.run(tiny.cell(), seed=8)
    assert res["correct"] is False, res["checks"]
