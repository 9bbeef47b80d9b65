"""The comparison that decides ``correct``.

It judges what the timed path produced on the ticks the sampler kept,
at the timed sizes, against the configuration's plain reference, once
the window has closed and the fleet is freed:

* ``ingest_max_abs_err``: every lane the program admitted holds, in its
  batch pool and its gate reference, the reference's model-resolution and
  gate-resolution planes of the frame it staged; every other lane holds
  what it held before the tick.  The largest absolute difference.
* ``admit_errors``: lanes whose admit decision differs from the
  reference's, given the thresholds the host shipped.  A lane whose
  reference score lies within ``admit_tie`` of its threshold is a tie and
  is not counted.
* ``flag_disagreement``: for each frame both admitted, a flag that
  differs from the reference's is judged by the reference's margin (see
  the reference's docstring): the sum of those margins over every flag
  checked, divided by the number of flags checked.  The largest such
  margin (``flag_gap``) is reported beside it but not judged: it is a
  maximum over a few flips near the rule's boundary, and it swings from
  seed to seed by more than a lower precision moves it.

The control puts the reference, at a lower precision, in the program's
place: its outputs are judged by the same numbers.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.drive import INNER, KINDS, OUTER
from chipbench.spec import model_res

MASK_ROW = {OUTER: (0, 2), INNER: (1, 3)}        # (admit, flags) rows


def _lanes(sample: dict, r: int, kind: str, slots: int):
    """Pool index staged in each lane (-1: lane not active)."""
    idx = np.full(slots, -1)
    for rep, k, lane, pool_i in sample["lanes"]:
        if rep == r and k == kind:
            idx[lane] = pool_i
    return idx


def compare(samples: list, pools: dict, cfg: dict, ref, params,
            *, control: bool = False) -> dict:
    """The numbers for the program's outputs (``control=False``) or the
    control's, over the kept ticks on the host (``Sampler.host``)."""
    slots = cfg["slots"]
    tie = cfg["limits"]["admit_tie"]
    ingest_err, admit_errors, flag_gap, flag_sum = 0.0, 0, 0.0, 0.0
    frames = flags = 0
    for smp in samples:
        for r, (bef, aft) in enumerate(zip(smp["before"], smp["after"])):
            for kind in KINDS:
                idx = _lanes(smp, r, kind, slots)
                act = idx >= 0
                if not act.any():
                    continue
                src = pools[kind][np.where(act, idx, 0)]
                b = bef[kind]
                model, gate, score = (np.asarray(x) for x in ref.ingest(
                    jnp.asarray(src), jnp.asarray(b["refs"]),
                    model_res=model_res(cfg, kind),
                    gate_res=cfg["gate_res"],
                    block=cfg["gate_block"], control=False))
                want = act & ((score > b["thresh"]) | ~b["has_ref"])
                ties = act & b["has_ref"] & (np.abs(score - b["thresh"])
                                             <= tie)
                if control:
                    c_model, c_gate, c_score = (np.asarray(x) for x in
                                                ref.ingest(
                        jnp.asarray(src), jnp.asarray(b["refs"]),
                        model_res=model_res(cfg, kind),
                        gate_res=cfg["gate_res"], block=cfg["gate_block"],
                        control=True))
                    got = act & ((c_score > b["thresh"]) | ~b["has_ref"])
                    m = got[:, None, None, None]
                    batch = np.where(m, c_model, b["batch"])
                    refs = np.where(m, c_gate, b["refs"])
                    rows = c_model
                else:
                    a_row, _ = MASK_ROW[kind]
                    got = np.asarray(smp["masks"][a_row][r], bool)
                    batch, refs = aft[kind]["batch"], aft[kind]["refs"]
                    rows = None
                admit_errors += int(np.sum((got != want) & ~ties))
                # admitted lanes hold the new planes, the others are kept
                m = got[:, None, None, None]
                err_b = np.abs(batch - np.where(m, model, b["batch"]))
                err_r = np.abs(refs - np.where(m, gate, b["refs"]))
                ingest_err = max(ingest_err, float(err_b.max()),
                                 float(err_r.max()))
                frames += int(act.sum())
                both = got & want
                if not both.any():
                    continue
                margin = np.asarray(ref.margins(params, jnp.asarray(model),
                                                cfg, kind))
                if control:
                    c_m = np.asarray(ref.margins(params, jnp.asarray(rows),
                                                 cfg, kind, control=True))
                    served = c_m > 0
                else:
                    _, f_row = MASK_ROW[kind]
                    served = np.asarray(smp["masks"][f_row][r], bool)
                wrong = both & (served != (margin > 0))
                if wrong.any():
                    flag_gap = max(flag_gap,
                                   float(np.abs(margin[wrong]).max()))
                    flag_sum += float(np.abs(margin[wrong]).sum())
                flags += int(both.sum())
    return {"ingest_max_abs_err": ingest_err, "admit_errors": admit_errors,
            "flag_disagreement": flag_sum / max(flags, 1),
            "flag_gap": flag_gap, "frames_checked": frames,
            "flags_checked": flags}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]) — each number beside its limit."""
    rows = [("ingest_max_abs_err", numbers["ingest_max_abs_err"],
             limits["ingest_max_abs_err"]),
            ("admit_errors", numbers["admit_errors"], 0),
            ("flag_disagreement", numbers["flag_disagreement"],
             limits["flag_disagreement"])]
    ok = all(v <= lim for _, v, lim in rows)
    ok = ok and numbers["frames_checked"] > 0 and numbers["flags_checked"] > 0
    rows += [("frames_checked", numbers["frames_checked"], "> 0"),
             ("flags_checked", numbers["flags_checked"], "> 0"),
             ("flag_gap", numbers["flag_gap"], "not judged")]
    return ok, rows
