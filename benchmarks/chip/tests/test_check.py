"""``correct`` comes out false when the timed path is broken underneath
a run, and for the control: the reference one precision lower in the
system's place. Each case drives the rest of a run on the CPU at a small
scale (the look for a chip is skipped)."""
import numpy as np
import pytest

import control
import harness

CELLS = ["q1_sf1", "q18sub_sf1"]
# a scale a test run can hold; at it Q18's spec thresholds (312-315)
# select no order, so the test lowers them to select some
SMALL = {"q1_sf1": 0.002, "q18sub_sf1": 0.01}


def small_cell(name):
    cell = harness.load_cell(name)
    cell.config["scale_factor"] = SMALL[name]
    if name == "q18sub_sf1":
        cell.workload["mix"][0]["params"] = {
            "fixed": [{"quantity": 150}, {"quantity": 200}]}
    return cell


def run(cell):
    return harness.run(cell, 2**31 + 77, 1.0, False, require_tpu=False)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    res = run(small_cell(name))
    assert res["correct"], res["check"]
    assert res["check"]["answers"]["value"] == res["attempted"] > 0


def _drop_half(orig):
    def absorb(self, key_cols, val_cols, reducer=None):
        # half of the batch left out, the aggregates taken over the rest
        half = (len(key_cols[0]) + 1) // 2
        return orig(self, [k[:half] for k in key_cols],
                    [v[:half] for v in val_cols], reducer=reducer)
    return absorb


def _alter_answer(orig):
    def assemble_output(op, batches, stats, store, write_outputs):
        # one float of the answer altered where it is produced
        out = orig(op, batches, stats, store, write_outputs)
        for col in out.values():
            names = col.dtype.names or (None,)
            for f in names:
                v = col if f is None else col[f]
                if v.dtype.kind == "f" and len(v):
                    v[0] *= 1 + 1e-6
                    return out
        return out
    return assemble_output


def _alter_dropped_groups(orig):
    def emit(self):
        # one in fifty of the groups that HAVING drops gets a totalprice
        # off by a millionth: the answer itself stays right
        out = orig(self)
        if out is not None and "totalprice" in out:
            low = np.flatnonzero(np.asarray(out["sum_qty"]) < 100)[::50]
            out["totalprice"][low] *= 1 + 1e-6
        return out
    return emit


FAULTS = [(n, f) for n in CELLS for f in ("drop_half", "alter_answer")] + [
    ("q18sub_sf1", "alter_dropped_groups")]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_a_broken_path_is_not_correct(name, fault, monkeypatch):
    from repro.core import executor, relops
    from repro.dist import driver
    if fault == "drop_half":
        monkeypatch.setattr(relops.AggMap, "absorb",
                            _drop_half(relops.AggMap.absorb))
    elif fault == "alter_dropped_groups":
        monkeypatch.setattr(relops.AggMap, "emit",
                            _alter_dropped_groups(relops.AggMap.emit))
    else:
        broken = _alter_answer(relops.assemble_output)
        monkeypatch.setattr(executor, "assemble_output", broken)
        monkeypatch.setattr(driver, "assemble_output", broken)
    res = run(small_cell(name))
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_the_limits(name):
    cell = small_cell(name)
    r = control.readings(cell, 2**31 + 5)
    limits = cell.workload["limits"]
    assert any(r[k] > limits[k] for k in r), r
    assert np.isfinite(r["float_rel_err"])
