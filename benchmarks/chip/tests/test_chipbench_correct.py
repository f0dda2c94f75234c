"""``correct`` holds for the program and fails for the control and for
each fault a cell can have, at small sizes on the CPU.

The faults are planted underneath the timed path: a power iteration
that returns its basis unchanged, a column mean taken over half of the
columns, and an answer altered where it is produced.  The one-chip
cells exchange nothing between chips.
"""
import jax.numpy as jnp
import pytest

from benchmarks.chip import reference
from benchmarks.chip.tests.small_cells import run_small

CELLS = ["lfw_faces.q2"]


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell, seed):
    out = run_small(cell, seed=seed)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = run_small(cell, program=reference.control)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_power_step_returning_its_state_is_caught(cell, monkeypatch):
    from repro.core import schedule

    def unchanged(sched, eng, op, Q, mu, t, state):
        return Q, state, jnp.eye(Q.shape[1], dtype=Q.dtype)

    monkeypatch.setattr(schedule, "power_step", unchanged)
    out = run_small(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_mean_over_half_the_columns_is_caught(cell, monkeypatch):
    from repro.core import contact

    def half_mean(self, op):
        n = op.shape[1]
        w = (jnp.arange(n) < n // 2).astype(jnp.float32)[:, None]
        return self.matmat(op, w)[:, 0] / (n // 2)

    monkeypatch.setattr(contact.ContactEngine, "col_mean", half_mean)
    out = run_small(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("what", ["S", "U"])
@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_caught(cell, what):
    from repro import api
    from repro.core.srsvd import SVDResult

    def altered(*a, **kw):
        res, rep = api.factorize(*a, **kw)
        if what == "S":
            res = SVDResult(res.U, res.S * 1.01, res.Vt)
        else:
            res = SVDResult(res.U[:, ::-1], res.S, res.Vt)
        return res, rep

    out = run_small(cell, program=altered)
    assert not out["correct"], out["checks"]
