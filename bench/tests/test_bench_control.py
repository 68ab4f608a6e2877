"""The comparison that decides ``correct`` fails the control and each fault
a cell can have, at a size a test run holds.

The control is the program's own float32 path in place of the float64 the
configurations state.  The faults are planted in the program underneath a
dry run of the harness: a step that returns its state unchanged, half of
each evaluated batch left out, and an answer altered where the rule
produces it.  (The exchange between chips left out is in
``test_bench_ring.py``, which needs four devices.)
"""

import jax.numpy as jnp
import pytest

import benchtest
from repro.core import adaptive, distributed, rules
from repro.service import batch_engine

CELLS = ["tiny_f4.solve", "tiny_gauss.closed4"]


@pytest.fixture(scope="module")
def suite(tmp_path_factory):
    return benchtest.tiny_suite(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(suite, cell):
    result = benchtest.run_dry(suite, cell, quadrature={"dtype": "float32"},
                               traffic={"stall_seconds": 10})
    assert result["correct"] is False, result["checks"]


def _unchanged(*args, **kwargs):
    return lambda state, *a, **k: state


def _wrap_rule(monkeypatch, alter):
    produce = rules.GenzMalikRule.eval_batch

    def eval_batch(self, centers, halfw):
        return alter(*produce(self, centers, halfw))

    monkeypatch.setattr(rules.GenzMalikRule, "eval_batch", eval_batch)


def _half_left_out(est, err, axis):
    keep = jnp.arange(est.shape[0]) < est.shape[0] // 2
    return jnp.where(keep, est, 0.0), jnp.where(keep, err, 0.0), axis


def _altered(est, err, axis):
    return est * (1.0 + 1e-3), err, axis


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(suite, cell, fault, monkeypatch):
    if fault == "state_unchanged":
        monkeypatch.setattr(adaptive, "make_advance_step", _unchanged)
        monkeypatch.setattr(batch_engine, "make_advance_step", _unchanged)
        monkeypatch.setattr(distributed, "make_classify_split", _unchanged)
    else:
        _wrap_rule(monkeypatch, _half_left_out if fault == "half_batch" else _altered)
    result = benchtest.run_dry(suite, cell, traffic={"stall_seconds": 10})
    assert result["correct"] is False, result["checks"]
