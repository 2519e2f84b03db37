"""Each fault planted under the training cell's timed path comes out not
correct under the cell's limits, at CPU-test size."""
import pytest

from bench import faults
from bench.tests.tiny_cells import run_tiny

CELL = "adult-train-p5"


@pytest.mark.parametrize("fault", faults.TRAIN, ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch.setattr)
    line = run_tiny(CELL)
    assert not line["correct"], line["limits"]
