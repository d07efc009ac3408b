"""The correctness check of ``eval-cot-4b``, run at a size the CPU holds through
the whole harness (the look for a chip skipped): a sound run is correct;
the control (the reference in float8 in the program's place) and each
fault the cell can have, planted in the timed path, are not."""
import pytest

from bench.tests.tiny import run_tiny

CELL = "eval-cot-4b"


def test_sound_run_is_correct():
    out = run_tiny(CELL, 2718281829)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_is_not_correct():
    out = run_tiny(CELL, 2718281829, control=True)
    assert not out["correct"]
    assert any(v["value"] > v["limit"] for v in out["checks"].values()), out["checks"]


@pytest.mark.parametrize("fault", ["token", "stale_cache", "half_providers", "retrieval", "prompt"])
def test_planted_fault_is_not_correct(fault):
    out = run_tiny(CELL, 1618033989, fault=fault)
    assert not out["correct"], (fault, out["checks"])
