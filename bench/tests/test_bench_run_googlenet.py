"""Whole runs of the GoogleNet cells on the CPU at a tiny size: a sound run
is correct; one answer altered where it is produced is not, and neither is
the bfloat16 control put in the program's place."""
import pytest

from bench.tests import faults


def test_sound_b1_run_is_correct(monkeypatch):
    result = faults.run_cell(monkeypatch, "googlenet224-b1")
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"b1_latency_ms", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", ["googlenet224-b1", "googlenet224-poisson"])
def test_altered_answer_is_not_correct(monkeypatch, cell):
    faults.altered_answers(monkeypatch)
    result = faults.run_cell(monkeypatch, cell)
    assert result["correct"] is False
    err = result["checks"]["logit_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("cell", ["googlenet224-b1", "googlenet224-poisson"])
def test_bf16_control_in_place_is_not_correct(monkeypatch, cell):
    faults.bf16_control_in_place(monkeypatch)
    result = faults.run_cell(monkeypatch, cell)
    assert result["correct"] is False
    grid = result["checks"]["bf16_grid"]
    assert grid["value"] == 100.0 > grid["limit"]
