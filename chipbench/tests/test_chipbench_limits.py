"""Each cell's committed limits against the readings they were set from
(``calibration/<cell>.jsonl``: ``calibrate.py`` on the chip at the
cell's own sizes). Judged as a run judges them, every reading of the
program is correct, and the control and every planted fault are not."""
import json

import pytest

from chipbench.harness import BENCH_DIR, Bench, judge

CELLS = [w["name"] for w in Bench().spec["workloads"]]


def _readings(cell: str) -> list:
    path = BENCH_DIR / "calibration" / f"{cell}.jsonl"
    return [json.loads(line) for line in path.read_text().splitlines()]


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_is_correct_on_every_calibration_seed(cell):
    limits = Bench().workload(cell)["limits"]
    program = [r for r in _readings(cell) if r["what"] == "program"]
    assert len({r["seed"] for r in program}) >= 12
    for r in program:
        assert judge(r["numbers"], limits)[1], r


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_and_every_fault_fail(cell):
    limits = Bench().workload(cell)["limits"]
    others = [r for r in _readings(cell) if r["what"] != "program"]
    assert "control_fp8" in {r["what"] for r in others}
    assert any(r["what"].startswith("fault_") for r in others)
    for what in {r["what"] for r in others}:
        assert len({r["seed"] for r in others if r["what"] == what}) >= 3
    for r in others:
        assert not judge(r["numbers"], limits)[1], r
