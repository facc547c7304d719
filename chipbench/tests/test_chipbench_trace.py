"""The reduction from a profiler trace to busy time, idle gaps by host
span, self time by operation, and exposed all-reduce time: on intervals
made by hand, and on a small trace recorded on one TPU v5e with
``tools/record_trace.py`` (four steps of matrix products, a 2 ms host
pause in ``loader`` before each)."""
from pathlib import Path

import pytest

from chipbench import trace

RECORDED = Path(__file__).parent / "data" / "trace_v5e_1chip.xplane.pb"


def test_busy_is_the_union_of_operation_intervals():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7), (8, 8)]) == \
        [(0, 3), (5, 7)]
    ops = [("a", 0, 2), ("b", 1, 3), ("c", 5, 6)]
    d = trace.device_numbers(ops, 0, 10, [])
    assert d["busy_ns"] == 4
    assert d["gaps_ns"] == {"other": 6}


def test_nested_operations_are_charged_their_self_time():
    ops = [("while", 0, 10), ("fusion", 1, 3), ("dot", 4, 6), ("copy", 12, 13)]
    assert trace.self_times(ops) == {"while": 6, "fusion": 2, "dot": 2,
                                     "copy": 1}


def test_operations_are_named_by_instruction_and_result_type():
    assert trace.op_name("%fusion.7 = bf16[8,1024]{1,0:T(8,128)} fusion("
                         "bf16[8,1024]{1,0} %p), kind=kOutput") == \
        "fusion.7 bf16[8,1024]"
    assert trace.op_name("%while.3 = (s32[], f32[2]{0}) while((s32[]) %t)") \
        == "while.3"
    assert trace.op_name("jit_step(12)") == "jit_step(12)"


def test_exposed_allreduce_is_the_part_nothing_else_overlaps():
    ops = [("fusion.1", 0, 4), ("all-reduce.3", 3, 7), ("fusion.2", 6, 9),
           ("all-reduce-start.1", 10, 12)]
    d = trace.device_numbers(ops, 0, 12, [])
    assert d["allreduce_ns"] == 6          # [3, 7) and [10, 12)
    assert d["allreduce_exposed_ns"] == 4  # [4, 6) and [10, 12)


def test_gaps_go_to_the_host_span_that_overlaps_them_most():
    spans = [("loader", 0, 4), ("run_step", 4, 10), ("host_fetch", 10, 20)]
    ops = [("fusion", 5, 9)]
    d = trace.device_numbers(ops, 0, 20, spans)
    # [0, 5) lies 4 in loader, 1 in run_step; [9, 20) 10 in host_fetch
    assert d["gaps_ns"] == {"loader": 5, "host_fetch": 11}
    s = trace.summarize([d], 0, 20, n_steps=1)
    assert s["idle_share"] == pytest.approx(1 - 4 / 20)
    assert s["idle_gaps"][0] == ["host_fetch", pytest.approx(11e-9)]


def test_recorded_trace():
    s = trace.reduce_profile(str(RECORDED), [0])
    assert s["steps"] == 4 and s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    ops = dict(s["device_ops"])
    assert sum(ops.values()) <= s["busy_s"] * (1 + 1e-9)
    assert all(not name.startswith("%") and " = " not in name for name in ops)
    gaps = dict(s["idle_gaps"])
    # four 2 ms pauses; a gap that runs on from a longer host_fetch
    # (the first compiles its slice) is charged there
    assert gaps["loader"] > 2 * 0.002
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert s["allreduce_s"] == 0           # one chip: nothing to exchange
