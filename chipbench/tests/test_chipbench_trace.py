"""The trace reduction: known numbers on hand-made events and on a small
trace recorded on the CPU (``data/record_cpu_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.trace import Op

DATA = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"
MS = 1_000_000      # ns


def test_reduce_hand_made_events():
    ops = {"/device:TPU:0": [Op(0 * MS, 2 * MS, "a", "jit_ingest"),
                             Op(1 * MS, 3 * MS, "b", "jit_ingest"),
                             Op(6 * MS, 7 * MS, "k", "jit_run", True),
                             Op(9 * MS, 12 * MS, "k", "jit_run", True)]}
    host = [(0, 10 * MS, "window"), (3 * MS, 6 * MS, "step"),
            (7 * MS, 8 * MS, "wait"), (7.5 * MS, 9 * MS, "submit_query")]
    s = trace.reduce(ops, host, {"jit_ingest": 1, "jit_run": 2})
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)     # [0,3] + [6,7] + [9,10]
    assert s.program_s("jit_ingest") == pytest.approx(0.003)
    assert s.program_s("jit_run") == pytest.approx(0.002)
    assert s.kernel_s("jit_run") == pytest.approx(0.002)
    assert s.kernel_s("jit_ingest") == 0
    assert s.calls("jit_(run|ingest)") == 3
    assert s.gaps == [pytest.approx((0.003, "step")),
                      pytest.approx((0.002, "submit_query"))]
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_ingest/a"
    assert b["idle_gaps"][0] == ["step", pytest.approx(0.003)]


def test_op_names_keep_the_instruction_name():
    text = ('%bulk_append.1 = (u32[8,128]) custom-call(u32[8,128] %b), '
            'custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "bulk_append.1"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [Op(0, 4 * MS, "a", "p")],
           "/device:TPU:1": [Op(0, 2 * MS, "a", "p")]}
    s = trace.reduce(ops, [(0, 8 * MS, "window")], {"p": 2})
    assert s.devices == 2 and s.busy_s == pytest.approx(0.003)


def test_devices_idle_in_the_window_are_not_averaged():
    ops = {"/device:TPU:0": [Op(0, 4 * MS, "a", "p")],
           "/device:TPU:1": [],
           "/device:TPU:2": [Op(9 * MS, 10 * MS, "a", "p")]}
    s = trace.reduce(ops, [(0, 8 * MS, "window")], {"p": 2})
    assert s.devices == 1 and s.busy_s == pytest.approx(0.004)


def test_recorded_cpu_trace():
    s = trace.load(DATA)
    assert s.window_s == pytest.approx(0.046130388)
    assert s.busy_s == pytest.approx(0.0316213)
    assert s.program_s("jit_prog_sort") == pytest.approx(0.030896362)
    assert s.program_s("jit_prog_cumsum") == pytest.approx(0.000724938)
    assert s.program_calls == {"jit_prog_sort": 3, "jit_prog_cumsum": 2}
    assert [n for _, n in s.gaps[:3]] == ["wait"] * 3
    assert len(s.breakdown()["device_ops"]) == 10


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({}, [(0, 1, "step")], {})
