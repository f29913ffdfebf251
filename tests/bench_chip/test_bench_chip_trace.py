"""The chip benchmark's reduction from a profiler trace to device busy time,
step and kernel times and idle gaps, on hand-made events and on a trimmed
recording of a TPU v5e trace."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "benchmarks" / "chip"))

from chipbench import trace as T  # noqa: E402
from chipbench.trace import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", T.HOST_PLANE


def _ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def test_busy_is_the_union_and_gaps_go_to_the_covering_span():
    events = [
        _ev(HOST, "python", "tick.eff", 0, 10),
        _ev(HOST, "python", "submit", 10, 2),
        _ev(HOST, "python", "tick.perf", 12, 8),
        _ev(DEV, T.MODULES_LINE, "jit_decode_step_paged(7)", 1, 6),
        _ev(DEV, T.OPS_LINE, "fusion.1", 1, 3),
        _ev(DEV, T.OPS_LINE, "paged_decode_attention", 3, 3),   # overlaps
        _ev(DEV, T.MODULES_LINE, "jit_prefill_paged_chunk(9)", 14, 4),
        _ev(DEV, T.OPS_LINE, "fusion.2", 14, 4),
        _ev(DEV, T.OPS_LINE, "fusion.3", 30, 1),   # outside the host spans
    ]
    s = T.reduce(events)
    assert s.window_s == pytest.approx(0.020)
    assert s.busy_s == pytest.approx(0.009)          # [1, 6] and [14, 18]
    assert s.step_ns["decode_step_paged"] == [6e6]
    assert s.step_ns["prefill_paged_chunk"] == [4e6]
    assert s.kernel_ns == [3e6]
    idle = dict(s.idle_by_span)
    # [0,1] and [6,10] under tick.eff, [10,12] under submit, [12,14] and
    # [18,20] under tick.perf
    assert idle["tick.eff"] == pytest.approx(0.005)
    assert idle["submit"] == pytest.approx(0.002)
    assert idle["tick.perf"] == pytest.approx(0.004)
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["fusion.2", pytest.approx(0.004)]
    assert len(bd["idle_gaps"]) <= 10


def test_nothing_to_read_gives_nothing():
    assert T.reduce([]) is None
    assert T.reduce([_ev(HOST, "python", "tick.eff", 0, 10)]) is None


def test_events_round_trip(tmp_path):
    events = [_ev(DEV, T.OPS_LINE, "fusion.1", 1, 3),
              _ev(HOST, "python", "submit", 0, 2)]
    T.save_events(events, tmp_path / "e.json.gz")
    assert T.load_events(tmp_path / "e.json.gz") == events


def test_recorded_v5e_trace():
    """0.3 s of a TPU v5e trace of ``qwen2_5_3b.chat`` (two pools ticked in
    turn), trimmed to the harness's host spans in it and the device events
    that overlap them."""
    s = T.reduce(T.load_events(HERE / "data" / "v5e_qwen_chat_trace.json.gz"))
    decode = s.step_ns["decode_step_paged"]
    assert len(decode) == 11 and len(s.step_ns["prefill_paged_chunk"]) == 2
    assert sum(decode) / len(decode) == pytest.approx(18.83e6, rel=1e-3)
    assert len(s.kernel_ns) == 36 * len(decode)      # one call per layer
    assert s.window_s == pytest.approx(0.2961, rel=1e-3)
    assert 0.75 < s.busy_s / s.window_s < 0.85
    assert sum(v for _, v in s.idle_by_span) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert {k for k, _ in s.idle_by_span} <= {
        "tick.tpu-v5lite-eff", "tick.tpu-v5e-perf", "submit", "wait_arrival",
        "outside_spans"}
    assert not any(n.startswith(T.LOOP_OP) for n, _ in s.op_totals)
    assert s.breakdown()["device_ops"][1][0].startswith(
        "%paged_decode_attention")
