"""The split of the device's idle time by the program's own spans
(``chipbench.spans``) and the numbers read from it, on hand-made nested
events and on a trimmed recording of a TPU v5e trace that holds the spans;
and ``admit_wait_p95_ms`` over the requests' queue stamps."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "benchmarks" / "chip"))

from chipbench import spans as S  # noqa: E402
from chipbench import spec  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.e2e import p95  # noqa: E402
from chipbench.trace import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", T.HOST_PLANE


def _ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def _span(name, start_ms, end_ms, **stats):
    return _ev(HOST, "python3", S.with_stats(name, stats.items()), start_ms,
               end_ms - start_ms)


HARNESS = [_ev(HOST, "python3", "tick.eff", 0, 10),
           _ev(HOST, "python3", "submit", 10, 2),
           _ev(HOST, "python3", "tick.perf", 12, 8)]
PROGRAM = [
    _span("batcher.step", 0.5, 9.5),
    _span("batcher.admit", 0.5, 1.5),
    _span("batcher.prefill", 1.5, 3, rid=7, tokens=5),
    _span("batcher.decode", 3, 4, lanes=3),
    _span("batcher.sync", 4, 8, phase="decode"),
    _span("batcher.retire", 8.5, 9, rid=7),
    _span("router.submit", 10.5, 11.5, rid=8, m=5),
    _span("batcher.step", 12.5, 19.5),
    _span("batcher.decode", 12.5, 13, lanes=5),
    _span("batcher.sync", 13, 19, phase="decode"),
]


def _ops(plane, shift_ms=0.0):
    return [_ev(plane, T.OPS_LINE, f"fusion.{i}", a + shift_ms, d)
            for i, (a, d) in enumerate([(1, 1), (4, 3.5), (13.5, 5)])]


def test_idle_goes_to_the_innermost_span():
    """Idle [0,1] [2,4] [7.5,13.5] [18.5,20] ms of a 20 ms window:
    dispatch 0.5 (admit) + 1 (prefill) + 1 + 0.5 (decode) + 0.5 (retire);
    sync 0.5 + 0.5 + 0.5; bookkeeping [8,8.5] [9,9.5] [19,19.5]; outside
    [0,0.5] [9.5,12.5] (router.submit included) [19.5,20]."""
    events = HARNESS + _ops(DEV)
    s = S.summarize(events, PROGRAM)
    assert s.window_s == pytest.approx(0.020)
    assert s.busy_s == pytest.approx(0.0095)
    assert s.idle_s == pytest.approx({"dispatch": 0.0035, "sync": 0.0015,
                                      "bookkeeping": 0.0015,
                                      "outside": 0.004})
    assert s.decode_lanes == [3, 5]
    # the harness's own reduction reads as it did without the spans
    r = T.reduce(events)
    assert (r.window_s, r.busy_s) == (pytest.approx(s.window_s),
                                      pytest.approx(s.busy_s))


def test_parts_sum_to_the_idle_share_over_devices():
    events = HARNESS + _ops(DEV) + _ops("/device:TPU:1", shift_ms=0.7)
    s = S.summarize(events, PROGRAM)
    r = T.reduce(events)
    assert sum(s.idle_s.values()) == pytest.approx(r.window_s - r.busy_s)
    assert sum(s.idle_pct(p) for p in S.PARTS) == pytest.approx(
        100.0 * (1.0 - r.busy_s / r.window_s))


def test_nothing_to_read_gives_nothing():
    assert S.summarize(HARNESS + _ops(DEV), []) is None
    assert S.summarize(HARNESS, PROGRAM) is None
    no_decode = [e for e in PROGRAM if "decode" not in e.name]
    assert S.summarize(HARNESS + _ops(DEV),
                       no_decode).readings()["decode_batch_mean"] is None


def test_stats_ride_in_the_name():
    name = S.with_stats("batcher.prefill", [("rid", 12), ("tokens", 32)])
    assert name == "batcher.prefill#rid=12,tokens=32#"
    assert S.parse(name) == ("batcher.prefill", {"rid": 12, "tokens": 32})
    assert S.parse("batcher.sync#phase=decode#") == ("batcher.sync",
                                                    {"phase": "decode"})
    assert S.parse("batcher.step") == ("batcher.step", {})
    assert S.is_program_span(PROGRAM[2])
    assert not any(S.is_program_span(e) for e in HARNESS)


def test_readings_of_the_split():
    got = S.summarize(HARNESS + _ops(DEV), PROGRAM).readings()
    assert got == pytest.approx({"idle_dispatch_pct": 17.5,
                                 "idle_sync_pct": 7.5,
                                 "idle_bookkeeping_pct": 7.5,
                                 "idle_outside_pct": 20.0,
                                 "decode_batch_mean": 4.0})


def _record(due_s, submit_s, req):
    return SimpleNamespace(in_window=True, due_s=due_s, submit_s=submit_s,
                           req=req)


def test_admit_wait_reads_the_request_stamps():
    read = spec.load_reader("admit_wait_p95_ms")
    recs = [_record(0.1 * i, 0.1 * i,
                    SimpleNamespace(queued_s=100.0 + 0.1 * i,
                                    admitted_s=100.0 + 0.1 * i + 0.01 * i))
            for i in range(20)]
    recs.append(_record(1.0, 1.0, SimpleNamespace(queued_s=101.0,
                                                  admitted_s=None)))
    recs.append(_record(9.0, 9.0, SimpleNamespace(queued_s=109.0,
                                                  admitted_s=None)))
    run = SimpleNamespace(served=SimpleNamespace(records=recs, end_s=3.0),
                          profile_from_s=5.0)
    # 20 admitted waits 0 .. 0.19 s and one never admitted, waiting from
    # 1.0 s to the end of observation at 3.0 s; the request due after the
    # profile started is left out
    waits = [0.01 * i for i in range(20)] + [2.0]
    assert read(run) == pytest.approx(1e3 * p95(waits))
    # a program that writes no stamps gives nothing
    for r in recs:
        r.req = SimpleNamespace()
    assert read(run) is None


def test_excerpt_keeps_whole_spans_and_the_ops_under_them():
    events = HARNESS + PROGRAM + _ops(DEV)
    kept = S.excerpt(events, 0.0, 12.0)
    names = {S.parse(e.name)[0] for e in kept if e.plane == HOST}
    assert "tick.perf" not in names and "tick.eff" in names
    assert "router.submit" in names
    assert sum(e.plane == DEV for e in kept) == 2      # ops at 1 and 4 ms


def test_recorded_v5e_trace_with_program_spans():
    """0.28 s of a TPU v5e trace of ``qwen2_5_3b.chat`` with the program's
    spans (``idle_split.py --seed 3130000001 --save``): nine ticks of the
    two pools in turn, the harness's spans, the program's and the device
    events under them."""
    events = T.load_events(HERE / "data" / "v5e_qwen_chat_spans.json.gz")
    spans = [e for e in events if S.is_program_span(e)]
    rest = [e for e in events if not S.is_program_span(e)]
    names = [S.parse(e.name)[0] for e in spans]
    assert set(names) == set(S.PROGRAM_SPANS)
    assert names.count("batcher.step") == 9
    r = T.reduce(rest)
    got = S.summarize(rest, spans).readings()
    assert got == pytest.approx({"idle_dispatch_pct": 11.4486,
                                 "idle_sync_pct": 5.8899,
                                 "idle_bookkeeping_pct": 0.2214,
                                 "idle_outside_pct": 0.2435,
                                 "decode_batch_mean": 9.4}, rel=1e-3)
    assert sum(got[f"idle_{p}_pct"] for p in S.PARTS) == pytest.approx(
        100.0 * (1.0 - r.busy_s / r.window_s), rel=1e-9)
    # the paged steps carry their names, so no op is needed to find them
    kinds = T._step_kinds([e for e in rest if e.plane != HOST])
    assert sorted(kinds.values()) == sorted(T.STEP_MODULES)
    assert all(name.startswith(f"jit_{kind}(") for name, kind in kinds.items())
    assert len(r.step_ns["decode_step_paged"]) == 9
