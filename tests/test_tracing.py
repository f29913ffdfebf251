"""The serving path's own spans in a profiler trace, on the host at a tiny
size: the closed set of names, how they nest, their stats, the requests'
queue stamps, and the names the jitted paged steps lower to."""
import sys
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import build_router
from repro.serving.batching import (SPANS, ContinuousBatcher,
                                    PagedContinuousBatcher, Request)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))

from chipbench import spans as S  # noqa: E402
from chipbench import trace as T  # noqa: E402

PROMPTS = [5, 21, 9, 40, 3, 12]         # tokens; a chunk is 32 with lanes 2
NEW_TOKENS = [4, 2, 6, 3, 5, 2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Six requests submitted and served to the end through two pools of
    two lanes, the whole of it profiled. Returns (every host-plane event
    name seen, the program's spans, the routed requests)."""
    router = build_router("qwen2.5-3b", t_in=16, max_len=128, lanes=2)
    rng = np.random.default_rng(0)
    for cb in router.batchers.values():          # compile outside the profile
        cb.submit(_request(rng, 33, 2))
        cb.run()
    logdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        routed = [router.submit(rng.integers(0, 100, m), n)
                  for m, n in zip(PROMPTS, NEW_TOKENS)]
        while any(cb.busy for cb in router.batchers.values()):
            for cb in router.batchers.values():
                if cb.busy:
                    cb.step()
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(logdir)
    from jax.profiler import ProfileData
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == T.HOST_PLANE
             for line in plane.lines for ev in line.events}
    return names, S.read_spans(path), routed


def _request(rng, m, n):
    return Request(-1, rng.integers(0, 100, m), n)


def _parsed(spans, name):
    return [(e, stats) for e in spans
            for base, stats in [S.parse(e.name)] if base == name]


def test_benchmark_matches_the_programs_span_set():
    assert S.PROGRAM_SPANS == SPANS
    assert set(S.PART_OF) | {"router.submit"} == set(SPANS)


def test_only_the_closed_set_of_names(traced):
    names, spans, _ = traced
    ours = {n.split("#")[0] for n in names
            if n.startswith(("batcher.", "router."))}
    assert ours == set(SPANS)
    assert {S.parse(e.name)[0] for e in spans} == set(SPANS)


def test_every_batcher_span_lies_in_a_tick(traced):
    _, spans, _ = traced
    steps = [e for e, _ in _parsed(spans, "batcher.step")]
    for e in spans:
        name = S.parse(e.name)[0]
        if name in ("batcher.step", "router.submit"):
            assert not any(s.start_ns < e.start_ns < s.end_ns for s in steps)
            continue
        assert any(s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
                   for s in steps), name
    # the children of a tick do not overlap one another
    kids = sorted((e.start_ns, e.end_ns) for e in spans
                  if S.parse(e.name)[0] not in ("batcher.step",
                                                "router.submit"))
    assert all(b[0] >= a[1] for a, b in zip(kids, kids[1:]))


def test_stats_match_the_requests(traced):
    _, spans, routed = traced
    by_rid = {r.rid: r for r in routed}
    submits = {st["rid"]: st["m"] for _, st in _parsed(spans, "router.submit")}
    assert submits == {r.rid: len(r.request.tokens) for r in routed}
    chunked = defaultdict(int)
    for _, st in _parsed(spans, "batcher.prefill"):
        if "rid" in st:
            chunked[st["rid"]] += st["tokens"]
    assert dict(chunked) == {r.rid: len(r.request.tokens) for r in routed}
    retired = sorted(st["rid"] for _, st in _parsed(spans, "batcher.retire"))
    assert retired == sorted(by_rid)
    # every token after a request's first came from a decode call, and a
    # decode call's ``lanes`` counts the lanes it advanced
    lanes = [st["lanes"] for _, st in _parsed(spans, "batcher.decode")]
    assert sum(lanes) == sum(len(r.request.out_tokens) - 1 for r in routed)
    assert all(1 <= k <= 2 for k in lanes)
    phases = {st["phase"] for _, st in _parsed(spans, "batcher.sync")}
    assert phases == {"prefill", "decode"}


def test_decode_blocks_stat_counts_the_blocks_the_kernel_walks(monkeypatch):
    """Each ``batcher.decode`` span's ``blocks`` stat is what the paged
    decode kernel walks a layer: over the call's live lanes, the sum of
    cdiv(kv_len, block_size), with kv_len (``pos`` + 1) read from the cache
    the decode step is handed."""
    import repro.serving.batching as B

    router = build_router("qwen2.5-3b", t_in=16, max_len=128, lanes=2)
    opened, walked = [], []

    class Recorder:
        def __init__(self, name, **stats):
            if name == "batcher.decode":
                opened.append(stats["blocks"])

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def recording(decode):
        def call(tokens, cache, live):
            kv_len = np.asarray(cache["pos"])[np.asarray(live)] + 1
            bs = cache["kp"].shape[3]
            walked.append(int(np.sum(-(-kv_len // bs))))
            return decode(tokens, cache, live)
        return call

    monkeypatch.setattr(B, "TraceAnnotation", Recorder)
    for eng in {id(cb.engine): cb.engine
                for cb in router.batchers.values()}.values():
        monkeypatch.setattr(eng, "decode_paged", recording(eng.decode_paged))
    rng = np.random.default_rng(1)
    for m, n in ((15, 4), (31, 3), (47, 3), (4, 5)):   # contexts cross blocks
        router.submit(rng.integers(0, 100, m), n)
    while any(cb.busy for cb in router.batchers.values()):
        for cb in router.batchers.values():
            if cb.busy:
                cb.step()
    assert opened == walked
    assert max(walked) > 2            # contexts past one block per lane


def test_requests_carry_their_queue_stamps(traced):
    _, _, routed = traced
    for r in routed:
        req = r.request
        assert req.done
        assert req.queued_s is not None and req.admitted_s is not None
        assert req.queued_s <= req.admitted_s


def test_dense_batcher_stamps_admission_too():
    router = build_router("qwen2.5-3b", t_in=16, max_len=64, lanes=2)
    engine = next(iter(router.engines.values()))
    cb = ContinuousBatcher(engine, slots=1)
    reqs = [Request(i, np.arange(1, 6), 2) for i in range(2)]
    for r in reqs:
        cb.submit(r)
    cb.run()
    assert all(r.queued_s <= r.admitted_s for r in reqs)
    assert reqs[0].admitted_s <= reqs[1].admitted_s


@pytest.mark.parametrize("step", ["decode_step_paged", "prefill_paged_chunk"])
def test_paged_steps_lower_under_their_names(step):
    router = build_router("qwen2.5-3b", t_in=16, max_len=64, lanes=2)
    cb = next(iter(router.batchers.values()))
    assert isinstance(cb, PagedContinuousBatcher)
    eng = cb.engine
    if step == "decode_step_paged":
        lowered = eng._decode_paged.lower(
            params=eng.params, tokens=jnp.zeros((2, 1), jnp.int32),
            cache=cb.cache, live=jnp.ones((2,), bool))
    else:
        lowered = eng._prefill_chunk.lower(
            params=eng.params, tokens=jnp.zeros((1, cb.chunk), jnp.int32),
            cache=cb.cache, lane=0, n_valid=3)
    assert lowered.as_text().startswith(f"module @jit_{step} ")
