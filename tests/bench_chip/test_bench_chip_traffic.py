"""The chip benchmark's traffic generator and its data-driven lookup."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH_DIR))

from chipbench import spec  # noqa: E402
from chipbench.traffic import BLOCK, GROUP, Traffic, _quantiles  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _traffic(name, seed, *, max_len=2048, vocab=151936, rate_per_s=2.0):
    mix = spec.load_traffic(name)
    return Traffic(mix, rate_per_s=rate_per_s, max_len=max_len, vocab=vocab,
                   seed=seed)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_order(name):
    a = [_traffic(name, BIG_SEED).request(i) for i in range(2 * BLOCK)]
    b = [_traffic(name, BIG_SEED).request(i) for i in range(2 * BLOCK)]
    c = [_traffic(name, BIG_SEED + 1).request(i) for i in range(2 * BLOCK)]
    assert [(r.m, r.n, r.due_s) for r in a] == [(r.m, r.n, r.due_s) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [(r.m, r.n) for r in a] != [(r.m, r.n) for r in c]
    # each block holds the same sizes whatever the seed: only the order moves
    for k in range(2):
        blk = slice(k * BLOCK, (k + 1) * BLOCK)
        assert Counter(r.m for r in a[blk]) == Counter(r.m for r in c[blk])
        assert Counter(r.n for r in a[blk]) == Counter(r.n for r in c[blk])


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_every_group_holds_one_quantile_of_each_stratum(seed):
    k = BLOCK // GROUP
    for stream in (1, 2, 3):
        for block in range(3):
            u = _quantiles(seed, stream, block)
            assert sorted(u) == sorted((np.arange(BLOCK) + 0.5) / BLOCK)
            strata = np.floor(u * BLOCK).astype(int) // k
            for g in range(k):
                assert sorted(strata[g * GROUP:(g + 1) * GROUP]) == list(range(GROUP))
    # so a group of due times spans about GROUP mean gaps, whatever the seed
    t = _traffic("chat", seed, rate_per_s=4.0)
    due = np.array([t.request(i).due_s for i in range(4 * BLOCK)])
    spans = np.diff(due[::GROUP])
    assert np.all(np.abs(spans - GROUP / 4.0) < 0.35 * GROUP / 4.0)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("max_len", [1024, 2048])
def test_lengths_fit_the_lanes_and_the_clips(name, max_len):
    mix = spec.load_traffic(name)
    t = _traffic(name, 3, max_len=max_len, vocab=32000)
    reqs = [t.request(i) for i in range(4 * BLOCK)]
    for r in reqs:
        assert 1 <= r.m and 1 <= r.n and r.m + r.n <= max_len
        assert len(r.prompt) == r.m and r.prompt.min() >= 0
        assert r.prompt.max() < 32000
        lo = mix["input"].get("min", 1)
        hi = mix["input"].get("max", max_len)
        assert lo <= r.m <= hi
    out = mix["output"]
    assert all(r.n <= out.get("max", out.get("high", max_len)) for r in reqs)


def test_chat_splits_61_39_at_t_in_32():
    reqs = [_traffic("chat", 7).request(i) for i in range(4 * BLOCK)]
    assert sum(r.m <= 32 for r in reqs) / len(reqs) == pytest.approx(39 / 64)


def test_uniform_lengths_cover_their_range():
    mix = {"name": "u", "input": {"dist": "lognormal", "mu": 6.4, "sigma": 0.35,
                                  "min": 256, "max": 960},
           "output": {"dist": "uniform", "low": 16, "high": 64},
           "arrivals": {"process": "poisson"}}
    t = Traffic(mix, rate_per_s=1.0, max_len=1024, vocab=100, seed=BIG_SEED)
    reqs = [t.request(i) for i in range(BLOCK)]
    assert all(256 <= r.m <= 960 and 16 <= r.n <= 64 for r in reqs)
    assert min(r.n for r in reqs) == 16 and max(r.n for r in reqs) == 64
    assert not any(r.m <= 32 for r in reqs)


def test_poisson_due_times_rise_at_the_rate():
    bench = spec.load_benchmark()
    rate = spec.cell_rate(spec.cell(bench, "qwen2_5_3b.chat"))
    t = _traffic("chat", BIG_SEED, rate_per_s=rate)
    reqs = t.requests_due_before(200 / rate)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert len(reqs) == pytest.approx(200, rel=0.1)
    assert np.diff(due).mean() == pytest.approx(1 / rate, rel=0.1)


def test_poisson_mix_needs_the_cells_rate():
    with pytest.raises(ValueError):
        _traffic("chat", 1, rate_per_s=None)


def test_backlog_has_no_due_times():
    t = _traffic("chat_backlog", 1, rate_per_s=None)
    assert t.depth_per_pool > 0 and t.request(5).due_s == 0.0
    with pytest.raises(ValueError):
        t.requests_due_before(1.0)


def test_every_named_piece_loads():
    """A configuration, traffic mix or per-layer metric is a file found by
    its name: every one that BENCHMARK.json names loads, and every reader
    present is named."""
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        conf = spec.load_config(bench, c["name"])
        assert conf["name"] == c["name"] and c["file"].endswith(f"{c['name']}.json")
        assert set(c["reduced"]) <= set(conf["changed_from_source"])
        for key in ("dtype", "lanes_per_pool", "max_len", "block_size", "chunk"):
            assert key in conf["serving"]
    for w in bench["workloads"]:
        mix = spec.load_traffic(w["traffic"])
        assert mix["name"] == w["traffic"]
        rate = spec.cell_rate(w)
        assert (rate is not None) == (mix["arrivals"]["process"] == "poisson")
        assert rate is None or rate > 0
    readers = {m["name"]: spec.load_reader(m["name"]) for m in bench["per_layer"]}
    assert all(callable(r) for r in readers.values())
    assert set(readers) == {p.stem for p in (BENCH_DIR / "metrics").glob("*.py")}
    assert {w["traffic"] for w in bench["workloads"]} <= set(MIXES)
    # a rate file belongs to a cell that BENCHMARK.json names
    cells = {w["name"] for w in bench["workloads"]}
    assert {p.name[:-len(".json")] for p in (BENCH_DIR / "cells").glob("*.json")} <= cells
    configs = {c["name"] for c in bench["configs"]}
    files = {p.stem for p in (BENCH_DIR / "configs").glob("*.json")}
    assert configs <= files
    for name in files:
        with open(BENCH_DIR / "configs" / f"{name}.json") as f:
            assert json.load(f)["name"] == name


def test_each_cell_reports_what_its_metrics_move():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.metrics_of(bench, w["name"], "end_to_end")}
        layer = spec.metrics_of(bench, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)
        # an open-loop cell reports a tail of time to first token: end to
        # end where its runs repeat closely enough, else per layer
        ttft = "ttft_p95_ms" in e2e or any(
            m["name"] == "first_token_p95_ms" for m in layer)
        assert ttft == (
            spec.load_traffic(w["traffic"])["arrivals"]["process"] == "poisson")


def test_benchmark_json_names_files_under_paths():
    bench = spec.load_benchmark()
    assert json.loads(json.dumps(bench)) == bench
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
