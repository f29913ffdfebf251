"""A whole run of the chip benchmark on the host at a tiny size, with the
look for a chip skipped: sound, it comes out correct; with the timed path
broken underneath, ``correct`` comes out false. Also: the command itself
refuses a host without a TPU and prints no result."""
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from chipbench import spec  # noqa: E402
from chipbench.run import run_cell  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402

BENCH = spec.load_benchmark()
CELL = spec.cell(BENCH, "qwen2_5_3b.chat")


def _tiny_run(seed=2**31 + 99):
    conf = spec.load_config(BENCH, CELL["config"])
    conf.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
                num_key_value_heads=1, head_dim=32, num_hidden_layers=2,
                vocab_size=256)
    conf["serving"] = dict(conf["serving"], lanes_per_pool=4, max_len=512)
    mix = spec.load_traffic(CELL["traffic"])
    return run_cell(CELL, conf, mix, rate_per_s=8.0, bench=BENCH, seed=seed,
                    seconds=2.0,
                    trace=False, t_start=time.perf_counter(), peaks=None)


def _roll_tokens(orig):
    def decode_paged(self, tokens, cache, live):
        logits, cache = orig(self, tokens, cache, live)
        return jnp.roll(logits, 1, axis=-1), cache
    return decode_paged


def _state_unchanged(orig):
    def decode_paged(self, tokens, cache, live):
        logits, _ = orig(self, tokens, cache, live)
        return logits, cache
    return decode_paged


def _half_the_lanes(orig):
    """The first, third, ... live lane left out: at least half of them,
    however few are live."""
    def decode_paged(self, tokens, cache, live):
        rank = jnp.cumsum(live) - 1
        return orig(self, tokens, cache, live & (rank % 2 == 1))
    return decode_paged


def test_sound_run_is_correct():
    out = _tiny_run()
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"itl_p95_ms", "output_tok_s", "setup_s"}


@pytest.mark.parametrize("fault", [_roll_tokens, _state_unchanged,
                                   _half_the_lanes])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(InferenceEngine, "decode_paged",
                        fault(InferenceEngine.decode_paged))
    out = _tiny_run()
    assert out["correct"] is False
    gap = out["check"]["served_logit_gap_sd"]
    assert gap["value"] > gap["limit"]


def _run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         CELL["name"], "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run_command(ROOT)
    assert proc.returncode != 0
    assert '"correct": true' not in proc.stdout
    assert "TPU" in proc.stderr


def test_command_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
