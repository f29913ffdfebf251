"""The control of the chip benchmark's check, at a size a test run holds:
the reference computed in float8 (the precision below the configuration's
bf16), put in the program's place at each position of the served prompts
and tokens, reads a gap over the configuration's limit, so the check would
call it not correct; the bf16 program itself stays under it."""
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks" / "chip"))

from chipbench import spec  # noqa: E402
from chipbench.run import run_cell  # noqa: E402

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("seed", [2**31 + 11, 2**31 + 12, 2**31 + 13])
def test_float8_control_fails_the_check(seed):
    cell = spec.cell(BENCH, "qwen2_5_3b.chat")
    conf = spec.load_config(BENCH, cell["config"])
    limit = conf["check_limits"]["served_logit_gap_sd"]
    # sixteen layers at reduced width: the control's error grows with depth,
    # as on the chip at 36 layers
    conf.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
                num_key_value_heads=1, head_dim=64, num_hidden_layers=16,
                vocab_size=1024)
    conf["serving"] = dict(conf["serving"], lanes_per_pool=4, max_len=512)
    mix = spec.load_traffic(cell["traffic"])
    out = run_cell(cell, conf, mix, rate_per_s=6.0, bench=BENCH, seed=seed,
                   seconds=4.0,
                   trace=False, t_start=time.perf_counter(), peaks=None,
                   controls=("fp8",))
    assert out["served_tokens_compared"] > 0
    assert out["control_fp8_gap_sd"] > limit
    assert out["served_logit_gap_sd"] < limit
