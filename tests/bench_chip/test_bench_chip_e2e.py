"""End-to-end arithmetic of the chip benchmark on hand-made stamps."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks" / "chip"))

from chipbench import e2e, flops  # noqa: E402
from chipbench.e2e import Record  # noqa: E402


def _rec(i, due, tokens, *, in_window=True):
    return Record(i, "pool", m=4, n=len(tokens) or 3, due_s=due, submit_s=due,
                  in_window=in_window, admit_s=due, token_s=list(tokens))


def test_ttft_counts_every_due_request_and_unfinished_ones_to_the_end():
    recs = [_rec(i, 0.1 * i, [0.1 * i + 0.05 * (i + 1), 9.0]) for i in range(19)]
    recs.append(_rec(19, 9.5, []))                     # never got a token
    recs.append(_rec(20, 11.0, [11.2], in_window=False))  # due after the close
    ttft = e2e.ttft_s(recs, end_s=12.0)
    assert len(ttft) == 20
    assert ttft[-1] == pytest.approx(2.5)              # 12.0 - 9.5
    assert e2e.failed(recs) == 1
    s = e2e.summary(recs, window_s=10.0, end_s=12.0)
    assert s["ttft_p95_ms"] == pytest.approx(1e3 * np.percentile(ttft, 95))
    assert s["ttft_p95_ms"] > 1e3 * 0.05 * 19           # the tail holds it
    assert s["requests_in_window"] == 20 and s["failed"] == 1


def test_first_token_reader_takes_the_window_before_the_profile():
    from types import SimpleNamespace

    from chipbench import spec
    recs = [_rec(i, 0.1 * i, [0.1 * i + 0.05 * (i + 1)]) for i in range(19)]
    recs.append(_rec(19, 1.95, []))                    # never got a token
    recs.append(_rec(20, 8.0, [30.0]))                 # due while profiling
    view = SimpleNamespace(served=SimpleNamespace(records=recs, end_s=12.0),
                           profile_from_s=6.0)
    got = spec.load_reader("first_token_p95_ms")(view)
    want = e2e.ttft_s(recs[:20], end_s=12.0)
    assert got == pytest.approx(1e3 * np.percentile(want, 95))
    view.served.records = recs[20:]
    assert spec.load_reader("first_token_p95_ms")(view) is None


def test_itl_takes_every_gap_that_ends_in_the_window():
    recs = [_rec(0, 0.0, [0.1, 0.1, 0.3, 0.6]),     # a zero gap is a gap
            _rec(1, 0.0, [9.0, 9.5, 10.5]),         # the last gap ends late
            _rec(2, 0.0, [])]
    gaps = e2e.itl_s(recs, window_s=10.0)
    assert sorted(gaps) == pytest.approx(sorted([0.0, 0.2, 0.3, 0.5]))
    s = e2e.summary(recs, window_s=10.0, end_s=10.0)
    assert s["itl_samples"] == 4
    assert s["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))


def test_output_rate_is_tokens_in_the_window_over_the_whole_window():
    recs = [_rec(0, 0.0, [0.5, 1.0, 1.5]), _rec(1, 0.0, [9.9, 10.1, 12.0])]
    # 4 tokens inside [0, 10], however idle the rest of the window was
    assert e2e.output_tok_s(recs, window_s=10.0) == pytest.approx(0.4)


def test_op_counts_follow_the_shapes():
    conf = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
            "vocab_size": 10, "sliding_window": 5}
    mm = 8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8 + 3 * 8 * 16
    assert flops.layer_matmul_params(conf) == mm
    per_tok = 2 * (3 * mm + 8 * 10)
    attn = lambda spans: 4 * 2 * 4 * spans * 3  # noqa: E731
    assert flops.decode_flops(conf, [2, 9]) == 2 * per_tok + attn(2 + 5)
    assert flops.prefill_flops(conf, 3, 2) == 2 * 3 * mm * 2 + 2 * 8 * 10 + attn(4 + 5)
    f, b = flops.paged_attn_cost(conf, [2, 9])
    assert f == 4 * 2 * 4 * 7
    assert b == 2 * 1 * 4 * 2 * 7 + 2 * 2 * 2 * 4 * 2
    conf["use_sliding_window"] = False
    assert flops.paged_attn_cost(conf, [9])[0] == 4 * 2 * 4 * 9
