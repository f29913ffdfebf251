"""mistral_7b_16l's served path against the chip benchmark's plain reference,
on the host at a small size, with the sliding window binding.

The benchmark's own router (``harness.build_router``) serves two requests,
one to each pool, through ``PagedContinuousBatcher`` and the engine's paged
steps, with the Pallas paged decode kernel in interpret mode. The window is
cut to 24 tokens, blocks and chunks to 8, so prompts span several chunks,
chunk and window edges fall inside blocks, and every context runs past three
windows. The logits the served path computed at the end of each prompt and
at every decode position are compared with ``reference.logits_at`` over the
same tokens."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from chipbench import harness, reference, weights  # noqa: E402
from repro.kernels import ops  # noqa: E402

WINDOW = 24
# (prompt tokens, tokens asked for): the short prompt goes to the
# efficiency pool (t_in 32), the long one to the performance pool; both
# contexts pass 3 x WINDOW = 72 tokens
REQUESTS = ((29, 60), (45, 40))
# both sides float32 on the host, every token the same: they differ by
# summation order alone (the kernel's online softmax, the chunked prefill):
# about 3e-6 of a position's logit spread. The reference without the window
# reads above 3, at int8 above 0.1
TOL = 1e-4


def _conf():
    with open(CHIP / "configs" / "mistral_7b_v0_1" / "mistral_7b_16l.json") as f:
        conf = json.load(f)
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=1, head_dim=16, num_hidden_layers=2,
                vocab_size=97, sliding_window=WINDOW)
    conf["serving"] = dict(conf["serving"], dtype="float32", lanes_per_pool=2,
                           max_len=128, block_size=8, chunk=8)
    return conf


class _Recorder:
    """A pool's engine that keeps the logits of every paged step, keyed by
    (request id, position of the query token)."""

    def __init__(self, engine, batcher, seen):
        self._engine, self._cb, self._seen = engine, batcher, seen

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prefill_chunk(self, tokens, cache, lane, n_valid):
        logits, out = self._engine.prefill_chunk(tokens, cache, lane, n_valid)
        req = self._cb.active[lane]
        end = int(np.asarray(cache["pos"])[lane]) + n_valid
        if end == len(req.tokens):                  # the prompt's last chunk
            self._seen[req.rid, end - 1] = np.asarray(logits[0])
        return logits, out

    def decode_paged(self, tokens, cache, live):
        logits, out = self._engine.decode_paged(tokens, cache, live)
        pos = np.asarray(cache["pos"])
        for i in np.flatnonzero(np.asarray(live)):
            self._seen[self._cb.active[i].rid, int(pos[i])] = \
                np.asarray(logits[i])
        return logits, out


@pytest.fixture(scope="module")
def served():
    """(conf, params, [(tokens, rows, served logits)] per request)."""
    conf = _conf()
    params = weights.make_params(conf, 2**31 + 15, "float32")
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "_FORCED", "pallas_interpret")
    try:
        router = harness.build_router(conf, params)
        seen = {}
        for cb in router.batchers.values():
            cb.engine = _Recorder(cb.engine, cb, seen)
        rng = np.random.default_rng(15)
        routed = [router.submit(rng.integers(0, 97, m).astype(np.int32), n)
                  for m, n in REQUESTS]
        router.drain()
    finally:
        mp.undo()
    out = []
    for r in routed:
        req = r.request
        assert req.done and len(req.out_tokens) == req.max_new_tokens
        seq = np.concatenate([req.tokens, req.out_tokens[:-1]]).astype(np.int32)
        rows = np.arange(len(req.tokens) - 1, len(seq))
        out.append((seq, rows, np.stack([seen[req.rid, p] for p in rows])))
    assert sorted({r.pool for r in routed}) == sorted(router.batchers)
    return conf, params, out


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.std(want))


def _gaps(served, quant=None, **conf_over):
    conf, params, out = served
    ref_conf = dict(conf, **conf_over)
    return [_gap(got, reference.logits_at(params, ref_conf, seq, rows,
                                          quant=quant))
            for seq, rows, got in out]


def test_traffic_crosses_chunks_blocks_and_the_window(served):
    conf, _, out = served
    sv = conf["serving"]
    for (m, n), (seq, rows, _) in zip(REQUESTS, out):
        assert -(-m // sv["chunk"]) >= 4
        assert m % sv["block_size"] and m % sv["chunk"]   # a chunk ends mid-block
        assert len(seq) + 1 > 3 * WINDOW
        assert len(rows) == n


def test_served_logits_match_the_reference(served):
    assert max(_gaps(served)) < TOL


def test_reference_without_the_window_fails_the_tolerance(served):
    # every position past the first WINDOW attends to keys the window drops
    assert min(_gaps(served, sliding_window=None)) > TOL


def test_reference_one_precision_lower_fails_the_tolerance(served):
    # int8 operands round at about 1/254 of each row's range
    assert min(_gaps(served, quant="int8")) > TOL
