"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them and the longest of each pool, is run through the plain
reference over its prompt and served tokens. For every served token the
number compared is how far its reference logit lies below the reference's
best at that position, in units of that position's logit standard
deviation. Greedy serving that agrees with the reference gives 0; bf16
rounding flips near-ties and gives small gaps. The limit on the widest gap
belongs to the configuration (``check_limits`` in its file): it was set
from chip readings of the program and of the control (the same reference
computed in a lower precision), as ``PERF.md`` records.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import logits_at, served_gaps

SAMPLE = 8


def pick_sample(records, seed: int, k: int = SAMPLE) -> list:
    """The longest finished request of the window, the longest of each
    pool, and seeded others up to ``k``."""
    done = [r for r in records if r.in_window and r.req.done]
    if not done:
        return []
    size = lambda r: (r.m + r.n, -r.index)  # noqa: E731
    chosen = [max(done, key=size)]
    for pool in sorted({r.pool for r in done}):
        best = max((r for r in done if r.pool == pool), key=size)
        if best not in chosen:
            chosen.append(best)
    rest = [r for r in done if r not in chosen]
    take = min(k - len(chosen), len(rest))
    if take > 0:
        pick = np.random.default_rng([int(seed), 9]).choice(len(rest), take,
                                                            replace=False)
        chosen += [rest[i] for i in sorted(pick)]
    return chosen


def compare(params, conf: dict, sample, *, controls=()) -> dict:
    """Widest served-token gap over the sample, and for each precision in
    ``controls`` the widest gap of the tokens that precision puts first."""
    vocab = conf["vocab_size"]
    gaps, oov, n_tok = [], 0, 0
    low_gaps = {q: [] for q in controls}
    for r in sample:
        served = np.asarray(r.req.out_tokens[:r.n], np.int64)
        oov += int(np.sum((served < 0) | (served >= vocab)))
        served = np.clip(served, 0, vocab - 1)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(r.m - 1, r.m - 1 + len(served))
        ref = logits_at(params, conf, seq, rows)
        gaps.append(float(served_gaps(ref, served).max()))
        n_tok += len(served)
        for q in controls:
            low = logits_at(params, conf, seq, rows, quant=q)
            low_gaps[q].append(float(served_gaps(ref, low.argmax(-1)).max()))
    out = {"sampled_requests": len(sample), "served_tokens_compared": n_tok,
           "tokens_out_of_vocab": oov,
           "served_logit_gap_sd": max(gaps) if gaps else float("nan"),
           "per_request_gap_sd": gaps}
    for q, g in low_gaps.items():
        out[f"control_{q}_gap_sd"] = max(g) if g else float("nan")
        out[f"per_request_control_{q}_gap_sd"] = g
    return out


def coverage(sample, conf: dict) -> dict:
    """What the sample exercised: pools, prefill chunks and KV blocks."""
    sv = conf["serving"]
    return {"pools": sorted({r.pool for r in sample}),
            "max_prompt_chunks": max((-(-r.m // sv["chunk"]) for r in sample),
                                     default=0),
            "max_context_blocks": max((-(-(r.m + r.n) // sv["block_size"])
                                       for r in sample), default=0)}


def verdict(result: dict, conf: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) of a comparison."""
    limit = conf["check_limits"]["served_logit_gap_sd"]
    if limit is None:
        raise ValueError(f"{conf['name']}: no limit set for the check")
    numbers = {
        "served_logit_gap_sd": {"value": result["served_logit_gap_sd"],
                                "limit": limit},
        "tokens_out_of_vocab": {"value": result["tokens_out_of_vocab"],
                                "limit": 0},
    }
    ok = (result["served_tokens_compared"] > 0
          and result["served_logit_gap_sd"] <= limit
          and result["tokens_out_of_vocab"] == 0)
    return ok, numbers
