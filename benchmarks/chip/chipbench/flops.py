"""Operations and bytes that the algorithm needs, from shapes alone.

``conf`` is a configuration file of ``benchmarks/chip/configs`` (published
key names). Counts are model work: padded chunk rows, idle lanes and the
null-block writes the program also computes are not counted.
"""
from __future__ import annotations

from typing import Iterable


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def window(conf: dict):
    """The attention window in tokens, or None where attention is full."""
    if not conf.get("use_sliding_window", True):
        return None
    return conf.get("sliding_window")


def layer_matmul_params(conf: dict) -> int:
    """Weights a token passes through in one decoder layer's matmuls."""
    d, hd = conf["hidden_size"], head_dim(conf)
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return attn + 3 * d * conf["intermediate_size"]


def _span(conf: dict, kv_len: int) -> int:
    """Keys one query attends to under the sliding window."""
    w = window(conf)
    return min(kv_len, w) if w else kv_len


def _attn_flops(conf: dict, spans: int) -> int:
    """QK^T and PV over ``spans`` query-key pairs, every layer."""
    return 4 * conf["num_attention_heads"] * head_dim(conf) * spans \
        * conf["num_hidden_layers"]


def decode_flops(conf: dict, kv_lens: Iterable[int]) -> int:
    """One batched decode step over the live lanes; ``kv_lens`` includes
    each lane's new token."""
    kv_lens = list(kv_lens)
    per_tok = 2 * (layer_matmul_params(conf) * conf["num_hidden_layers"]
                   + conf["hidden_size"] * conf["vocab_size"])
    return per_tok * len(kv_lens) + _attn_flops(
        conf, sum(_span(conf, k) for k in kv_lens))


def prefill_flops(conf: dict, start: int, n_valid: int) -> int:
    """One prefill chunk of ``n_valid`` prompt tokens after ``start`` cached
    ones; logits only for the chunk's last token."""
    mm = 2 * layer_matmul_params(conf) * conf["num_hidden_layers"] * n_valid
    head = 2 * conf["hidden_size"] * conf["vocab_size"]
    spans = sum(_span(conf, start + i + 1) for i in range(n_valid))
    return mm + head + _attn_flops(conf, spans)


def paged_attn_cost(conf: dict, kv_lens: Iterable[int],
                    kv_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of one paged decode attention call, i.e. one layer:
    the live lanes' K and V rows read once, their queries read and their
    outputs written once."""
    hd = head_dim(conf)
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    kv_lens = list(kv_lens)
    spans = sum(_span(conf, k) for k in kv_lens)
    flops = 4 * hq * hd * spans
    nbytes = 2 * hkv * hd * kv_bytes * spans + 2 * len(kv_lens) * hq * hd * kv_bytes
    return flops, nbytes
