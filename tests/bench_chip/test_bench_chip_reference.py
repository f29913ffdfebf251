"""The chip benchmark's plain reference against the program's own model, at a
size a test run holds, for both configuration families."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "configs"
sys.path.insert(0, str(CONFIGS.parent))

from chipbench import harness, reference, weights  # noqa: E402
from repro.models import model as M  # noqa: E402


def _small(name, **over):
    """The configuration file at reduced widths; every other key kept."""
    with open(CONFIGS / f"{name}.json") as f:
        conf = json.load(f)
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2 if conf["attention_bias"] else 1,
                head_dim=16, num_hidden_layers=2, vocab_size=97, **over)
    return conf


# qwen2.5 family with its q/k/v biases and theta 1e6; mistral with a window
# cut to 8 tokens so that it binds inside the 20-token sequence
FAMILIES = {"qwen2_5_3b": {}, "mistral_7b_16l": {"sliding_window": 8}}


def _program_logits(conf, params, tokens):
    cfg = harness.program_config(conf)
    logits, _ = M.forward_train(params, cfg, {"tokens": jnp.asarray(tokens)[None]},
                                backend="ref")
    return np.asarray(logits[0], np.float64)


def _rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.std(want))


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_reference_matches_the_program_in_float32(name):
    conf = _small(name, **FAMILIES[name])
    params = weights.make_params(conf, 2**31 + 3, "float32")
    tokens = np.random.default_rng(0).integers(0, 97, 20).astype(np.int32)
    want = _program_logits(conf, params, tokens)
    got = reference.logits_at(params, conf, tokens, np.arange(20))
    # both float32 on the host: they differ by summation order alone
    assert _rel_err(got, want) < 1e-4
    # the same comparison computed one precision lower fails it
    low = reference.logits_at(params, conf, tokens, np.arange(20), quant="int8")
    assert _rel_err(low, want) > 1e-2


def test_window_and_bias_are_in_the_reference():
    conf = _small("mistral_7b_16l", sliding_window=8)
    params = weights.make_params(conf, 5, "float32")
    tokens = np.arange(20, dtype=np.int32) % 97
    full = dict(conf, sliding_window=None)
    a = reference.logits_at(params, conf, tokens, np.arange(20))
    b = reference.logits_at(params, full, tokens, np.arange(20))
    assert np.allclose(a[:8], b[:8], rtol=1e-5, atol=1e-5)
    assert not np.allclose(a[8:], b[8:], rtol=1e-3, atol=1e-3)
    conf = _small("qwen2_5_3b")
    params = weights.make_params(conf, 5, "float32")
    flat = jax.tree.map(jnp.zeros_like, params["layers"]["attn"]["q"]["b"])
    no_bias = dict(params, layers=dict(params["layers"], attn=dict(
        params["layers"]["attn"], q=dict(params["layers"]["attn"]["q"], b=flat))))
    tokens = np.arange(12, dtype=np.int32)
    assert not np.allclose(reference.logits_at(params, conf, tokens, [11]),
                           reference.logits_at(no_bias, conf, tokens, [11]))


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_weights_have_the_programs_layout(name):
    conf = _small(name)
    ours = jax.eval_shape(lambda: weights.make_params(conf, 1, "bfloat16"))
    cfg = harness.program_config(conf)
    theirs = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0),
                                                  jnp.bfloat16))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), ours) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), theirs)


def test_weights_come_from_the_seed():
    conf = _small("mistral_7b_16l")
    a = weights.make_params(conf, 2**33 + 1, "bfloat16")
    b = weights.make_params(conf, 2**33 + 1, "bfloat16")
    c = weights.make_params(conf, 2**33 + 2, "bfloat16")
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    diff = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, c)
    assert all(jax.tree.leaves(same)) and not any(jax.tree.leaves(diff))


def test_served_gap_is_zero_for_the_best_token_and_scaled_by_spread():
    ref = np.array([[0.0, 1.0, 3.0, 2.0], [5.0, 1.0, 1.0, 1.0]])
    gaps = reference.served_gaps(ref, np.array([2, 1]))
    assert gaps[0] == 0.0
    assert gaps[1] == pytest.approx(4.0 / np.std(ref[1]))
