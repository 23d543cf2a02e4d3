"""Prefill/decode continuation for recurrent families: prefilling a prompt
and then decoding must match pure step-by-step decode exactly (hybrid SSM
states, shared-attn KV, mLSTM/sLSTM states all carried correctly)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model

# f32 isolates the state-carrying logic from bf16 parallel-vs-sequential
# rounding noise (the chunked and stepwise forms order reductions
# differently; numerics equivalence in f32 is the correctness statement).
def f32_cfg(arch):
    return dataclasses.replace(get_smoke(arch), remat=False,
                               dtype=jnp.float32)


def f32_params(model, key):
    """ArrayDef defaults keep params bf16; upcast so the equivalence test is
    exact (activations inherit the embed dtype)."""
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        model.init(key))


@pytest.mark.parametrize("arch", ["zamba2_7b", "xlstm_350m",
                                  "granite_4_0_h_small"])
def test_prefill_then_decode_matches_stepwise(arch):
    cfg = f32_cfg(arch)
    model = build_model(cfg)
    params = f32_params(model, jax.random.PRNGKey(0))
    S, n_new, cache_len = 8, 4, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab, S).astype(np.int32)
    decode = jax.jit(model.decode)

    # Path A: prefill, then greedy decode.
    cache = model.init_cache(1, cache_len)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(tokens[None])}, cache)
    a = []
    pos = S
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits[0]))
        a.append(nxt)
        logits, cache = decode(params, cache, jnp.asarray([[nxt]]),
                               jnp.int32(pos))
        pos += 1

    # Path B: pure step-by-step decode.
    cache = model.init_cache(1, cache_len)
    for t in range(S):
        logits_b, cache = decode(params, cache, jnp.asarray([[tokens[t]]]),
                                 jnp.int32(t))
    b = []
    pos = S
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits_b[0]))
        b.append(nxt)
        logits_b, cache = decode(params, cache, jnp.asarray([[nxt]]),
                                 jnp.int32(pos))
        pos += 1
    assert a == b, (arch, a, b)


@pytest.mark.parametrize("arch", ["zamba2_7b", "xlstm_350m",
                                  "granite_4_0_h_small"])
def test_prefill_logits_match_stepwise_logits(arch):
    """The prefill's final-position logits themselves agree with stepwise
    decode at the same position (tight tolerance: same math, chunked vs
    sequential)."""
    cfg = f32_cfg(arch)
    model = build_model(cfg)
    params = f32_params(model, jax.random.PRNGKey(1))
    S = 8
    rng = np.random.default_rng(2)
    tokens = rng.integers(1, cfg.vocab, S).astype(np.int32)
    cache = model.init_cache(1, 16)
    lp, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(tokens[None])}, cache)
    cache = model.init_cache(1, 16)
    decode = jax.jit(model.decode)
    for t in range(S):
        ld, cache = decode(params, cache, jnp.asarray([[tokens[t]]]),
                           jnp.int32(t))
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(ld, np.float32),
                               atol=2e-3, rtol=2e-3)
    assert int(jnp.argmax(lp[0])) == int(jnp.argmax(ld[0]))
