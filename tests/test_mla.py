"""DeepSeek-V2-Lite's mechanisms against their equations, at the SMOKE
size on the CPU: multi-head latent attention (absorbed decode over paged
latents, plain prefill), YaRN rope, a leading dense layer, shared experts
and top-k gates without renormalization — through ``LLM`` on the resident
and the expert-offchip paths.

The reference below is a copy of the equations of
``benchmarks/chip/references/mla_moe.py`` (DeepSeek-V2, arXiv:2405.04434
Sec. 2.1-2.2) in float32 at the highest matmul precision, written apart
from the program.  Like the program it pairs rope lanes half-split (lane i
with lane i + d/2), where DeepSeek's checkpoint pairs interleaved lanes:
the two agree under a fixed permutation of the rope columns of W_q and
W_kv_a, which seeded random weights do not tell apart.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, get_smoke
from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_mla_decode_pallas
from repro.models import build_model
from repro.models.layers import (
    mla_attention,
    mla_attention_decode,
    yarn_freqs,
    yarn_mscale,
)
from repro.models.moe import MoEConfig, route_tokens
from repro.serve import LLM, SamplingParams, ServeConfig

F32 = jnp.float32


# ============================================================ reference
def published_yarn_inv_freq(dim, base, factor, original_max, beta_fast,
                            beta_slow):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse
    frequencies, step by step as published."""
    def find_dim(num_rot):
        return (dim * math.log(original_max / (num_rot * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (
        np.arange(0, dim, 2, dtype=np.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * scale


def _rope(x, pos, inv_freq, mscale):
    """x (T, heads, d), half-split pairs."""
    ang = pos.astype(F32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * mscale)[:, None]
    sin = (jnp.sin(ang) * mscale)[:, None]
    h = x.shape[-1] // 2
    return jnp.concatenate([x[..., :h] * cos - x[..., h:] * sin,
                            x[..., h:] * cos + x[..., :h] * sin], -1)


def _swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def reference_logits(cfg, params, seq):
    """Logits (T, vocab) at every position of ``seq``: the whole forward
    in float32, plain (un-absorbed) latent attention."""
    p = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
    y = cfg.yarn
    inv = jnp.asarray(published_yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, y.factor,
        y.original_max_position, y.beta_fast, y.beta_slow))
    rope_ms = _get_mscale(y.factor, y.mscale) / _get_mscale(
        y.factor, y.mscale_all_dim)
    m = _get_mscale(y.factor, y.mscale_all_dim)
    scale = m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    C, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    T = len(seq)
    pos = jnp.arange(T)

    def attn(w, h):
        q = jnp.einsum("td,dhk->thk", h, w["wq"])
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv,
                                                rope_ms)], -1)
        kv = h @ w["wkv_a"]
        c = _rmsnorm(kv[:, :C], w["kv_norm"]["scale"])
        k_pe = _rope(kv[:, None, C:], pos, inv, rope_ms)
        k_nope = jnp.einsum("tc,chn->thn", c, w["wk"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + (
                k_pe.shape[-1],))], -1)
        v = jnp.einsum("tc,chv->thv", c, w["wv"])
        s = jnp.einsum("qhk,shk->hqs", q, k) * scale
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
        o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(s, -1), v)
        return jnp.einsum("qhv,hvd->qd", o, w["wo"])

    def moe(w, h):
        probs = jax.nn.softmax(h @ w["moe"]["router"], -1)
        gates, experts = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)
        weight = jnp.zeros_like(probs).at[
            jnp.arange(T)[:, None], experts].add(gates)
        out = sum(weight[:, e:e + 1] * _swiglu(h, jax.tree.map(
            lambda a: a[e], w["moe"])) for e in range(cfg.n_experts))
        return out + _swiglu(h, w["shared"])

    with jax.default_matmul_precision("highest"):
        x = p["embed"]["tok"][jnp.asarray(seq)]
        stacks = [(p["dense_layers"], lambda w, h: _swiglu(h, w["mlp"])),
                  (p["layers"], moe)]
        for stack, ffn in stacks:
            n = jax.tree.leaves(stack)[0].shape[0]
            for l in range(n):
                w = jax.tree.map(lambda a: a[l], stack)
                x = x + attn(w["attn"], _rmsnorm(x, w["ln1"]["scale"]))
                x = x + ffn(w, _rmsnorm(x, w["ln2"]["scale"]))
        x = _rmsnorm(x, p["final_ln"]["scale"])
        return np.asarray(x @ p["head"]["w"])


# ============================================================== fixtures
@pytest.fixture(scope="module")
def dsv2():
    """SMOKE DeepSeek-V2-Lite with float32 weights, activations and cache:
    the engine's results then differ from the reference by summation order
    alone."""
    cfg = dataclasses.replace(get_smoke("deepseek_v2_lite"), remat=False,
                              dtype=F32)
    model = build_model(cfg)
    params = jax.tree.map(lambda a: a.astype(F32),
                          model.init(jax.random.PRNGKey(3)))
    return cfg, model, params


PROMPTS = [[5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4, 6, 2, 6, 4],
           [3, 1, 4, 1, 5, 9, 2], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5]]
N_NEW = 6


def serve(dsv2, **kw):
    """Greedy streams through ``LLM`` with every decode step's logits."""
    cfg, model, params = dsv2
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=48, host_pages=64,
        max_pages_per_seq=8, interval_steps=4, keep_logits=True, **kw))
    handles = [llm.submit(p, SamplingParams(max_tokens=N_NEW))
               for p in PROMPTS]
    logits = {h.request_id: [] for h in handles}
    while any(not h.finished for h in handles):
        for rid in llm.step():
            # A request's last step finishes it, and its logits go with it.
            if rid in llm.engine.last_logits:
                logits[rid].append(llm.engine.last_logits[rid].copy())
    return llm, [(h.result().token_ids, np.stack(logits[h.request_id]))
                 for h in handles]


def assert_matches_reference(dsv2, runs):
    """Each decode step's logits against the reference's full forward over
    prompt + served tokens, at the position that step predicted from.
    Tolerance 2e-5 of the logits' range: float32 throughout, so the two
    part only by summation order (absorbed against plain attention, the
    grouped GEMM against dense experts), which measures under 1e-6 of the
    range; a wrong equation (renormalized gates measured 0.08 to 0.11, and
    a wrong scale, rope or a missing shared expert alike) moves logits by
    1e-2 of their range or more."""
    cfg, _, params = dsv2
    for prompt, (tokens, got) in zip(PROMPTS, runs):
        assert len(tokens) == N_NEW
        want = reference_logits(cfg, params, prompt + tokens[:-1])
        want = want[len(prompt) - 1:]
        assert len(got) == N_NEW - 1
        span = float(want.max() - want.min())
        np.testing.assert_allclose(got, want[:len(got)], rtol=0,
                                   atol=2e-5 * span)
        assert tokens == [int(t) for t in want.argmax(-1)]


# ================================================================= tests
def test_yarn_inverse_frequencies_match_published_formula():
    for cfg in (get("deepseek_v2_lite"), get_smoke("deepseek_v2_lite")):
        y = cfg.yarn
        args = (cfg.qk_rope_head_dim, cfg.rope_theta, y.factor,
                y.original_max_position, y.beta_fast, y.beta_slow)
        np.testing.assert_allclose(yarn_freqs(*args),
                                   published_yarn_inv_freq(*args),
                                   rtol=1e-6)
    # DeepSeek-V2-Lite: pairs 0..9 keep their frequency, 23.. are /40.
    f = yarn_freqs(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    # Softmax scale 192^-1/2 x 1.2608^2.
    m = build_model(dataclasses.replace(get("deepseek_v2_lite"))).mla_cfg
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert m.softmax_scale() == pytest.approx(1.5896 / math.sqrt(192),
                                              rel=1e-4)
    assert m.rope_mscale() == 1.0


def test_gates_without_renormalization_keep_softmax_mass():
    """norm_topk_prob=False (DeepSeek) keeps the top-k softmax
    probabilities; True (granite) divides them by their sum.  Same picks."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 8)) * 0.5
    base = MoEConfig(d_model=32, d_ff=8, n_experts=8, top_k=3)
    g_raw, e_raw = route_tokens(
        router, x, dataclasses.replace(base, norm_topk_prob=False))
    g_norm, e_norm = route_tokens(router, x, base)
    np.testing.assert_array_equal(e_raw, e_norm)
    probs = jax.nn.softmax(x @ router, -1)
    np.testing.assert_allclose(
        g_raw, jnp.take_along_axis(probs, e_raw, -1), rtol=1e-6)
    assert float(g_raw.sum(-1).max()) < 1.0
    np.testing.assert_allclose(g_norm, g_raw / g_raw.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_mla_kernel_matches_oracle_with_values_from_key_lanes():
    """The absorbed decode kernel (interpret mode) against the jnp oracle
    and a per-row loop, at a scale that is not 1/sqrt(L) and values taken
    from the first dv lanes of the same latent rows."""
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    B, H, L, N, P, dv = 3, 4, 40, 10, 8, 32
    q = jax.random.normal(ks[0], (B, H, L))
    pool = jax.random.normal(ks[1], (N, P, L))
    table = jnp.asarray([[3, 1, -1, -1], [0, 2, 5, 7], [9, -1, -1, -1]],
                        jnp.int32)
    lens = jnp.asarray([12, 30, 5], jnp.int32)
    scale = 0.37
    got = paged_mla_decode_pallas(q, pool, table, lens, scale=scale, dv=dv,
                                  interpret=True)
    oracle = ref.paged_mla_decode_reference(q, pool, table, lens,
                                            scale=scale, dv=dv)
    # float32 online softmax against one softmax: order only.
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)
    qn, pn, tn = np.asarray(q), np.asarray(pool), np.asarray(table)
    for b in range(B):
        rows = np.concatenate([pn[s] for s in tn[b] if s >= 0])[:lens[b]]
        s = qn[b] @ rows.T * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        np.testing.assert_allclose(oracle[b], w @ rows[:, :dv], rtol=1e-5,
                                   atol=1e-5)


def test_absorbed_decode_equals_plain_attention(dsv2):
    """One-token absorbed decode over a latent cache, step by step, against
    the plain form over the whole sequence (float32: order only)."""
    cfg, model, params = dsv2
    p = jax.tree.map(lambda a: a[0], params["dense_layers"])["attn"]
    T = 9
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, cfg.d_model)) * 0.5
    with jax.default_matmul_precision("highest"):
        want = mla_attention(p, x, model.mla_cfg,
                             jnp.arange(T, dtype=jnp.int32)[None])
        cache = jnp.zeros((1, 16, model.mla_cfg.latent_dim), F32)
        got = []
        for t in range(T):
            y, cache = mla_attention_decode(p, x[:, t:t + 1], cache,
                                            jnp.int32(t), model.mla_cfg)
            got.append(y[:, 0])
    np.testing.assert_allclose(jnp.stack(got, 1), want, rtol=1e-5,
                               atol=1e-5)


def test_resident_path_matches_reference(dsv2):
    _, runs = serve(dsv2)
    assert_matches_reference(dsv2, runs)


def test_interleaved_prefill_matches_reference(dsv2):
    """Prefill in chunks of 8 prompt tokens: later chunks start past 0 and
    attend to the latents of earlier ones."""
    llm, runs = serve(dsv2, prefill_chunk_tokens=8)
    assert llm.engine.prefill_chunks > len(PROMPTS)
    assert_matches_reference(dsv2, runs)


@pytest.mark.parametrize("cache", [0, 8])
def test_tiered_path_matches_reference(dsv2, cache):
    """Experts off the chip behind caches of every block (0) and of 8 of
    16, where layers evict each other's blocks."""
    llm, runs = serve(dsv2, expert_offchip=True, expert_cache_size=cache)
    assert_matches_reference(dsv2, runs)
    if cache:
        assert llm.engine.expert_store.evictions > 0


def test_pallas_kernels_match_reference(dsv2):
    """The serving path with the Pallas kernels in interpret mode: the
    latent decode kernel and flash attention over the prompt."""
    ops.set_mode("interpret")
    try:
        _, runs = serve(dsv2, expert_offchip=True, expert_cache_size=8)
    finally:
        ops.set_mode(None)
    assert_matches_reference(dsv2, runs)


def test_expert_store_covers_moe_layers_only(dsv2):
    """Behind a dense first layer the store holds the MoE layers alone:
    its layer cycle and a cache size of 0 count them, not every layer."""
    cfg, model, params = dsv2
    n_moe = cfg.n_layers - cfg.first_dense_layers
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=48, host_pages=64,
        max_pages_per_seq=8, expert_offchip=True, expert_cache_size=0))
    st = llm.engine.expert_store
    assert st.n_layers == n_moe
    assert st.cache_slots == n_moe * cfg.n_experts
    assert "shared" in llm.engine.params["layers"]
    assert set(llm.engine.params["layers"]["moe"]) == {"router"}
    llm.generate([PROMPTS[1]], SamplingParams(max_tokens=3))
    assert {b.layer for b in st.blocks.values()} == set(range(n_moe))
    assert st.needed_blocks > 0


def test_dense_layer_pieces_take_the_pools_donated(dsv2):
    """The leading dense layer's tiered pieces consume the latent pool they
    are handed, as the MoE layers' pieces do, and the pool names live
    arrays once the request is served."""
    _, model, params = dsv2
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=48, host_pages=64,
        max_pages_per_seq=8, expert_offchip=True, expert_cache_size=0))
    engine = llm.cluster.replicas[0].engine
    handed = {kind: [] for kind in ("prefill", "decode")}
    dense = engine._t_dense

    def call(*args, dispatch):
        handed[dispatch].append(args[1])
        return dense(*args, dispatch=dispatch)
    engine._t_dense = call
    llm.generate([PROMPTS[1]], SamplingParams(max_tokens=3))
    for kind, calls in handed.items():
        assert calls, f"the {kind} dense piece never ran"
        assert all(a.is_deleted() for pools in calls for a in pools), kind
    assert not any(a.is_deleted() for a in engine.pool.hbm)
