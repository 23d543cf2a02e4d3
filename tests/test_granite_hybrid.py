"""granite-4.0-H's stack of Mamba-2 and attention layers, served through
``LLM`` at a tiny width on the CPU and held to the chip benchmark's plain
reference (``benchmarks/chip/references/granite_hybrid.py``) in float32:
prefill of bucket-padded prompts, a prompt ingested in chunks, decode,
pause and resume, preemption and recompute.  Beside it the pieces: the
Mamba-2 mixer's padding and chunking, the decode kernel and the state
slot kernels (interpret mode) against their oracles, and the expert
share of a layer against the uncut layer.

Weights come from the benchmark's own seeded draw (``weights.py``), cast
to float32 so the engine and the reference compute the same function;
what differs is the order of their float32 operations (chunked scan
against token by token, paged attention against dense), hence 1e-4."""

import dataclasses
import importlib.util
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.ssm_decode import (
    ssm_decode_pallas,
    ssm_state_read_pallas,
    ssm_state_write_pallas,
)
from repro.models import build_model
from repro.models.common import init_params
from repro.models.layers import mlp, mlp_defs
from repro.models.moe import MoEConfig, moe, moe_defs
from repro.models.ssm import (
    SSMConfig,
    ssm_decode,
    ssm_defs,
    ssm_forward,
    ssm_state_step,
)
from repro.serve import LLM, ServeConfig

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import span_trace  # noqa: E402
import weights as wmod  # noqa: E402


def _reference():
    path = BENCH / "references" / "granite_hybrid.py"
    spec = importlib.util.spec_from_file_location("granite_hybrid_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _reference()
SEED = 2**31 + 17
TOL = 1e-4


def tiny_cfg(**kw):
    # Held experts: the first 5 of 8 (a chip's expert-parallel share).
    # The embedding multiplier is 1 here: at this width Granite's x12
    # would make every random-weight position's best logit its own input
    # token, and the comparison could not tell a wrong stream from a
    # right one.
    return dataclasses.replace(
        get_smoke("granite_4_0_h_small"), remat=False, dtype=jnp.float32,
        n_local_experts=5, embedding_multiplier=1.0, **kw)


def ref_sizes(cfg, model):
    """The configuration file's ``model`` block for ``cfg``."""
    sc = model.ssm_cfg
    return {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "kv_heads": cfg.kv_heads, "top_k": cfg.top_k,
            "layer_types": list(cfg.mixers), "mamba_n_heads": sc.n_heads,
            "mamba_d_head": sc.head_dim, "mamba_d_state": sc.state_dim,
            "mamba_n_groups": sc.n_groups, "mamba_d_conv": sc.conv_width,
            "rms_norm_eps": cfg.norm_eps,
            "residual_multiplier": cfg.residual_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling}


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    model = build_model(cfg)
    abstract = model.abstract()
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          wmod.init_weights(abstract, SEED))
    layers = wmod.LayerWeights(abstract)
    top = wmod.top_weights(abstract, SEED)

    def want(prompt, tokens):
        """The reference's logits at every served position."""
        return reference.logits(
            ref_sizes(cfg, model), lambda l: layers.draw(SEED, l), top,
            [list(prompt) + list(tokens[:-1])], [len(prompt) - 1])[0]
    return cfg, model, params, want


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(ops, "_MODE", "interpret")


def engine_of(model, params, **kw):
    serve = dict(max_batch=3, page_size=8, hbm_pages=64, host_pages=8,
                 max_pages_per_seq=8, keep_logits=True)
    serve.update(kw)
    eng = LLM(model, params, ServeConfig(**serve)).cluster.replicas[0].engine
    rows = {}
    run = eng._run_batch

    def recorded(pairs):
        out = run(pairs)
        for req, _ in pairs:
            rows.setdefault(req.request_id, []).append(
                np.array(eng.last_logits[req.request_id]))
        return out
    eng._run_batch = recorded
    return eng, rows


def prompts_of(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab, n)] for n in lengths]


def check_streams(eng, rows, prompts, want):
    for rid, prompt in enumerate(prompts):
        req = eng.finished[rid]
        got = np.stack(rows[rid])
        ref = want(prompt, req.generated)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def drain(eng):
    """Step until no request but a preempted one is live."""
    for _ in range(200):
        if all(r.state == "preempted" for r in eng.requests.values()):
            return
        eng.step()
    raise AssertionError("requests still live")


def test_prefill_and_decode_match_the_reference(tiny, interpret):
    """Prompts of 5, 20 and 13 tokens ingest in buckets of 8, 32 and 16
    (page 8): their padding rows leave every state as the prompt's last
    token left it."""
    cfg, model, params, want = tiny
    eng, rows = engine_of(model, params)
    prompts = prompts_of(cfg, (5, 20, 13))
    for rid, p in enumerate(prompts):
        eng.add_request(rid, p, max_new=6)
    drain(eng)
    check_streams(eng, rows, prompts, want)
    assert eng.stats()["ssm_slots_live"] == 0


def test_a_prompt_ingested_in_chunks_continues_its_state(tiny, interpret):
    """Interleaved prefill of at most 8 tokens a step: a 20-token prompt
    ingests as chunks of 8, 8 and 3, each continuing the state the last
    one left."""
    cfg, model, params, want = tiny
    eng, rows = engine_of(model, params, prefill_chunk_tokens=8)
    prompts = prompts_of(cfg, (20, 11), seed=1)
    for rid, p in enumerate(prompts):
        eng.add_request(rid, p, max_new=5)
    drain(eng)
    assert eng.prefill_chunks >= 4
    check_streams(eng, rows, prompts, want)


def test_a_long_prompt_goes_as_dispatches_of_the_cap(tiny, interpret):
    """A prompt longer than the dispatch cap (8 tokens here) ingests as
    dispatches of the cap's bucket each, the last one padded."""
    cfg, model, params, want = tiny
    eng, rows = engine_of(model, params)
    eng._dispatch_cap = 8
    prompts = prompts_of(cfg, (21,), seed=5)
    eng.add_request(0, prompts[0], max_new=4)
    assert eng.prefill_dispatches == 3
    drain(eng)
    check_streams(eng, rows, prompts, want)


def test_pause_resume_and_preempt_recompute(tiny, interpret):
    """Two state slots (max_batch 2).  Request 0 pauses, keeping its slot,
    and resumes where it was; paused again, it yields its slot to request
    2 (preempted), and resumed it is prefilled anew from its prompt and
    what it generated, its state rebuilt."""
    cfg, model, params, want = tiny
    eng, rows = engine_of(model, params, max_batch=2)
    prompts = prompts_of(cfg, (9, 6, 7), seed=2)
    eng.add_request(0, prompts[0], max_new=9)
    eng.add_request(1, prompts[1], max_new=4)
    eng.step()
    eng.pause(0)
    eng.step()                                # 0 keeps its slot
    assert eng.stats()["ssm_slots_live"] == 2
    eng.resume(0)
    eng.step()
    eng.pause(0)
    eng.add_request(2, prompts[2], max_new=3)    # no free slot: preempt 0
    assert eng.requests[0].state == "preempted"
    drain(eng)
    eng.resume(0)
    drain(eng)
    stats = eng.stats()
    assert stats["preemptions"] == 1 and stats["ssm_recomputes"] == 1
    # The recomputed prefill writes no logits rows of its own: request 0's
    # rows are its decode steps, before and after.
    check_streams(eng, rows, prompts, want)


def test_the_state_pool_is_counted(tiny):
    cfg, model, params, _ = tiny
    eng, _ = engine_of(model, params, max_batch=3)
    sc = model.ssm_cfg
    n_mamba = cfg.mixers.count("mamba")
    per_slot = n_mamba * ((sc.conv_width - 1) * sc.conv_dim * 4
                          + sc.state_dim * sc.d_inner * 4)
    stats = eng.stats()
    assert stats["ssm_state_bytes"] == 4 * per_slot   # 3 slots + scratch
    assert stats["ssm_slots_live"] == 0 and stats["ssm_recomputes"] == 0
    assert eng.pool.hbm[0].shape[0] == cfg.mixers.count("attention")


@pytest.mark.parametrize("knob", ["expert_offchip", "enable_prefix_cache"])
def test_a_model_with_mamba_layers_refuses(tiny, knob):
    cfg, model, params, _ = tiny
    with pytest.raises(ValueError, match=knob):
        LLM(model, params, ServeConfig(max_batch=2, page_size=8,
                                       hbm_pages=32, **{knob: True}))


def test_state_spans_mark_every_alloc_and_free(tiny, tmp_path):
    cfg, model, params, _ = tiny
    eng, _ = engine_of(model, params)
    span_trace.start(str(tmp_path))
    try:
        for rid, p in enumerate(prompts_of(cfg, (6, 9), seed=3)):
            eng.add_request(rid, p, max_new=2)
        drain(eng)
    finally:
        jax.profiler.stop_trace()
    names = Counter(e.name for e in span_trace.load_dir(tmp_path))
    assert names["repro.engine.state"] == 4          # two allocs, two frees


def test_model_loss_follows_the_reference(tiny):
    """The training forward (``Model.loss``) over the same stack."""
    cfg, model, params, want = tiny
    tokens = np.array(prompts_of(cfg, (12,), seed=4))
    labels = np.roll(tokens, -1, axis=1)
    loss = model.loss(params, {"tokens": jnp.asarray(tokens),
                               "labels": jnp.asarray(labels)})
    logits = want([int(t) for t in tokens[0]][:1],
                  [int(t) for t in tokens[0][1:]] + [0])
    logz = jax.nn.logsumexp(logits, axis=-1)
    ce = np.mean(logz - logits[np.arange(12), labels[0]])
    assert float(loss) == pytest.approx(float(ce), abs=1e-5)


# ---------------------------------------------------------------- mixer
def mixer(G=1):
    cfg = SSMConfig(d_model=32, d_inner=64, head_dim=16, state_dim=8,
                    n_groups=G, conv_width=4, chunk=8, eps=1e-5)
    defs = jax.tree.map(
        lambda d: dataclasses.replace(d, dtype=jnp.float32), ssm_defs(cfg),
        is_leaf=lambda d: hasattr(d, "init"))
    p = init_params(defs, jax.random.PRNGKey(G))
    k = jax.random.split(jax.random.PRNGKey(10 + G), 4)
    p = {**p, "a_log": jax.random.normal(k[0], p["a_log"].shape) * 0.3,
         "dt_bias": jax.random.normal(k[1], p["dt_bias"].shape) * 0.3,
         "conv_b": jax.random.normal(k[2], p["conv_b"].shape) * 0.3}
    return cfg, p


@pytest.mark.parametrize("G", [1, 2])
def test_padding_rows_leave_the_state_as_the_last_row_left_it(G):
    cfg, p = mixer(G)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 32))
    out, conv, ssm = ssm_forward(p, u[:, :11], cfg)
    pad = jnp.concatenate([u[:, :11], jnp.full((1, 5, 32), 7.0)], axis=1)
    out_p, conv_p, ssm_p = ssm_forward(p, pad, cfg, n_real=jnp.int32(11))
    np.testing.assert_array_equal(conv_p, conv)
    np.testing.assert_allclose(ssm_p, ssm, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out_p[:, :11], out, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("G", [1, 2])
def test_two_chunks_give_the_state_of_one_pass(G):
    cfg, p = mixer(G)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    out, conv, ssm = ssm_forward(p, u, cfg)
    out_a, conv_a, ssm_a = ssm_forward(p, u[:, :16], cfg)
    out_b, conv_b, ssm_b = ssm_forward(p, u[:, 16:], cfg, conv_state=conv_a,
                                       ssm_state=ssm_a)
    np.testing.assert_array_equal(conv_b, conv)
    np.testing.assert_allclose(ssm_b, ssm, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.concatenate([out_a, out_b], 1), out,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("G", [1, 2])
def test_decode_steps_continue_the_prefill(G):
    cfg, p = mixer(G)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 32))
    out, conv, ssm = ssm_forward(p, u, cfg)
    _, conv_s, ssm_s = ssm_forward(p, u[:, :8], cfg)
    for t in range(8, 12):
        y, conv_s, ssm_s = ssm_decode(p, u[:, t:t + 1], conv_s, ssm_s, cfg)
        np.testing.assert_allclose(y[:, 0], out[:, t], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ssm_s, ssm, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(conv_s, conv)


# --------------------------------------------------------------- kernels
@pytest.mark.parametrize("H,P,N,G,block", [(8, 32, 16, 1, 2048),
                                           (8, 32, 16, 2, 128),
                                           (4, 64, 128, 1, 128)])
def test_ssm_decode_kernel_matches_the_model_step(H, P, N, G, block):
    """The kernel (interpret mode) against ``ssm_state_step``, the model's
    recurrence, and against the kernel oracle: rows at their slots, the
    pool's other rows untouched."""
    k = jax.random.split(jax.random.PRNGKey(H + G), 8)
    R, Lm, S = 3, 2, 5
    pool = jax.random.normal(k[0], (Lm, S, N, H * P))
    slots = jnp.array([3, 0, 1], jnp.int32)
    x = jax.random.normal(k[1], (R, H * P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    A = -jnp.exp(jax.random.normal(k[3], (H,)) * 0.3)
    D = jax.random.normal(k[4], (H,))
    Bm = jax.random.normal(k[5], (R, G, N))
    Cm = jax.random.normal(k[6], (R, G, N))
    y, new = ssm_decode_pallas(pool, jnp.int32(1), slots, x, dt, A, D, Bm,
                               Cm, block=block, interpret=True)
    cfg = SSMConfig(d_model=1, d_inner=H * P, head_dim=P, state_dim=N,
                    n_groups=G)
    state = pool[1, slots].reshape(R, N, H, P)
    y_m, state_m = ssm_state_step(state, x.reshape(R, H, P), dt, A, Bm, Cm,
                                  D, cfg)
    np.testing.assert_allclose(y, y_m.reshape(R, -1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1, slots], state_m.reshape(R, N, -1),
                               rtol=1e-5, atol=1e-5)
    y_r, new_r = kref.ssm_decode_reference(pool, jnp.int32(1), slots, x, dt,
                                           A, D, Bm, Cm)
    np.testing.assert_allclose(new, new_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, jnp.array([2, 4])],
                                  pool[1, jnp.array([2, 4])])


def test_state_slot_kernels_read_and_write_one_row():
    pool = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 16, 256))
    row = ssm_state_read_pallas(pool, jnp.int32(2), jnp.int32(1),
                                block=128, interpret=True)
    np.testing.assert_array_equal(row, pool[2, 1])
    new = jnp.full((16, 256), 3.0)
    out = ssm_state_write_pallas(pool, jnp.int32(1), jnp.int32(3), new,
                                 block=128, interpret=True)
    np.testing.assert_array_equal(out, pool.at[1, 3].set(new))


# ---------------------------------------------------------- expert share
def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 of 8 on one chip and 4-7 on the other (the router
    rotated so that they are its first four), the shared expert counted
    once: together the uncut layer."""
    E, d, f, k = 8, 32, 16, 3
    full = MoEConfig(d_model=d, d_ff=f, n_experts=E, top_k=k)
    half = dataclasses.replace(full, n_local=E // 2)
    f32 = lambda defs: jax.tree.map(  # noqa: E731
        lambda a: dataclasses.replace(a, dtype=jnp.float32), defs,
        is_leaf=lambda a: hasattr(a, "init"))
    p = init_params(f32(moe_defs(full)), jax.random.PRNGKey(0))
    shared = init_params(f32(mlp_defs(d, 2 * f)), jax.random.PRNGKey(1))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 5, d))

    def share(first):
        idx = (np.arange(E) + first) % E
        return moe({"router": p["router"][:, idx],
                    **{w: p[w][first:first + E // 2]
                       for w in ("w_gate", "w_up", "w_down")}}, h, half)

    uncut = moe(p, h, full) + mlp(shared, h)
    parts = share(0) + mlp(shared, h) + share(E // 2)
    np.testing.assert_allclose(parts, uncut, rtol=1e-5, atol=1e-6)
