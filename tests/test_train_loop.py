"""End-to-end training loop tests, including the GDT offload integration:
tier migrations must never change numerics, only placement."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import GuidanceConfig
from repro.core.placement import memory_kind_of
from repro.data import SyntheticLM
from repro.models import build_model
from repro.optim import AdamW
from repro.train import StepConfig, Trainer, TrainerConfig

MB = 2**20


def tiny_model():
    cfg = get_smoke("llama3_2_1b")
    return build_model(dataclasses.replace(cfg, remat=False))


def batches(model, n_steps, batch=4, seq=64):
    src = SyntheticLM(model.cfg.vocab, seq, batch, seed=3)
    out = []
    for i in range(n_steps + 1):
        b = src.batch_np(i)
        out.append({k: jnp.asarray(v) for k, v in b.items()})
    return out


def test_loss_decreases():
    model = tiny_model()
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    tr = Trainer(model, opt, TrainerConfig(steps=60, log_every=1, seed=0))
    res = tr.run(iter(batches(model, 60, batch=16)))
    losses = [m["loss"] for m in tr.metrics_log]
    assert min(losses[-5:]) < losses[0] * 0.95
    assert np.isfinite(losses).all()


def test_grad_accumulation_matches_full_batch():
    model = tiny_model()
    opt = AdamW(lr=1e-3, weight_decay=0.0, grad_clip=None)
    data = batches(model, 2, batch=8, seq=32)

    tr1 = Trainer(model, opt, TrainerConfig(steps=1, log_every=1),
                  rng=jax.random.PRNGKey(1))
    tr2 = Trainer(model, opt,
                  TrainerConfig(steps=1, log_every=1,
                                step=StepConfig(accum=4)),
                  rng=jax.random.PRNGKey(1))
    tr1.run(iter(data))
    tr2.run(iter(data))
    l1 = jax.tree.leaves(tr1.params)
    l2 = jax.tree.leaves(tr2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_int8_compression_tracks_uncompressed():
    """The lossy gradient channel must not visibly derail optimization:
    the int8 run's loss trajectory stays within a few percent of the
    uncompressed run's."""
    model = tiny_model()
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    data = batches(model, 30, batch=16)
    tr_plain = Trainer(model, opt, TrainerConfig(steps=30, log_every=1),
                       rng=jax.random.PRNGKey(2))
    tr_plain.run(iter(data))
    tr_int8 = Trainer(model, opt,
                      TrainerConfig(steps=30, log_every=1,
                                    step=StepConfig(compression="int8")),
                      rng=jax.random.PRNGKey(2))
    tr_int8.run(iter(data))
    lp = [m["loss"] for m in tr_plain.metrics_log]
    li = [m["loss"] for m in tr_int8.metrics_log]
    assert li[-1] < lp[-1] * 1.05
    assert li[-1] < li[0]          # and it is actually improving


def test_gdt_offload_preserves_numerics_and_migrates():
    """Under a tight HBM budget the controller offloads cold groups (adam
    moments mostly); loss trajectory must match the non-tiered run exactly
    because migration never alters values."""
    model = tiny_model()
    opt = AdamW(lr=3e-3, weight_decay=0.0)
    data = batches(model, 25)

    tr_plain = Trainer(model, opt, TrainerConfig(steps=25, log_every=1),
                       rng=jax.random.PRNGKey(5))
    res_plain = tr_plain.run(iter(data))

    # Budget ~60% of total state -> something must live on the host tier.
    state_bytes = sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(tr_plain.params))
    state_bytes += sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(tr_plain.opt_state.m)) * 2
    gdt = GuidanceConfig(enabled=True, strategy="thermos",
                    fast_capacity_bytes=int(state_bytes * 0.6),
                    interval_steps=5, promotion_threshold=1024)
    tr_gdt = Trainer(model, opt,
                     TrainerConfig(steps=25, log_every=1, gdt=gdt),
                     rng=jax.random.PRNGKey(5))
    res_gdt = tr_gdt.run(iter(data))

    pl = [m["loss"] for m in tr_plain.metrics_log]
    gl = [m["loss"] for m in tr_gdt.metrics_log]
    np.testing.assert_allclose(pl, gl, rtol=1e-5, atol=1e-5)

    # Something actually lives on the slow tier and transfers happened.
    assert tr_gdt.placer.slow_bytes() > 0
    assert tr_gdt.placer.transfers_bytes > 0
    kinds = {memory_kind_of(e.array)
             for entries in tr_gdt.placer._store.values() for e in entries}
    assert "pinned_host" in kinds


def test_checkpoint_restart_in_trainer(tmp_path):
    model = tiny_model()
    opt = AdamW(lr=1e-3, weight_decay=0.0)
    data = batches(model, 12)
    tr = Trainer(model, opt,
                 TrainerConfig(steps=10, log_every=1, ckpt_every=5,
                               ckpt_dir=str(tmp_path), seed=0))
    tr.run(iter(data))
    tr2 = Trainer(model, opt, TrainerConfig(steps=1, log_every=1,
                                            ckpt_dir=str(tmp_path), seed=0))
    meta = tr2.restore_checkpoint()
    assert meta["step"] == 10
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
