"""End-to-end behaviour tests for the paper's system: the full pipeline
(profile -> recommend -> ski-rental -> migrate) across its three hosts —
the calibrated simulator, the training loop, and the serving engine —
plus the launcher failure drill."""

import dataclasses
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import CLX, GuidanceConfig
from repro.data import SyntheticLM
from repro.mem import MemorySimulator
from repro.mem.workloads import lulesh
from repro.models import build_model
from repro.optim import AdamW
from repro.train import Trainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_paper_pipeline_end_to_end_on_simulator():
    """The headline claim, end to end: online guidance beats first touch on
    a memory-bound workload and converges near the offline oracle."""
    wl = lulesh("medium")
    sim = MemorySimulator(CLX, wl)
    cap = int(wl.peak_rss * 0.3)
    ft = sim.run_first_touch(cap)
    online = sim.run_online(cap)
    offline = sim.run_offline(cap)
    assert online.speedup_over(ft) > 2.0
    assert online.throughput > 0.6 * offline.throughput
    assert online.bytes_migrated > 0


def test_training_with_guidance_is_lossless_and_offloads():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    opt = AdamW(lr=3e-3, weight_decay=0.0)
    src = SyntheticLM(cfg.vocab, 64, 4, seed=1)
    data = [{k: jnp.asarray(v) for k, v in src.batch_np(i).items()}
            for i in range(16)]
    from repro.models.common import count_params, tree_bytes

    defs = model.param_defs()
    state_bytes = tree_bytes(defs) + 2 * 4 * count_params(defs)  # + f32 m,v
    runs = {}
    for name, gdt in (
        ("plain", None),
        ("guided", GuidanceConfig(enabled=True,
                             fast_capacity_bytes=int(state_bytes * 0.6),
                             interval_steps=4, promotion_threshold=1024)),
    ):
        tr = Trainer(model, opt, TrainerConfig(steps=15, log_every=1,
                                               gdt=gdt),
                     rng=jax.random.PRNGKey(7))
        tr.run(iter(data))
        runs[name] = ([m["loss"] for m in tr.metrics_log], tr)
    np.testing.assert_allclose(runs["plain"][0], runs["guided"][0],
                               rtol=1e-5)
    assert runs["guided"][1].placer.slow_bytes() > 0


def test_launcher_failure_drill(tmp_path):
    """Injected failure + checkpoint restart through the real CLI."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "llama3_2_1b",
         "--smoke", "--steps", "8", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
         "--simulate-failure", "5"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "restarting from checkpoint" in proc.stdout


def test_dryrun_cell_via_cli(tmp_path):
    """One full AOT cell through the real dry-run entry point (512 devices)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "llama3_2_1b",
         "--shape", "decode_32k", "--mesh", "single",
         "--outdir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "all cells compiled" in proc.stdout
    import json
    rec = json.load(open(tmp_path / "pod256" /
                         "llama3_2_1b__decode_32k.json"))
    assert rec["status"] == "ok"
    assert rec["devices"] == 256
    assert rec["global_cost"]["flops"] > 0
