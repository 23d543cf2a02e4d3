"""A tiny granite-4.0-H cell through the whole harness on the CPU (the
chip check skipped): built from its files, Mamba-2 and attention layers
with a share of the experts held, its served tokens held to the plain
reference (``references/granite_hybrid.py``) — sound, and its float8
control not.

The tiny configuration sets the embedding multiplier to 1: at this width
Granite's x12 would make every random-weight position's best logit its
own input token, which any stream, right or wrong, repeats."""

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402

CELL = "tiny-hybrid.backlog"


def tiny_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny-hybrid.json", bench / "configs")
    shutil.copy(DATA / "tiny-backlog.json", bench / "traffic")
    shutil.copy(DATA / f"{CELL}.json", bench / "cells")
    with open(REPO / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-hybrid", "source": "test",
        "file": "benchmarks/chip/configs/tiny-hybrid.json",
        "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                              "traffic": "tiny-backlog", "chips": 1,
                              "why": "test"})
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return tmp


def test_a_sound_run_is_correct_and_its_float8_control_is_not(tmp_path):
    import jax

    root = tiny_root(tmp_path)
    args = argparse.Namespace(workload=CELL, seed=2**31 + 9, seconds=1.5,
                              trace=0, control=True)
    saved = harness.require_chips, harness.enable_compile_cache
    harness.require_chips = lambda chips: jax.devices()
    harness.enable_compile_cache = lambda root: None
    peaks.PEAKS.setdefault(jax.devices()[0].device_kind, peaks.V5E)
    try:
        res = harness.run(root, args, time.perf_counter(),
                          bench_dir=root / "benchmarks" / "chip")
    finally:
        harness.require_chips, harness.enable_compile_cache = saved
    program = res["program"]
    assert program["correct"], program["checks"]
    assert not res["correct"], res["checks"]
    assert res["checks"]["compared_tokens"]["value"] >= 20
    assert set(res["metrics"]) == {"tok_s", "setup_s"}
