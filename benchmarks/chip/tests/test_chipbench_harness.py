"""The harness: a configuration, a traffic mix, a cell and a per-layer
metric added as files only; a run without a TPU; and whole runs on the CPU
(the chip check skipped) of a tiny cell: sound, with its float8 control,
and with a token altered where the engine produces it."""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402

CELL = "tiny-dense.backlog"
CELL_NAME = json.load(open(REPO / "BENCHMARK.json"))["workloads"][0]["name"]


def tiny_root(tmp: Path) -> Path:
    """A checkout whose benchmark gained one configuration, one mix, one
    cell and one metric, by new files and new BENCHMARK.json entries."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny-dense.json", bench / "configs")
    shutil.copy(DATA / "tiny-backlog.json", bench / "traffic")
    shutil.copy(DATA / f"{CELL}.json", bench / "cells")
    shutil.copy(DATA / "tokens_per_step.py", bench / "metrics")
    with open(REPO / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-dense", "source": "test",
        "file": "benchmarks/chip/configs/tiny-dense.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-dense",
                              "traffic": "tiny-backlog", "chips": 1,
                              "why": "test"})
    # An end-to-end metric of this cell alone, added for it by name.
    spec["end_to_end"].append({
        "name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": [CELL]})
    spec["per_layer"].append({
        "name": "tokens_per_step", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "engine step", "moves": "tok_s",
        "workloads": [CELL]})
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return tmp


def test_files_alone_add_a_config_a_mix_a_cell_and_a_metric(tmp_path):
    root = tiny_root(tmp_path)
    bench = root / "benchmarks" / "chip"
    cell = harness.load_cell(root, CELL, bench)
    assert cell.config["name"] == "tiny-dense"
    assert cell.mix["arrival"]["clients"] == 4
    assert cell.settings["serve"]["max_batch"] == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "tok_s", "itl_p95_ms", "setup_s"}
    assert "tokens_per_step" in {m["name"] for m in cell.per_layer}
    read = harness.metric_reader(bench, "tokens_per_step")
    steps = [harness.Step(1.0, 2.0, [(0, 5), (1, 9)], [], 0, 0),
             harness.Step(2.0, 3.0, [(0, 6)], [], 0, 0)]
    w = harness.Window(1.0, 3.0, steps, {}, {}, None, peaks.V5E, None, 3)
    assert read(w) == 1.5
    # A split name falls back to its base name's reader.
    assert harness.metric_reader(bench, "step_ms.chat")(w) == 1000.0


def test_every_cell_of_the_benchmark_loads(tmp_path):
    with open(REPO / "BENCHMARK.json") as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(REPO, w["name"])
        assert all(v > 0 for v in cell.settings["check"]["limits"].values())
        for m in cell.per_layer:
            assert callable(harness.metric_reader(BENCH, m["name"]))


@pytest.mark.parametrize("root", ["repo", "bare"])
def test_no_tpu_exits_nonzero_without_a_result(tmp_path, root):
    if root == "bare":           # only BENCHMARK.json and the benchmark
        shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    else:
        cwd = REPO
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         CELL_NAME, "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def run_tiny(root: Path, seed: int, hook=None, control=False):
    """One whole run of the tiny cell on the CPU, the chip check skipped."""
    import jax

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.5,
                              trace=0, control=control)
    saved = harness.require_chips, harness.enable_compile_cache
    harness.require_chips = lambda chips: jax.devices()
    harness.enable_compile_cache = lambda root: None
    peaks.PEAKS.setdefault(jax.devices()[0].device_kind, peaks.V5E)
    try:
        return harness.run(root, args, time.perf_counter(), setup_hook=hook,
                           bench_dir=root / "benchmarks" / "chip")
    finally:
        harness.require_chips, harness.enable_compile_cache = saved


def test_a_sound_run_is_correct_and_its_float8_control_is_not(tmp_path):
    root = tiny_root(tmp_path)
    res = run_tiny(root, 2**31 + 5, control=True)
    # The control, in the program's place, fails the limits that the
    # program's own readings of the same run meet.
    program = res["program"]
    assert program["correct"], program["checks"]
    assert not res["correct"], res["checks"]
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert program["checks"]["mean_logit_gap"]["value"] <= gap["limit"]
    assert res["checks"]["compared_tokens"]["value"] >= 20
    assert set(res["metrics"]) == {"tok_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_a_token_altered_where_produced_is_not_correct(tmp_path):
    root = tiny_root(tmp_path)

    def alter(llm):
        eng = llm.cluster.replicas[0].engine
        orig = eng._run_batch
        eng._run_batch = lambda pairs: [(t + 1) % 512 for t in orig(pairs)]

    res = run_tiny(root, 11, hook=alter)
    assert not res["correct"]
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("got,ok", [
    ({"mean_logit_gap": 0.004, "compared_tokens": 300}, True),
    ({"mean_logit_gap": 0.008, "compared_tokens": 300}, True),
    ({"mean_logit_gap": 0.009, "compared_tokens": 300}, False),
    ({"mean_logit_gap": 0.004, "compared_tokens": 299}, False),
    ({"mean_logit_gap": float("inf"), "compared_tokens": 0}, False),
])
def test_judge_holds_tokens_to_a_floor_and_gaps_to_a_ceiling(got, ok):
    limits = {"mean_logit_gap": 0.008, "compared_tokens": 300}
    checks, correct = harness.judge(limits, {**got, "widest_logit_gap": 9.0})
    assert correct is ok
    assert set(checks) == set(limits)
    assert all(checks[k]["limit"] == limits[k] for k in limits)
