"""Shared by the benchmark suite's tests: a temporary benchmark root that
holds the tiny CPU fixtures, and one run of a cell in this process."""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "fixtures")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


#: the accepted per-layer entries whose readers find something to read in
#: an expert cell built from the decoder's routed layer and flash kernels
#: (PERF.md section 7 row 31; my chip runs, PR 42, call A: each read a number
#: in `keye-vl-2.0-30b-a3b_train_ep8share` and in
#: `sdar-30b-a3b-chat_train_ep8share`), on whose lists PR 42 put both
EXPERT_CELL_LISTS = (
    "mosaic_time_share_pct", "setup_import_s", "setup_state_s",
    "setup_program_s", "trainer_python_ms_per_step",
    "prefetch_wait_ms_per_step", "prefetch_put_ms_per_batch",
    "gc_pause_ms_per_block", "step_forward_ms", "step_backward_ms",
    "step_optimizer_ms", "step_unscoped_pct", "flash_fwd_ms_per_step",
    "flash_bwd_ms_per_step", "idle_outside_program_pct", "moe_ms_per_step",
    "moe_expert_matmul_ms_per_step", "moe_expert_matmul_roofline_pct",
    "moe_expert_load_max_over_mean", "moe_worst_case_layers_pct")
#: PR 37's four, which name every cell
COST_LISTS = ("step_hbm_gb", "step_floor_ms", "step_memory_bound_ms",
              "step_optimizer_hbm_gb")


def named(entries, name):
    """The one entry of a list of `BENCHMARK.json` called *name*: entries
    are found by name, never by their place in the list."""
    entry, = [e for e in entries if e["name"] == name]
    return entry


def fixture_root(tmp_path, copy_code=False):
    """A benchmark root under *tmp_path*: the fixture `BENCHMARK.json`,
    the real traffic mixes and peaks, the fixture configurations.  With
    *copy_code* the whole of `benchmarks/` is copied, so that files can be
    added beside the real ones."""
    root = str(tmp_path / "root")
    bench = os.path.join(root, "benchmarks")
    if copy_code:
        shutil.copytree(os.path.join(REPO, "benchmarks"), bench,
                        ignore=shutil.ignore_patterns("__pycache__"))
    else:
        os.makedirs(os.path.join(bench, "configs"))
        shutil.copytree(os.path.join(REPO, "benchmarks", "traffic"),
                        os.path.join(bench, "traffic"))
        shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"), bench)
    shutil.copy(os.path.join(FIXTURES, "BENCHMARK.json"), root)
    for name in ("tiny_lm.json", "tiny_resnet.json"):
        shutil.copy(os.path.join(FIXTURES, name),
                    os.path.join(bench, "configs"))
    shutil.copy(os.path.join(FIXTURES, "fit_prefetch_short.json"),
                os.path.join(bench, "traffic"))
    return root


def run_cell(root, workload, seed=7, seconds=1.0, trace=0):
    """One run of a fixture cell on the CPU, past the harness's look for a
    chip: ``(outcome, last line as a dict)``."""
    import jax
    from benchmarks import harness

    cell = harness.Cell(workload, seed, seconds, trace,
                        time.perf_counter(), root)
    devices = jax.devices()[:cell.chips]
    outcome = cell.kind().run(cell, devices)
    line = json.loads(json.dumps(
        harness.result_line(cell, outcome, devices)))
    return outcome, line
