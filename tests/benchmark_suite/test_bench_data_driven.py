"""`BENCHMARK.json` and the files it names agree, and a later PR can add a
configuration, a traffic mix, a per-layer metric and a cell as new files
plus one entry each, with no file that is there edited."""

import hashlib
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

import bench_suite_util as util

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        conf = configs[w["config"]]
        with open(os.path.join(util.REPO, conf["file"])) as f:
            cfg = json.load(f)
        assert set(conf["reduced"]) == set(cfg["reduced"])
        importlib.import_module("benchmarks.models." + cfg["family"])
        importlib.import_module("benchmarks.reference." + cfg["family"])
        with open(os.path.join(util.REPO, "benchmarks", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        kind = importlib.import_module("benchmarks.kinds." + mix["kind"])
        assert callable(kind.run)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= 1


def test_every_per_layer_metric_has_its_reader(spec):
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        reader = importlib.import_module(
            "benchmarks.layer_metrics." + m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == \
            (m["unit"], m["layer"], m["moves"], m["source"]), m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", [])) <= cells
        assert reader.__doc__ and callable(reader.read)


def test_published_widths_are_not_cut(spec):
    published = {"ffn_dim": 8192, "hidden_size": 2048,
                 "num_attention_heads": 32, "vocab_size": 50272,
                 "max_position_embeddings": 2048, "word_embed_proj_dim": 2048}
    with open(os.path.join(util.REPO, "benchmarks", "configs",
                           "opt-1.3b-1chip.json")) as f:
        cfg = json.load(f)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["train"]["sequence_length"] == 2048
    assert cfg["reduced"] == ["num_hidden_layers"]


def test_a_missing_device_kind_is_an_error(tmp_path):
    from benchmarks import harness
    cell = harness.Cell("tiny_lm_train", 1, 1, 0, 0.0,
                        util.fixture_root(tmp_path))
    assert cell.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        cell.peak("TPU v9 imaginary", "bf16_flops_per_s")


def _digests(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_files_and_entries_add_a_cell_and_a_metric(tmp_path):
    root = util.fixture_root(tmp_path, copy_code=True)
    bench = os.path.join(root, "benchmarks")
    before = _digests(bench)

    # a configuration, a traffic mix and a per-layer metric: new files
    with open(os.path.join(bench, "configs", "tiny_lm.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 1
    with open(os.path.join(bench, "configs", "tiny_lm_one_layer.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "fit_ring_of_five.json"),
              "w") as f:
        json.dump({"kind": "train_fit", "ring_batches": 5,
                   "prefetch_depth": 1, "traced_blocks": 2,
                   "traced_min_blocks": 2}, f)
    with open(os.path.join(bench, "layer_metrics", "steps_in_window.py"),
              "w") as f:
        f.write('"""Steps the window ran."""\n'
                'LAYER = "the whole loop"\nUNIT = "count"\n'
                'MOVES = "train_samples_per_s"\nSOURCE = "program_counter"\n'
                '\n\ndef read(outcome):\n'
                '    return outcome.facts.get("steps")\n')
    # ... and one entry each in BENCHMARK.json
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_lm_one_layer", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_lm_one_layer.json", "why": "test"})
    spec["workloads"].append({
        "name": "added_cell", "config": "tiny_lm_one_layer",
        "traffic": "fit_ring_of_five", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "the whole loop",
        "moves": "train_samples_per_s", "workloads": ["added_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    # the copy's own code runs the new cell (a traced run: per-layer)
    script = (
        "import sys, json, time; sys.path.insert(0, %r); "
        "sys.path.insert(1, %r); import jax; "
        "from benchmarks import harness; "
        "assert harness.__file__.startswith(%r); "
        "cell = harness.Cell('added_cell', 5, 1.0, 1, time.perf_counter(), "
        "%r); dev = jax.devices()[:1]; out = cell.kind().run(cell, dev); "
        "print(json.dumps(harness.result_line(cell, out, dev)))"
        % (root, util.REPO, root, root))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["steps_in_window"]["value"] == line["attempted"]
    assert "warm_cache_misses" in line["metrics"]

    after = _digests(bench)
    assert {k: after[k] for k in before} == before      # nothing edited
    assert set(after) - set(before) == {
        "configs/tiny_lm_one_layer.json", "traffic/fit_ring_of_five.json",
        "layer_metrics/steps_in_window.py"}
