"""`BENCHMARK.json` and the files it names agree, and a later PR can add a
configuration, a traffic mix, a per-layer metric and a cell as new files
plus one entry each, with no file that is there edited.

The shape of `BENCHMARK.json` is held by the functions below, which take a
spec and the root its files lie under and find every entry by its `name`:
none looks at an entry's place in its list or at a list's length, so they
hold for any number of cells.  They run on the tree's spec and on a copy
with a made-up fifth configuration, cell and two per-layer entries
appended (`with_a_fifth_cell`)."""

import ast
import copy
import hashlib
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_suite_util as util

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
METRIC_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the rules, as functions of a spec ----------------------------------------
def _line(text, most=200):
    return isinstance(text, str) and 1 <= len(text) <= most \
        and "\n" not in text and "\t" not in text


def _by_name(entries):
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names), "a name twice: %s" % names
    assert all(NAME.match(n) for n in names), names
    return dict(zip(names, entries))


def _under_paths(spec, path):
    return any(path.startswith(p.rstrip("/") + "/") for p in spec["paths"])


def perf_md_layers():
    """The layers PERF.md section 3 lists: the first cell of its table's
    rows."""
    with open(os.path.join(util.REPO, "PERF.md")) as f:
        text = f.read()
    section = text[text.index("\n## 3. Layers"):text.index("\n## 4. ")]
    rows = [r.split("|")[1].strip() for r in section.splitlines()
            if r.startswith("| ")]
    return {r for r in rows if r != "layer" and set(r) - set("- ")}


def reader_constants(path):
    """The constants a reader file states at its top level, its docstring
    and whether it defines `read`, without importing it (a reader of
    another root is a file, not a module of this process)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = {"__doc__": ast.get_docstring(tree), "read": False}
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == "read":
            out["read"] = True
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant):
            out[node.targets[0].id] = node.value.value
    return out


def every_configuration_has_its_file(spec, root):
    configs = _by_name(spec["configs"])
    used = {w["config"] for w in spec["workloads"]}
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files), files
    for name, c in configs.items():
        assert set(c) == {"name", "source", "file", "reduced", "why"}, name
        assert _line(c["source"]) and _line(c["why"]), name
        assert name in used, "%s: no cell uses it" % name
        assert _under_paths(spec, c["file"]), c["file"]
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert c["reduced"] == cfg["reduced"] and len(c["reduced"]) <= 16, \
            name
        assert all(NAME.match(k) and k in cfg for k in c["reduced"]), name
        # the program's model of the family, with its plain reference
        for side in ("models", "reference"):
            assert os.path.exists(os.path.join(
                root, "benchmarks", side, cfg["family"] + ".py")), \
                (name, side)


def every_workload_finds_its_configuration_and_traffic(spec, root):
    configs = _by_name(spec["configs"])
    cells = _by_name(spec["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(set(pairs)) == len(pairs), pairs
    for name, w in cells.items():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, name
        assert _line(w["why"]) and w["chips"] in (1, 4), name
        assert w["config"] in configs and NAME.match(w["traffic"]), name
        with open(os.path.join(root, "benchmarks", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            root, "benchmarks", "kinds", mix["kind"] + ".py")), name


def at_most_a_quarter_of_the_cells_ask_for_four_chips(spec):
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def every_per_layer_entry_has_its_reader(spec, root, layers):
    end_to_end = _by_name(spec["end_to_end"])
    cells = _by_name(spec["workloads"])
    entries = _by_name(spec["per_layer"])
    assert not set(entries) & set(end_to_end)

    def reports(cell, metric):
        return cell in end_to_end[metric].get("workloads", cells)

    for name, m in entries.items():
        assert METRIC_KEYS <= set(m) <= METRIC_KEYS | {"workloads"}, name
        r = reader_constants(os.path.join(
            root, "benchmarks", "layer_metrics",
            name.replace("-", "_") + ".py"))
        assert (r["UNIT"], r["BETTER"], r["SOURCE"], r["LAYER"],
                r["MOVES"]) == (m["unit"], m["better"], m["source"],
                                m["layer"], m["moves"]), name
        assert r["__doc__"] and r["read"], name
        assert m["better"] in ("lower", "higher"), name
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), name
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), name
        assert m["layer"] in layers, "%s: PERF.md section 3 has no " \
            "layer %r" % (name, m["layer"])
        assert m["moves"] in end_to_end, name
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= set(cells) \
                and len(set(m["workloads"])) == len(m["workloads"]), name
        # a cell that reads it reports the end-to-end metric it moves
        for cell in m.get("workloads", cells):
            assert reports(cell, m["moves"]), (name, cell)
    for cell in cells:
        assert [m for m in entries.values()
                if cell in m.get("workloads", cells)], cell
        assert reports(cell, "setup_s") and [
            e for e in end_to_end if e != "setup_s" and reports(cell, e)]


def the_shape_holds(spec, root, layers):
    """Every rule above, on one spec."""
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    every_configuration_has_its_file(spec, root)
    every_workload_finds_its_configuration_and_traffic(spec, root)
    at_most_a_quarter_of_the_cells_ask_for_four_chips(spec)
    every_per_layer_entry_has_its_reader(spec, root, layers)


def with_a_fifth_cell(spec):
    """What the next `model_config` PR does to a spec, made up: a copy of
    *spec* with one configuration, one one-chip cell under `fit_prefetch`
    and two per-layer entries that name the new cell alone, each appended
    to its list, and the files the entries name, as ``{path: text}``.
    The names are ones *spec* does not hold yet, so a spec that was grown
    this way can be grown again."""
    spec = copy.deepcopy(spec)
    taken = {c["name"] for c in spec["configs"]}
    tag = next("made-up-%d" % n for n in range(1, len(taken) + 2)
               if "made-up-%d" % n not in taken)
    config, cell = tag, tag + "_train_ep8share"
    family = tag.replace("-", "_")
    source = "https://example.org/%s/config.json" % tag
    files = {
        "benchmarks/configs/%s.json" % config: json.dumps({
            "family": family, "source": source, "hidden_size": 64,
            "num_hidden_layers": 4, "reduced": ["num_hidden_layers"]}),
        "benchmarks/models/%s.py" % family: '"""A made-up family."""\n',
        "benchmarks/reference/%s.py" % family: '"""Its reference."""\n'}
    spec["configs"].append({
        "name": config, "source": source, "reduced": ["num_hidden_layers"],
        "file": "benchmarks/configs/%s.json" % config, "why": "made up"})
    spec["workloads"].append({
        "name": cell, "config": config, "traffic": "fit_prefetch",
        "chips": 1, "why": "made up: the cell a later PR appends"})
    for name, unit, better, layer in (
            (family + "_mask_ms_per_step", "ms", "lower", "step program"),
            (family + "_flash_roofline_pct", "%", "higher", "kernels")):
        files["benchmarks/layer_metrics/%s.py" % name] = (
            '"""A made-up reader."""\nLAYER = %r\nUNIT = %r\n'
            'MOVES = "train_samples_per_s"\nBETTER = %r\n'
            'SOURCE = "device_trace"\n\n\ndef read(outcome):\n'
            '    return None\n' % (layer, unit, better))
        spec["per_layer"].append({
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "train_samples_per_s", "workloads": [cell]})
    return spec, files


def write_files(root, files):
    for path, text in files.items():
        os.makedirs(os.path.dirname(os.path.join(root, path)),
                    exist_ok=True)
        with open(os.path.join(root, path), "w") as f:
            f.write(text)


# -- the rules on the tree, and on the tree with a fifth cell -----------------
@pytest.mark.parametrize("grown", [False, True],
                         ids=["the_tree", "a_fifth_cell_appended"])
def test_the_shape_rules_hold_for_any_number_of_cells(spec, tmp_path, grown):
    root = util.REPO
    if grown:
        root = str(tmp_path / "root")
        shutil.copytree(os.path.join(util.REPO, "benchmarks"),
                        os.path.join(root, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        before = len(spec["workloads"])
        spec, files = with_a_fifth_cell(spec)
        write_files(root, files)
        assert len(spec["workloads"]) == before + 1
    the_shape_holds(spec, root, perf_md_layers())


@pytest.mark.parametrize("broken, said", [
    (lambda s, layers: layers.discard("kernels"),
     "PERF.md section 3 has no layer 'kernels'"),
    (lambda s, layers: s["per_layer"].append(
        dict(util.named(s["per_layer"], "step_device_ms"))), "a name twice"),
    (lambda s, layers: util.named(s["workloads"], "resnet50_train").update(
        config="no-such-config"), "no cell uses it"),
    (lambda s, layers: [w.update(chips=4) for w in s["workloads"]], None),
    (lambda s, layers: util.named(s["per_layer"], "step_device_ms").update(
        better="higher"), "step_device_ms"),
    (lambda s, layers: util.named(s["per_layer"], "batchnorm_ms_per_step"
                              ).update(workloads=["no-such-cell"]),
     "batchnorm_ms_per_step"),
    (lambda s, layers: util.named(s["configs"], "resnet50_v1").update(
        reduced=["hidden_size"]), "resnet50_v1")],
    ids=["unknown_layer", "name_twice", "unknown_config", "four_chips",
         "better_is_not_the_readers", "unknown_cell", "reduced_differs"])
def test_the_shape_rules_refuse_a_spec_that_breaks_them(spec, broken, said):
    spec, layers = copy.deepcopy(spec), perf_md_layers()
    broken(spec, layers)
    with pytest.raises(AssertionError, match=said):
        the_shape_holds(spec, util.REPO, layers)


def test_every_cell_finds_its_files(spec):
    """In this tree the files the rules found are modules that import."""
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        with open(os.path.join(util.REPO, configs[w["config"]]["file"])) as f:
            cfg = json.load(f)
        importlib.import_module("benchmarks.models." + cfg["family"])
        importlib.import_module("benchmarks.reference." + cfg["family"])
        with open(os.path.join(util.REPO, "benchmarks", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        kind = importlib.import_module("benchmarks.kinds." + mix["kind"])
        assert callable(kind.run)


def test_every_per_layer_metric_has_its_reader(spec):
    """In this tree every reader imports, as the harness imports it."""
    for m in spec["per_layer"]:
        reader = importlib.import_module(
            "benchmarks.layer_metrics." + m["name"].replace("-", "_"))
        assert reader.__doc__ and callable(reader.read), m["name"]
    # no reader file waits undeclared beside the declared ones
    here = os.path.join(util.REPO, "benchmarks", "layer_metrics")
    assert {f[:-3] for f in os.listdir(here)
            if f.endswith(".py") and f != "__init__.py"} == \
        {m["name"] for m in spec["per_layer"]}


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
                'MOVES = "train_samples_per_s"\nBETTER = "higher"\n'
                'SOURCE = "program_counter"\n'
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
