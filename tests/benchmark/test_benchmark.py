"""The benchmark's tests, in one file on purpose.

Tier-1 runs under ``pytest-xdist --dist loadfile``, which hands out the
files with the most tests first, and the run is cut by its clock before it
reaches the small files. One large file is scheduled at the start: its
tests are counted, and its rehearsal runs (which start a cluster in a
process of their own) are over long before another worker's end-of-run
leak gate sweeps ``/dev/shm/ray_tpu``. No test here starts a cluster in
this process; each subprocess has its own time limit.
"""

import ast
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import dense_decoder as dense_family
from benchmark.harness import flops, lastline, loader, peaks, xplane
from benchmark.harness.modelcfg import build_llama_config, check_supported
from benchmark.reference import dense_decoder
from benchmark.traffic import open_loop_lognormal as chat
from benchmark.traffic import packed_documents as docs
from ray_tpu.models.llama import init_llama, llama_forward, llama_loss


# --------------------------------------------------------------------------
# the last line's validator, on good and bad objects
# --------------------------------------------------------------------------
SERVE, TRAIN = "serve_chat_steady", "train_l2_seq4k"


def good(manifest, cell, trace):
    values = {m["name"]: 1.5 for g in ("end_to_end", "per_layer")
              for m in manifest[g]}
    device = {"platform": "tpu", "kind": "TPU v5 lite",
              "count": next(w["chips"] for w in manifest["workloads"]
                            if w["name"] == cell),
              "memory_peak_bytes": 9_000_000_000,
              "window_s": 3.0, "busy_s": 1.25}
    breakdown = {"device_ops": [["fusion.1", 0.5], ["while", 0.25]],
                 "idle_gaps": [["between_steps", 0.125]]}
    return lastline.build(manifest, cell, trace, values=values, device=device,
                          correct=True, attempted=140, failed=0,
                          breakdown=breakdown)


def cells(manifest):
    return [w["name"] for w in manifest["workloads"]]


@pytest.mark.parametrize("trace", [False, True])
def test_good_lines_pass_in_every_cell(manifest, trace):
    for cell in cells(manifest):
        line = good(manifest, cell, trace)
        assert lastline.violations(line, manifest, cell, trace) == []
        # one line of JSON, and nothing but the contract's keys
        text = lastline.dumps(line)
        assert "\n" not in text and json.loads(text) == line
        assert set(line) <= {"correct", "attempted", "failed", "metrics",
                             "device", "breakdown"}


def test_untraced_line_has_the_cells_end_to_end_metrics_only(manifest):
    line = good(manifest, SERVE, False)
    want = {m["name"] for m in manifest["end_to_end"]
            if "workloads" not in m or SERVE in m["workloads"]}
    assert set(line["metrics"]) == want >= {
        "setup_s", "serve_gap_p95_ms", "serve_tokens_per_s"}
    assert not any(name.endswith(".serve") for name in line["metrics"])
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_traced_line_has_every_metric_of_the_cell(manifest):
    line = good(manifest, TRAIN, True)
    want = {m["name"] for g in ("end_to_end", "per_layer")
            for m in manifest[g]
            if "workloads" not in m or TRAIN in m["workloads"]}
    assert set(line["metrics"]) == want
    assert "collective_ms.train" not in want


def _drop(path):
    def edit(line):
        obj = line
        for k in path[:-1]:
            obj = obj[k]
        del obj[path[-1]]
    return edit


def _set(path, value):
    def edit(line):
        obj = line
        for k in path[:-1]:
            obj = obj[k]
        obj[path[-1]] = value
    return edit


BAD = [
    ("not an object", True, lambda line: None, "not a JSON object"),
    ("no metrics", True, _drop(["metrics"]), "'metrics' is missing"),
    ("no device", False, _drop(["device"]), "'device' is missing"),
    ("stray key", False, _set(["note"], "hello"), "does not belong"),
    ("breakdown untraced", False, _set(["breakdown"], {}), "does not belong"),
    ("correct a string", False, _set(["correct"], "yes"), "'correct'"),
    ("attempted negative", False, _set(["attempted"], -1), "not a count"),
    ("failed above attempted", False, _set(["failed"], 999), "more failed"),
    ("a per-layer metric missing", True,
     _drop(["metrics", "engine_step_ms.serve"]), "engine_step_ms.serve"),
    ("an end-to-end metric missing", False,
     _drop(["metrics", "serve_gap_p95_ms"]), "serve_gap_p95_ms"),
    ("setup_s missing", False, _drop(["metrics", "setup_s"]), "setup_s"),
    ("metric a bare number", False,
     _set(["metrics", "setup_s"], 12.5), "not {value, unit}"),
    ("metric with a third key", False,
     _set(["metrics", "setup_s"], {"value": 1.0, "unit": "s", "why": "x"}),
     "not {value, unit}"),
    ("wrong unit", False,
     _set(["metrics", "setup_s"], {"value": 1.0, "unit": "ms"}),
     "BENCHMARK.json says"),
    ("unit with a space", False,
     _set(["metrics", "serve_tokens_per_s"],
          {"value": 1.0, "unit": "tokens per second"}), "allowed form"),
    ("unit too long", False,
     _set(["metrics", "setup_s"], {"value": 1.0, "unit": "s" * 17}),
     "allowed form"),
    ("value NaN", False,
     _set(["metrics", "setup_s"], {"value": float("nan"), "unit": "s"}),
     "finite"),
    ("value a string", False,
     _set(["metrics", "setup_s"], {"value": "12", "unit": "s"}), "finite"),
    ("end-to-end zero", False,
     _set(["metrics", "serve_tokens_per_s"],
          {"value": 0.0, "unit": "tokens/s"}), "above 0"),
    ("unknown metric", False,
     _set(["metrics", "made_up"], {"value": 1.0, "unit": "s"}),
     "not in BENCHMARK.json"),
    ("metric name with a space", False,
     _set(["metrics", "made up"], {"value": 1.0, "unit": "s"}),
     "allowed form"),
    ("no platform", False, _drop(["device", "platform"]), "device.platform"),
    ("no memory", False, _drop(["device", "memory_peak_bytes"]),
     "memory_peak_bytes"),
    ("memory zero", False, _set(["device", "memory_peak_bytes"], 0),
     "positive byte count"),
    ("wrong chip count", False, _set(["device", "count"], 4), "asks for"),
    ("traced without busy_s", True, _drop(["device", "busy_s"]), "busy_s"),
    ("traced without window_s", True, _drop(["device", "window_s"]),
     "window_s"),
    ("busy_s zero", True, _set(["device", "busy_s"], 0.0), "not in (0"),
    ("busy_s above window_s", True, _set(["device", "busy_s"], 3.5),
     "not in (0"),
    ("device with a stray key", False, _set(["device", "pid"], 12),
     "does not belong"),
    ("breakdown too long", True,
     _set(["breakdown", "device_ops"], [["op", 0.1]] * 11), "at most"),
    ("breakdown entry malformed", True,
     _set(["breakdown", "idle_gaps"], [["gap"]]), "[name, seconds]"),
    ("breakdown missing a list", True, _drop(["breakdown", "idle_gaps"]),
     "device_ops and idle_gaps"),
]


@pytest.mark.parametrize("what,trace,edit,reason", BAD,
                         ids=[b[0] for b in BAD])
def test_bad_lines_are_refused_with_the_reason(manifest, what, trace, edit,
                                               reason):
    line = copy.deepcopy(good(manifest, SERVE, trace))
    if what == "not an object":
        line = [line]
    else:
        edit(line)
    found = lastline.violations(line, manifest, SERVE, trace)
    assert found and any(reason in v for v in found), found
    with pytest.raises(lastline.LastLineError):
        lastline.validate(line, manifest, SERVE, trace)


def test_a_share_of_a_peak_above_105_is_refused(manifest):
    line = good(manifest, TRAIN, True)
    line["metrics"]["mfu_pct.train"]["value"] = 106.0
    assert any("peak" in v for v in
               lastline.violations(line, manifest, TRAIN, True))
    line["metrics"]["mfu_pct.train"]["value"] = 61.0
    line["metrics"]["flash_attn_roofline_pct.train"]["value"] = 140.0
    assert any("peak" in v for v in
               lastline.violations(line, manifest, TRAIN, True))


def test_a_reader_that_found_nothing_leaves_its_metric_out(manifest):
    values = {m["name"]: 2.0 for g in ("end_to_end", "per_layer")
              for m in manifest[g]}
    values["flash_attn_ms.train"] = None
    line = lastline.build(
        manifest, TRAIN, True, values=values,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "window_s": 2.0, "busy_s": 1.0},
        correct=True, attempted=3, failed=0)
    assert "flash_attn_ms.train" not in line["metrics"]
    assert any("flash_attn_ms.train" in v for v in
               lastline.violations(line, manifest, TRAIN, True))


def test_unknown_cell(manifest):
    assert lastline.violations({}, manifest, "no_such_cell", False)


# --------------------------------------------------------------------------
# the loader: files resolve, files and BENCHMARK.json agree, one of each can be added
# --------------------------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTHS = re.compile(r"(hidden_size|intermediate_size|head_dim|_dim$|_rank$"
                    r"|num_experts_per_tok)")


def cell_names():
    return [f[:-5] for f in sorted(os.listdir(
        os.path.join(loader.BENCH_DIR, "workloads")))
        if f.endswith(".json") and not f.endswith(".rehearsal.json")]


def test_manifest_has_the_contracts_keys_and_limits(manifest, repo_root):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(repo_root, "BENCHMARK.json")) < 65536
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and lastline.UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_listed_cell_resolves_and_agrees_with_its_files(manifest):
    used = set()
    for w in manifest["workloads"]:
        cell = loader.load_cell(w["name"])
        used.add(cell["config"])
        assert (cell["config"], cell["traffic"]["name"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert hasattr(loader.load_driver(cell), "run")
        gen = loader.load_traffic(cell)
        assert hasattr(gen, "rows") or hasattr(gen, "schedule")
        # its rehearsal lays tiny sizes over the same keys
        tiny = loader.load_cell(w["name"], rehearsal=True)
        assert tiny["model"]["hidden_size"] < cell["model"]["hidden_size"]
        assert set(tiny["model"]) == set(cell["model"])
        # the configuration's family and its reference are files too
        family = loader.load_family(cell["model"])
        assert family is loader.load_family(tiny["model"])
        assert os.path.exists(os.path.join(
            loader.BENCH_DIR, "reference", family.REFERENCE + ".py"))
    assert used == {c["name"] for c in manifest["configs"]}


def test_every_cell_file_is_loadable_even_if_not_listed():
    for name in cell_names():
        cell = loader.load_cell(name)
        assert callable(loader.load_driver(cell).run)
        assert loader.metrics_for_cell(cell)


def config_names():
    return [f[:-5] for f in sorted(os.listdir(
        os.path.join(loader.BENCH_DIR, "configs"))) if f.endswith(".json")]


def test_configurations_state_source_cut_and_keep_every_width(manifest):
    """What every configuration file owes, whatever its family."""
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    listed = {c["name"]: c for c in manifest["configs"]}
    assert set(listed) <= set(config_names())
    for name in config_names():
        cfg = loader.load_config(name)
        assert cfg["source"].startswith("https://huggingface.co/"), name
        assert not any(WIDTHS.search(k) for k in cfg["reduced"]), name
        assert set(cfg["changed_from_source"]) == set(cfg["reduced"]), name
        for k, change in cfg["changed_from_source"].items():
            assert set(change) == {"source", "here"}, (name, k)
            assert change["here"] == cfg[k] != change["source"], (name, k)
        assert cfg["assumed"] and cfg["deployment"], name
        # its family resolves and accepts the file
        loader.load_family(cfg).check(cfg)
        if name in listed:
            c = listed[name]
            assert c["file"] == f"benchmark/configs/{name}.json"
            assert (cfg["source"], cfg["reduced"]) == (c["source"],
                                                       c["reduced"])


MISTRAL_7B_V03 = ("https://huggingface.co/mistralai/Mistral-7B-v0.3/"
                  "blob/main/config.json")


@pytest.mark.parametrize("name", [
    n for n in config_names()
    if loader.load_config(n)["source"] == MISTRAL_7B_V03])
def test_the_mistral_files_keep_mistrals_widths(name):
    """A pin of these files alone: another source brings its own."""
    full = {"hidden_size": 4096, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "head_dim": 128, "vocab_size": 32768, "rope_theta": 1e6,
            "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
            "sliding_window": None, "tie_word_embeddings": False}
    cfg = loader.load_config(name)
    for k, v in full.items():
        assert cfg[k] == v, (name, k)
    assert cfg["family"] == "dense_decoder"
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["changed_from_source"]["num_hidden_layers"] == {
        "source": 32, "here": cfg["num_hidden_layers"]}
    assert cfg["program"]["attn_impl"] == "flash"


def test_all_three_mistral_files_are_pinned():
    names = [n for n in config_names()
             if loader.load_config(n)["source"] == MISTRAL_7B_V03]
    assert names == ["mistral7b-serve-l16", "mistral7b-train-l2",
                     "mistral7b-train-l8-fsdp4"]


def test_metric_files_agree_with_the_manifest(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    files = {m["name"]: m for m in loader.load_metric_files()}
    kinds = {name: loader.load_cell(name)["kind"] for name in cell_names()}
    in_manifest = {w["name"] for w in manifest["workloads"]}
    for name, f in files.items():
        assert callable(loader.load_reader(f))
        cells = {c for c in in_manifest
                 if c in f.get("cells", ()) or kinds[c] in f.get("kinds", ())}
        if not cells:  # a metric of cells that are written but not listed
            assert name not in listed
            continue
        for k in ("unit", "better", "source", "layer", "moves"):
            assert f[k] == listed[name][k], (name, k)
        assert set(listed[name]["workloads"]) == cells, name
    assert set(listed) <= set(files)


def test_every_moves_is_reported_wherever_the_layer_metric_is(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in manifest["per_layer"]:
        target = e2e[m["moves"]]
        cells = m.get("workloads") or [w["name"]
                                       for w in manifest["workloads"]]
        for c in cells:
            assert "workloads" not in target or c in target["workloads"], (
                m["name"], c)
    for w in manifest["workloads"]:
        assert len(loader.manifest_metrics(manifest, w["name"],
                                           "end_to_end")) >= 2
        assert loader.manifest_metrics(manifest, w["name"], "per_layer")
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_files_under_paths_are_named_from_a_names_characters(manifest,
                                                             repo_root):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(repo_root, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), repo_root)
                assert ok.match(rel), rel


# what a `model_config` PR brings for a family the benchmark does not have:
# a module under families/, its reference, a configuration of it
NEW_FAMILY = '''
from benchmark.harness.loader import BOOKKEEPING_KEYS

REFERENCE = "sparse_decoder"
SHAPE_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "vocab_size", "num_experts", "num_experts_per_tok")


def check(m):
    unknown = sorted(set(m) - set(SHAPE_KEYS) - set(BOOKKEEPING_KEYS))
    if unknown:
        raise ValueError(f"the sparse family does not understand {unknown}")


def train_flops_per_token(m, seq):
    active = (3 * m["hidden_size"] * m["intermediate_size"]
              * m["num_experts_per_tok"])
    return 6.0 * (m["num_hidden_layers"] * active
                  + m["vocab_size"] * m["hidden_size"])
'''
NEW_REFERENCE = '''
def loss(params, inputs, targets, m):
    return 0.0


def last_logits(params, tokens, m):
    return [0.0] * m["vocab_size"]
'''


def test_one_of_each_can_be_added_without_editing_a_file(tmp_path, manifest):
    bench = tmp_path / "benchmark"
    shutil.copytree(loader.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "families" / "sparse_decoder.py").write_text(NEW_FAMILY)
    (bench / "reference" / "sparse_decoder.py").write_text(NEW_REFERENCE)
    cfg = {k: v for k, v in loader.load_config("mistral7b-train-l2").items()
           if k in loader.BOOKKEEPING_KEYS}
    cfg.update(name="other-l3", family="sparse_decoder", hidden_size=2048,
               intermediate_size=1024, num_hidden_layers=3, vocab_size=50304,
               num_experts=64, num_experts_per_tok=8)
    (bench / "configs" / "other-l3.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "saw_tooth.py").write_text(
        "def rows(table, *, params, seed, vocab):\n"
        "    return {'inputs': [], 'targets': []}\n")
    (bench / "traffic" / "saw_3x1k.json").write_text(json.dumps(
        {"name": "saw_3x1k", "generator": "saw_tooth", "seq": 1024,
         "rows_per_step": 3}))
    cell = json.loads((bench / "workloads" / "train_l2_seq4k.json")
                      .read_text())
    cell.update(name="train_l3_saw", config="other-l3", traffic="saw_3x1k")
    (bench / "workloads" / "train_l3_saw.json").write_text(json.dumps(cell))
    (bench / "readers" / "steps_done.py").write_text(
        "def read(view, metric):\n    return float(view['obs']['steps'])\n")
    (bench / "metrics" / "steps_done.train.json").write_text(json.dumps(
        {"name": "steps_done.train", "layer": "step", "unit": "steps",
         "moves": "train_tokens_per_s_per_chip", "better": "higher",
         "source": "program_counter", "cells": ["train_l3_saw"],
         "reader": "steps_done:read"}))

    got = loader.load_cell("train_l3_saw", bench_dir=str(bench))
    assert got["model"]["num_hidden_layers"] == 3
    # the cell goes through its own family and reference, found by name
    family = loader.load_family(got["model"], bench_dir=str(bench))
    assert family.__file__ == str(bench / "families" / "sparse_decoder.py")
    reference = loader.load_reference(got["model"], bench_dir=str(bench))
    assert reference.__file__ == str(bench / "reference" / "sparse_decoder.py")
    assert reference.loss(None, [], [], got["model"]) == 0.0
    # which the dense family would have refused, naming the key it meets
    with pytest.raises(ValueError, match="num_experts"):
        dense_family.check(got["model"])
    # and a key its own family does not know stops the cell from loading
    (bench / "configs" / "other-l3.json").write_text(
        json.dumps(dict(cfg, kv_lora_rank=512)))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        loader.load_cell("train_l3_saw", bench_dir=str(bench))
    (bench / "configs" / "other-l3.json").write_text(json.dumps(cfg))
    assert got["traffic"]["rows_per_step"] == 3
    assert loader.load_traffic(got, bench_dir=str(bench)).rows(
        None, params=None, seed=0, vocab=0) == {"inputs": [], "targets": []}
    assert hasattr(loader.load_driver(got, bench_dir=str(bench)), "run")
    mine = loader.metrics_for_cell(got, bench_dir=str(bench))
    assert "steps_done.train" in {m["name"] for m in mine}
    assert "mfu_pct.train" in {m["name"] for m in mine}  # by its kind
    # whose reader counts with the cell's family, not with the dense
    # family's layer: 8 experts of 1024 a token, and the head
    assert family.train_flops_per_token(got["model"], 1024) == 6.0 * (
        3 * 3 * 2048 * 1024 * 8 + 50304 * 2048)
    new = next(m for m in mine if m["name"] == "steps_done.train")
    assert loader.load_reader(new, bench_dir=str(bench))(
        {"obs": {"steps": 7}}, new) == 7.0
    # the old cells do not see the new metric, and no old file changed
    old = loader.metrics_for_cell(
        loader.load_cell("train_l2_seq4k", bench_dir=str(bench)),
        bench_dir=str(bench))
    assert "steps_done.train" not in {m["name"] for m in old}
    assert all(p.read_bytes() == data for p, data in before.items())

    # and the manifest takes the new entries beside the old ones
    grown = json.loads(json.dumps(manifest))
    grown["workloads"].append({"name": "train_l3_saw", "config": "other-l3",
                               "traffic": "saw_3x1k", "chips": 1,
                               "why": "x"})
    grown["per_layer"].append(
        {"name": "steps_done.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "step",
         "moves": "train_tokens_per_s_per_chip",
         "workloads": ["train_l3_saw"]})
    assert "steps_done.train" in lastline.required_metrics(
        grown, "train_l3_saw", True)
    assert "steps_done.train" not in lastline.required_metrics(
        grown, "train_l2_seq4k", True)


def test_a_missing_or_misnamed_file_is_an_error(tmp_path):
    with pytest.raises(loader.BenchmarkFileError, match="missing file"):
        loader.load_cell("no_such_cell")
    bench = tmp_path / "benchmark"
    shutil.copytree(loader.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    bad = bench / "workloads" / "renamed.json"
    bad.write_text((bench / "workloads" / "train_l2_seq4k.json").read_text())
    with pytest.raises(loader.BenchmarkFileError, match="names itself"):
        loader.load_cell("renamed", bench_dir=str(bench))


def test_the_harness_side_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import benchmark.run\n"
        "from benchmark.harness import lastline, loader, peaks, stats, flops\n"
        "from benchmark.harness import modelcfg\n"
        "from benchmark.families import dense_decoder\n"
        "assert loader.load_family({'family': 'dense_decoder'})"
        " is dense_decoder\n"
        "from benchmark.drivers import train, serve\n"
        "from benchmark.traffic import open_loop_lognormal, packed_documents\n"
        "from benchmark.tools import sweep_rate\n"
        "for m in loader.load_metric_files(): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# parameter and FLOP counts against the sizes worked out in ISSUE 23
# --------------------------------------------------------------------------
def model(name):
    return loader.load_config(name)


@pytest.mark.parametrize("config,millions", [
    ("mistral7b-train-l2", 704.7),
    ("mistral7b-train-l8-fsdp4", 2013.3),
    ("mistral7b-serve-l16", 3758.2),
])
def test_parameter_counts(config, millions):
    assert flops.num_params(model(config)) / 1e6 == pytest.approx(
        millions, abs=0.06)
    assert dense_family.num_params is flops.num_params


def test_one_layer_and_the_embedding():
    m = model("mistral7b-train-l2")
    assert flops.layer_params(m) / 1e6 == pytest.approx(218.1, abs=0.05)
    assert flops.embed_and_head_params(m) / 1e6 == pytest.approx(268.4,
                                                                 abs=0.05)


def test_the_programs_own_count_agrees():
    from benchmark.harness.modelcfg import build_llama_config

    for name in ("mistral7b-train-l2", "mistral7b-serve-l16"):
        m = model(name)
        assert build_llama_config(m).num_params() == flops.num_params(m)


def test_train_flops_leave_out_the_lookup_and_half_the_square():
    m = model("mistral7b-train-l2")
    seq = 4096
    matmul = 6.0 * (2 * (218.1e6 - 8192) + 32768 * 4096)
    attn = 2 * 7 * 2.0 * 32 * 128 * seq / 2
    assert flops.train_flops_per_token(m, seq) == pytest.approx(
        matmul + attn, rel=1e-3)
    # 3.66 GFLOP a token; the issue's 3.8 counts the attention square whole
    assert flops.train_flops_per_token(m, seq) / 1e9 == pytest.approx(
        3.66, abs=0.01)
    assert flops.train_flops_per_token(
        model("mistral7b-train-l8-fsdp4"), seq) / 1e9 == pytest.approx(
        12.21, abs=0.02)


def test_flash_kernels_need_seven_causal_matmuls():
    m = model("mistral7b-train-l2")
    one = 2.0 * 4096 * 4096 * 128 * 32 / 2  # one causal S x S x d matmul
    assert flops.flash_train_flops(m, 1, 4096) == pytest.approx(
        m["num_hidden_layers"] * 7 * one)
    assert flops.flash_train_flops(m, 2, 4096) == pytest.approx(
        2 * flops.flash_train_flops(m, 1, 4096))
    # compute bounds the kernels at this length, not HBM
    pk = peaks.peak("TPU v5 lite")
    assert (flops.flash_train_flops(m, 2, 4096) / pk["bf16_flops_per_s"]
            > flops.flash_train_bytes(m, 2, 4096) / pk["hbm_bytes_per_s"])


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in benchmark/harness/peaks.py"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# --------------------------------------------------------------------------
# the traffic generators
# --------------------------------------------------------------------------
BIG_SEED = 3_000_000_019  # more than 32 signed bits hold


def mix(name):
    with open(os.path.join(loader.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_chat_same_seed_same_schedule():
    p = mix("chat_steady")
    a = chat.schedule(p, seed=BIG_SEED, seconds=40, vocab=32768)
    b = chat.schedule(p, seed=BIG_SEED, seconds=40, vocab=32768)
    assert a == b


def test_chat_another_seed_same_requests_other_tokens():
    p = mix("chat_steady")
    a = chat.schedule(p, seed=BIG_SEED, seconds=40, vocab=32768)
    b = chat.schedule(p, seed=BIG_SEED + 1, seconds=40, vocab=32768)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert len(a) == len(b) == round(p["rate_per_s"] * 40)
    # the same requests at the same times: the seed changes no work
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    # another order_seed is another order of the same multiset
    c = chat.schedule(dict(p, order_seed=p["order_seed"] + 1), seed=BIG_SEED,
                      seconds=40, vocab=32768)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in c]
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)


def test_chat_clips_and_window_hold():
    p = mix("chat_steady")
    sched = chat.schedule(p, seed=7, seconds=40, vocab=32768)
    lens = [len(r["prompt"]) for r in sched]
    outs = [r["max_new"] for r in sched]
    assert min(lens) >= p["prompt_len"]["min"] == 32
    assert max(lens) <= p["prompt_len"]["max"] == 256
    assert min(outs) >= p["output_len"]["min"] == 8
    assert max(outs) <= p["output_len"]["max"] == 48
    assert abs(np.median(lens) - p["prompt_len"]["median"]) <= 3
    assert abs(np.median(outs) - p["output_len"]["median"]) <= 1
    due = [r["due_s"] for r in sched]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 40.0
    assert all(2 <= t < 32768 for r in sched for t in r["prompt"])
    # Poisson: gaps with a coefficient of variation near 1
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_chat_gaps_are_the_quantiles_of_an_exponential():
    gaps = chat.exponential_gaps(400, 4.0)
    assert gaps.sum() == pytest.approx(100.0)
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
    assert list(gaps) == sorted(gaps)  # quantiles: schedule() orders them


def test_chat_describe_counts_the_offered_tokens():
    p = mix("chat_steady")
    d = chat.describe(p, 40)
    sched = chat.schedule(p, seed=1, seconds=40, vocab=32768)
    assert d["requests"] == len(sched)
    assert d["output_tokens"] == sum(r["max_new"] for r in sched)


@pytest.mark.parametrize("name,rows", [("packed_docs_2x4k", 2),
                                       ("packed_docs_4x4k", 4)])
def test_packed_documents(name, rows):
    p = mix(name)
    assert p["rows_per_step"] == rows and p["seq"] == 4096
    ids = {"id": np.arange(rows)}
    a = docs.rows(ids, params=p, seed=BIG_SEED, vocab=32768)
    b = docs.rows(ids, params=p, seed=BIG_SEED, vocab=32768)
    c = docs.rows(ids, params=p, seed=BIG_SEED + 1, vocab=32768)
    assert a["inputs"].shape == a["targets"].shape == (rows, 4096)
    assert a["inputs"].dtype == np.int32
    assert (a["inputs"] == b["inputs"]).all()
    assert (a["inputs"] != c["inputs"]).any()
    assert (a["inputs"][:, 1:] == a["targets"][:, :-1]).all()
    assert a["inputs"].min() >= 1 and a["inputs"].max() < 32768
    assert (a["inputs"][:, 0] == p["bos_id"]).all()  # a document opens a row


def test_a_row_does_not_depend_on_its_block():
    p = mix("packed_docs_2x4k")
    whole = docs.rows({"id": np.arange(6)}, params=p, seed=5, vocab=32768)
    part = docs.rows({"id": np.arange(4, 6)}, params=p, seed=5, vocab=32768)
    assert (whole["inputs"][4:] == part["inputs"]).all()


def test_document_lengths_are_heavy_tailed_and_clipped():
    p = mix("packed_docs_2x4k")
    lens = docs.document_lengths(np.random.default_rng(3), p, 2_000_000)
    assert min(lens) >= p["doc_len_min"] and max(lens) <= p["doc_len_max"]
    assert abs(np.median(lens) - p["doc_len_median"]) < 60
    assert np.mean(lens) > 1.5 * np.median(lens)


# --------------------------------------------------------------------------
# the trace reduction: hand-made intervals, and a small trace recorded on the chip
# --------------------------------------------------------------------------
FIXTURES = os.path.join(loader.BENCH_DIR, "fixtures")


def test_merge_and_busy_union():
    iv = [(5, 6), (0, 2), (1, 3), (3, 4), (10, 10), (8, 7)]
    assert xplane.merge_intervals(iv) == [(0, 4), (5, 6)]
    assert xplane.busy_seconds(iv) == 5
    assert xplane.busy_seconds([]) == 0
    # an interval inside another adds nothing
    assert xplane.busy_seconds([(0, 10), (2, 3), (4, 5)]) == 10


@pytest.mark.parametrize("t0,t1,want", [
    (None, None, [(2, 4), (6, 9)]),
    (-1, 12, [(-1, 0), (2, 4), (6, 9), (10, 12)]),
    (0, 10, [(2, 4), (6, 9)]),
])
def test_idle_gaps(t0, t1, want):
    iv = [(0, 2), (4, 6), (9, 10), (4.5, 5)]
    assert xplane.idle_gaps(iv, t0, t1) == want


def test_idle_gaps_of_nothing():
    assert xplane.idle_gaps([], 1, 3) == [(1, 3)]
    assert xplane.idle_gaps([]) == []


def test_self_times_take_nested_operations_out_of_their_parent():
    events = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0),
              ("custom-call", 3.0, 4.0), ("copy", 12.0, 13.0),
              ("inner", 1.5, 2.0)]
    got = dict(xplane.self_times(events))
    assert got == {"while": 7.0, "fusion": 1.5, "inner": 0.5,
                   "custom-call": 1.0, "copy": 1.0}
    # self times of one stream add up to its busy time
    assert sum(got.values()) == xplane.busy_seconds(
        (s, e) for _, s, e in events)


def test_sum_by_name():
    got = xplane.sum_by_name([("a", 1.0), ("b", 2.0), ("a", 0.5)])
    assert got == {"a": [1.5, 2], "b": [2.0, 1]}


def test_gaps_are_labelled_with_the_span_that_covers_them():
    spans = [("input_wait", 1.0, 3.5), ("report", 20.1, 20.9),
             ("input_wait", 30.0, 30.1)]
    gaps = [(20.0, 21.0), (0.0, 4.0), (30.0, 32.0), (40.0, 40.5)]
    got = xplane.label_gaps(gaps, spans, top=3)
    assert got == [("input_wait", 4.0), ("between_steps", 2.0),
                   ("report", 1.0)]
    assert xplane.label_gaps(gaps, [], top=1) == [("unattributed", 4.0)]


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(FIXTURES, "small_v5e.xplane.pb")
    with open(os.path.join(FIXTURES, "small_v5e.expected.json")) as f:
        return path, json.load(f)


def test_recorded_trace_reduces_to_the_numbers_beside_it(recorded):
    path, want = recorded
    got = xplane.reduce_trace(path)
    assert got["n_devices"] == want["n_devices"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["span_s"] == pytest.approx(want["span_s"], rel=1e-9)
    assert [o[0] for o in got["ops"]] == [o[0] for o in want["ops"]]
    for (_, t, c), (_, wt, wc) in zip(got["ops"], want["ops"]):
        assert t == pytest.approx(wt, rel=1e-9) and c == wc
    assert got["gaps"] == [[lbl, pytest.approx(d, rel=1e-9)]
                           for lbl, d in want["gaps"]]


def test_recorded_trace_holds_together(recorded):
    path, want = recorded
    got = xplane.reduce_trace(path)
    # three calls of a scanned program on one TPU plane's XLA Ops line
    plane = got["planes"]["/device:TPU:0"]
    assert plane[xplane.OPS_LINE] > 0
    assert 0 < got["busy_s"] <= got["span_s"] <= want["window_s"]
    # one stream: the self times add up to the busy union
    assert sum(t for _, t, _ in got["ops"]) == pytest.approx(
        got["busy_s"], rel=1e-6)
    # the while of the scan is there, with its body taken out of it
    names = [n for n, _, _ in got["ops"]]
    assert any(n.startswith("while") for n in names)
    # the host's spans are on the device's clock: the gaps between the
    # three calls fall where the host slept in bench:input_wait
    assert got["host_spans"]["step"][1] == 3
    assert got["host_spans"]["input_wait"][1] == 3
    assert [lbl for lbl, _ in got["gaps"][:2]] == ["input_wait"] * 2
    assert all(d >= 0.003 for _, d in got["gaps"][:2])


def test_a_cpu_trace_has_no_device_plane_unless_rehearsing(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=xplane.profile_options())
    with jax.profiler.TraceAnnotation("bench:step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    real = xplane.reduce_trace(path)
    assert real["n_devices"] == 0 and real["busy_s"] == 0.0
    walk = xplane.reduce_trace(path, rehearsal=True)
    assert walk["n_devices"] == 1 and walk["busy_s"] > 0
    assert walk["host_spans"]["step"][1] == 1
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path / "nothing"))


# --------------------------------------------------------------------------
# the plain float32 reference against the program's model, tiny, on the CPU
# --------------------------------------------------------------------------
def tiny_model(**over):
    m = loader.load_cell("train_l2_seq4k", rehearsal=True)["model"]
    m = dict(m, program=dict(m["program"], attn_impl="reference",
                             dtype="float32", loss_chunk=0))
    m.update(over)
    return m


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = build_llama_config(m)
    params = init_llama(cfg, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, 64), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


def test_config_is_built_from_the_files_keys(setup):
    m, cfg, _, _ = setup
    assert (cfg.hidden, cfg.mlp_hidden, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.num_layers) == (
        m["hidden_size"], m["intermediate_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["vocab_size"],
        m["num_hidden_layers"])
    assert cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-5
    assert cfg.num_heads != cfg.num_kv_heads  # the grouped path is walked
    full = build_llama_config(loader.load_config("mistral7b-serve-l16"))
    assert full.param_dtype == jnp.bfloat16 and full.attn_impl == "flash"
    assert (full.hidden, full.mlp_hidden, full.num_kv_heads) == (
        4096, 14336, 8)


def test_logits_agree_with_llama_forward(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for row in range(tokens.shape[0]):
        want = dense_decoder.logits(params, tokens[row], m)
        # both float32 on the CPU: only the order of sums differs
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-4)
    last = dense_decoder.last_logits(params, tokens[0], m)
    np.testing.assert_allclose(got[0, -1], last, rtol=2e-4, atol=2e-4)


def test_loss_agrees_with_llama_loss(setup):
    m, cfg, params, tokens = setup
    inputs, targets = tokens[:1, :-1], tokens[:1, 1:]
    got = llama_loss(params, {"inputs": inputs, "targets": targets}, cfg)
    want = dense_decoder.loss(params, inputs[0], targets[0], m)
    assert float(got) == pytest.approx(float(want), abs=1e-4)
    assert abs(float(want) - np.log(m["vocab_size"])) < 1.0


def test_bf16_compute_is_told_from_float32(setup):
    """The tolerance of this file would fail a model computed in bf16."""
    m, _, params, tokens = setup
    cfg16 = build_llama_config(dict(m, program=dict(m["program"],
                                                    dtype="bfloat16")))
    got = llama_forward(params, tokens[:1], cfg16)[0]
    want = dense_decoder.logits(params, tokens[0], m)
    assert float(jnp.abs(got - want).max()) > 2e-3


def test_attention_blocks_of_queries_change_nothing(monkeypatch):
    q, k, v = (jax.random.normal(kk, s) for kk, s in zip(
        jax.random.split(jax.random.key(0), 3),
        [(48, 4, 8), (48, 2, 8), (48, 2, 8)]))
    whole = dense_decoder.grouped_causal_attention(q, k, v)
    monkeypatch.setattr(dense_decoder, "QUERY_BLOCK", 16)
    blocks = dense_decoder.grouped_causal_attention(q, k, v)
    np.testing.assert_allclose(whole, blocks, rtol=1e-5, atol=1e-6)
    # causal: the first position attends to itself alone
    np.testing.assert_allclose(whole[0], jnp.repeat(v[0], 2, axis=0),
                               rtol=1e-5)


@pytest.mark.parametrize("key,value", [("sliding_window", 4096),
                                       ("tie_word_embeddings", True),
                                       ("hidden_act", "gelu")])
def test_what_the_dense_path_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        check_supported(tiny_model(**{key: value}))


# --------------------------------------------------------------------------
# the seam between the benchmark and the program: a family module named by
# the configuration file, and a served class that warms and checks itself
# --------------------------------------------------------------------------
@pytest.mark.parametrize("key,value", [
    ("num_experts", 64), ("num_experts_per_tok", 8),
    ("norm_topk_prob", False), ("kv_lora_rank", 512),
    ("a_key_nobody_knows", 1), ("attention_dropout", 0.1)])
def test_the_dense_family_refuses_a_key_it_does_not_understand(key, value):
    """Before, ``num_experts: 64`` beside ``intermediate_size: 1024`` built
    a dense model of width 1024 and ran ``correct`` under the file's name."""
    m = dict(loader.load_config("mistral7b-train-l2"), **{key: value})
    with pytest.raises(ValueError, match=key):
        dense_family.check(m)
    with pytest.raises(ValueError, match=key):
        build_llama_config(m)


def test_a_configuration_that_names_no_family_is_an_error():
    m = loader.load_config("mistral7b-train-l2")
    del m["family"]
    with pytest.raises(loader.BenchmarkFileError, match="names no family"):
        loader.load_family(m)


BARRED = ("ray_tpu.models", "ray_tpu.serve.llm", "benchmark.harness.modelcfg",
          "benchmark.harness.flops", "benchmark.reference")


def imported_names(path):
    """Every module an ``import`` of the file names, at any depth."""
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


@pytest.mark.parametrize("driver", ["train", "serve"])
def test_a_driver_reaches_the_program_only_through_the_family(driver):
    names = imported_names(os.path.join(loader.BENCH_DIR, "drivers",
                                        driver + ".py"))
    assert "benchmark.harness.loader" in names
    bad = sorted(n for n in names
                 if any(n == b or n.startswith(b + ".") for b in BARRED))
    assert bad == []
    # the check finds what it looks for: the family imports all of them
    fam = imported_names(dense_family.__file__)
    assert {"ray_tpu.models.llama", "ray_tpu.serve.llm",
            "benchmark.harness.modelcfg", "benchmark.harness.flops"} <= fam


class FakeFamily:
    """A family whose counts no dense layer gives."""

    @staticmethod
    def train_flops_per_token(m, seq):
        return 1e9

    @staticmethod
    def attention_kernel_flops(m, rows, seq):
        return 197e12 * 1e-3 * rows

    @staticmethod
    def attention_kernel_bytes(m, rows, seq):
        return 0.0


@pytest.mark.parametrize("config,gflop_per_token", [
    ("mistral7b-train-l2", 3.66), ("mistral7b-train-l8-fsdp4", 12.21)])
def test_the_two_readers_count_with_the_cells_family(config, gflop_per_token,
                                                     monkeypatch):
    """The numbers the ledger's lines were made with, through the seam."""
    from benchmark.readers import flash_attn_roofline_pct_train as roofline
    from benchmark.readers import mfu_pct_train as mfu

    m = loader.load_config(config)
    pk = peaks.peak("TPU v5 lite")
    view = {"cell": {"model": m, "traffic": {"seq": 4096, "rows_per_step": 2}},
            "peaks": pk, "device": {"count": 1},
            "e2e": {"train_tokens_per_s_per_chip": 28313.0},
            "trace": {"ops": [("tpu_custom_call:x", 0.040, 6),
                              ("fusion.1", 1.0, 6)], "steps": 2}}
    got = mfu.read(view, {})
    assert got == pytest.approx(
        100.0 * gflop_per_token * 1e9 * 28313.0 / 197e12, rel=3e-3)
    assert got == 100.0 * flops.train_flops_per_token(m, 4096) * 28313.0 \
        / 197e12
    # seven causal matmuls a layer over 20 ms of kernels a step
    one = 2.0 * 4096 * 4096 * 128 * 32 / 2
    metric = {"match": "^tpu_custom_call:"}
    assert roofline.read(view, metric) == pytest.approx(
        100.0 * (m["num_hidden_layers"] * 7 * one * 2 / 197e12) / 0.020)
    assert roofline.read(dict(view, trace={"ops": [], "steps": 2}),
                         metric) is None
    # and it is the family the loader names that counts, not `flops`
    monkeypatch.setattr(loader, "load_family", lambda model: FakeFamily)
    assert mfu.read(view, {}) == pytest.approx(
        100.0 * 1e9 * 28313.0 / 197e12)
    assert roofline.read(view, metric) == pytest.approx(
        100.0 * 2e-3 / 0.020)


def test_the_family_gives_training_its_three_parts(setup):
    m, cfg, params, tokens = setup
    parts = dense_family.training(m)
    assert set(parts) == {"init", "logical_axes", "loss"}
    mine = parts["init"](jax.random.key(3))
    assert jax.tree.structure(mine) == jax.tree.structure(params) \
        == jax.tree.structure(parts["logical_axes"],
                              is_leaf=lambda x: isinstance(x, tuple))
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    assert float(parts["loss"](params, batch)) == float(
        llama_loss(params, batch, cfg))
    assert loader.load_reference(m) is dense_decoder


@pytest.fixture(scope="module")
def served():
    gen = dense_family.Served(config="tiny", max_batch_size=2,
                              allowed_batch_sizes=(2,), max_new_tokens=4,
                              seq_bucket=24)
    yield gen
    gen.engine.shutdown()


def test_last_position_logits_are_fwds_at_the_prompts_end(served):
    prompt = [3, 5, 7, 11, 13, 17, 19, 23]
    got = served.last_position_logits(prompt)
    tokens = np.zeros((2, len(prompt)), np.int32)
    tokens[0] = prompt
    want = np.asarray(served._fwd(served._params, jnp.asarray(tokens),
                                  None))[0, len(prompt) - 1]
    assert got.shape == (served._cfg.vocab_size,)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # the step's own choice of token is the argmax of these logits
    state = served._prefill({"prompt": prompt}, "")
    assert served._step("", [state, None])[0][0] == int(got.argmax())


def test_a_warmed_shape_leaves_the_step_nothing_to_compile(served):
    from benchmark.harness.onchip import count_compiles

    compiles = count_compiles()
    before = served.compiled_step_programs()
    served.warm_step_programs(48)
    assert compiles, "the listener saw the warm-up compile nothing"
    assert served.compiled_step_programs() == before + 1
    warmed = len(compiles)
    states = [served._prefill({"prompt": list(range(1, 30))}, ""),
              served._prefill({"prompt": list(range(1, 40))}, "")]
    for _ in range(2):
        out = served._step("", states)
        assert all(0 <= tok < served._cfg.vocab_size for tok, _ in out)
    assert len(compiles) == warmed, (
        f"{len(compiles) - warmed} compilation(s) in a warmed step")
    assert served.compiled_step_programs() == before + 1


def test_a_method_the_program_gains_takes_the_stand_ins_place():
    from ray_tpu.serve.llm import LlamaGenerator

    assert dense_family.Served.__mro__[1:3] == (
        LlamaGenerator, dense_family._OwedByTheProgram)

    class Program(LlamaGenerator):
        def warm_step_programs(self, seq_len):
            return "the program's own"

    class ServedThen(Program, dense_family._OwedByTheProgram):
        pass

    assert ServedThen.warm_step_programs is Program.warm_step_programs
    assert (ServedThen.last_position_logits
            is dense_family._OwedByTheProgram.last_position_logits)


def test_what_is_deployed_is_found_by_name_in_the_replica():
    """``bind_app``'s class has no body of its own: pickled by value, it
    names its two bases, which the replica imports."""
    import cloudpickle

    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell("serve_chat_steady", rehearsal=True)
    app = serve_driver.bind_app(cell, seed=1, rehearsal=True)
    cls = app.deployment.func_or_class
    assert cls.__bases__ == (serve_driver.BenchGenerator,
                             dense_family.Served)
    assert not set(vars(cls)) - {"__module__", "__doc__", "__qualname__"}
    again = cloudpickle.loads(cloudpickle.dumps(cls))
    assert again.__bases__ == cls.__bases__
    for name in ("bench_warm", "bench_check", "bench_device", "_step"):
        assert getattr(again, name) is getattr(serve_driver.BenchGenerator,
                                               name)
    for name in ("warm_step_programs", "last_position_logits",
                 "compiled_step_programs", "engine_stats", "__call__"):
        assert getattr(again, name) is getattr(dense_family.Served, name)


# --------------------------------------------------------------------------
# run.py --rehearsal in a process of its own: the real control flow on the CPU at tiny sizes,
# and the would-be last line held to the contract by the same validator
# --------------------------------------------------------------------------
MARK = "[bench REHEARSAL]"
LIMIT_S = 240


def run_rehearsal(repo_root, cell, trace, seconds):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONASYNCIODEBUG")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", str(seconds), "--trace", str(trace),
         "--rehearsal"],
        cwd=repo_root, env=env, capture_output=True, text=True,
        timeout=LIMIT_S)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def would_be_line(lines):
    head = MARK + " would-be last line: "
    found = [ln for ln in lines if ln.startswith(head)]
    assert len(found) == 1, lines[-5:]
    return json.loads(found[0][len(head):])


def check_rehearsal(repo_root, manifest, cell, trace, seconds):
    proc, lines = run_rehearsal(repo_root, cell, trace, seconds)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-3000:],
                                  lines[-5:])
    # every line of the benchmark's own says what it is; none is a result
    ours = [ln for ln in lines if ln.startswith("[bench")]
    assert ours and all(ln.startswith(MARK) for ln in ours)
    assert lines[-1] == MARK + " REHEARSAL ONLY: not a result"
    with_json = [ln for ln in lines if ln.lstrip().startswith("{")]
    assert with_json == []
    line = would_be_line(lines)
    lastline.validate(line, manifest, cell, bool(trace))
    assert line["correct"] is False and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert not any("NOT CORRECT" in ln for ln in lines), lines
    return line, lines


@pytest.mark.slow  # a cluster in a subprocess, 15-20 s
@pytest.mark.parametrize("trace", [0, 1])
def test_training_cell_rehearsal(repo_root, manifest, trace):
    line, lines = check_rehearsal(repo_root, manifest, "train_l2_seq4k",
                                  trace, seconds=3)
    assert line["attempted"] >= 2
    assert "train_tokens_per_s_per_chip" in line["metrics"]
    if trace:
        assert "breakdown" in line and line["breakdown"]["device_ops"]
        assert "step_device_ms.train" in line["metrics"]
        assert "collective_ms.train" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"setup_s",
                                        "train_tokens_per_s_per_chip"}
    assert any("compiles_in_window=0" in ln for ln in lines)


@pytest.mark.slow  # a cluster in a subprocess, 15-20 s
@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsal(repo_root, manifest, trace):
    line, lines = check_rehearsal(repo_root, manifest, "serve_chat_steady",
                                  trace, seconds=5)
    assert line["attempted"] == 10  # 2 requests/s for 5 s, whatever the seed
    assert {"serve_gap_p95_ms", "serve_tokens_per_s",
            "setup_s"} <= set(line["metrics"])
    if trace:
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert {"forward_device_ms.serve", "ttft_p50_ms.serve",
                "ttft_p90_ms.serve"} <= set(line["metrics"])
        assert line["breakdown"]["idle_gaps"]
    else:
        assert not any(n.endswith(".serve") for n in line["metrics"])
        assert "busy_s" not in line["device"]
