"""The four metrics that read the serving engine's own counters (PR 38):
`engine_host_ms.serve`, `engine_active_pct.serve`,
`engine_queue_wait_ms.serve` and `engine_queue_wait_max_ms.serve`.

The readers on hand-made views, the manifest's entries against the metric
files, and a run of the driver over each serving cell's replica in this
process (no cluster), whose observations the readers turn into numbers. The
walk of `run.py --rehearsal` itself starts a cluster in a subprocess and is
slow tier, as the other files' walks are.
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from benchmark.drivers import serve as serve_driver
from benchmark.harness import lastline, loader
from benchmark.readers import engine_counters_serve as readers

SERVING_CELLS = ("serve_chat_steady", "serve_olmoe_chat", "serve_lfm2_rag",
                 "serve_dsv2_docqa")
NAMES = {"engine_host_ms.serve": readers.host_ms,
         "engine_active_pct.serve": readers.active_pct,
         "engine_queue_wait_ms.serve": readers.queue_wait_ms,
         "engine_queue_wait_max_ms.serve": readers.queue_wait_max_ms}
# a window of 40 s: 300 steps in 30 s of iterations, 27 s of them the
# device's; 20 requests that waited 5 s in all, one of them 1.75 s
STATS = {"steps": 300, "emitted": 520, "completed": 20, "joined": 20,
         "active_s": 30.0, "step_device_s": 27.0, "queue_wait_s": 5.0,
         "queue_wait_max_s": 1.75}
WANT = {"engine_host_ms.serve": 10.0, "engine_active_pct.serve": 75.0,
        "engine_queue_wait_ms.serve": 250.0,
        "engine_queue_wait_max_ms.serve": 1750.0}


def view_of(stats, window_s=40.0):
    return {"obs": {"engine_stats_end": stats, "window_s": window_s}}


def metric_file(name):
    found = [m for m in loader.load_metric_files() if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_reader_is_the_arithmetic_its_file_states(name):
    metric = metric_file(name)
    assert loader.load_reader(metric) is NAMES[name]
    assert loader.load_reader(metric)(view_of(STATS), metric) == WANT[name]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_an_engine_that_served_nothing_reads_zero_and_not_none(name):
    nothing = {**dict.fromkeys(STATS, 0), "active_s": 0.0,
               "step_device_s": 0.0, "queue_wait_s": 0.0,
               "queue_wait_max_s": 0.0}
    assert NAMES[name](view_of(nothing), metric_file(name)) == 0.0


@pytest.mark.parametrize("name,counter", [
    ("engine_host_ms.serve", "step_device_s"),
    ("engine_host_ms.serve", "active_s"),
    ("engine_active_pct.serve", "active_s"),
    ("engine_queue_wait_ms.serve", "joined"),
    ("engine_queue_wait_ms.serve", "queue_wait_s"),
    ("engine_queue_wait_max_ms.serve", "queue_wait_max_s"),
])
def test_a_missing_counter_is_an_error_that_names_it(name, counter):
    stats = {k: v for k, v in STATS.items() if k != counter}
    with pytest.raises(KeyError) as err:
        NAMES[name](view_of(stats), metric_file(name))
    assert counter in str(err.value) and name in str(err.value)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_program_that_predates_the_counters_reads_not_measured(name,
                                                                 capsys):
    """The parent commit under these files: its `engine_stats()` has none
    of the counters. `lastline` refuses a traced line that leaves a listed
    metric out, so the reader gives a number no reading can be, and says
    why on standard error."""
    old = {k: v for k, v in STATS.items() if k not in readers.SINCE_PR_38}
    assert set(old) == {"steps", "emitted", "completed"}
    assert NAMES[name](view_of(old), metric_file(name)) == -1.0
    said = capsys.readouterr().err
    assert name in said and "predates" in said and "not measured" in said


def test_the_manifests_four_entries_agree_with_their_files(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-4:] == [
        "engine_host_ms.serve", "engine_active_pct.serve",
        "engine_queue_wait_ms.serve", "engine_queue_wait_max_ms.serve"]
    for name in NAMES:
        entry, f = listed[name], metric_file(name)
        assert entry["workloads"] == list(SERVING_CELLS)
        assert f["kinds"] == ["serve"] and "cells" not in f
        assert entry["source"] == f["source"] == "program_counter"
        assert entry["layer"] == f["layer"] == "serving engine"
        assert entry["better"] == f["better"] == "lower"
        assert (entry["unit"], entry["moves"]) == (f["unit"], f["moves"])
        assert "warm-up" in f["what"] and "-1.0" in f["what"]
    assert listed["engine_host_ms.serve"]["moves"] == "serve_gap_p95_ms"
    assert {listed[n]["moves"] for n in NAMES
            if n != "engine_host_ms.serve"} == {"serve_tokens_per_s"}
    assert listed["engine_active_pct.serve"]["unit"] == "%"


@pytest.mark.parametrize("cell_name", SERVING_CELLS)
def test_a_traced_line_of_a_serving_cell_needs_the_four(manifest, cell_name):
    """`lastline` asks every traced line of a serving cell for the four,
    takes them as numbers, and refuses the line without one."""
    assert set(NAMES) <= set(lastline.required_metrics(
        manifest, cell_name, True))
    assert not set(NAMES) & set(lastline.required_metrics(
        manifest, "train_l2_seq4k", True))
    values = {m["name"]: 1.5 for g in ("end_to_end", "per_layer")
              for m in manifest[g]}
    values.update(WANT)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 9_000_000_000, "window_s": 3.0,
              "busy_s": 1.25}
    line = lastline.build(manifest, cell_name, True, values=values,
                          device=device, correct=True, attempted=19,
                          failed=0)
    lastline.validate(line, manifest, cell_name, True)
    assert {n: line["metrics"][n]["value"] for n in NAMES} == WANT
    values["engine_host_ms.serve"] = None  # a reader that found nothing
    line = lastline.build(manifest, cell_name, True, values=values,
                          device=device, correct=True, attempted=19,
                          failed=0)
    with pytest.raises(lastline.LastLineError, match="engine_host_ms.serve"):
        lastline.validate(line, manifest, cell_name, True)


# --------------------------------------------------------------------------
# the driver's own run over a replica in this process: what the readers make
# of a program's real counters
# --------------------------------------------------------------------------
class LocalHandle:
    """What ``serve.run`` hands back, over a served object in this
    process."""

    def __init__(self, gen):
        self.gen = gen

    def options(self, stream=False):
        return types.SimpleNamespace(remote=self.gen)

    def __getattr__(self, method):
        def remote(*args):
            out = getattr(self.gen, method)(*args)
            return types.SimpleNamespace(result=lambda timeout_s=None: out)
        return types.SimpleNamespace(remote=remote)


@pytest.mark.parametrize("cell_name", SERVING_CELLS)
def test_a_cells_run_gives_the_four_as_numbers(cell_name, monkeypatch):
    import ray_tpu
    from ray_tpu import serve

    cell = loader.load_cell(cell_name, rehearsal=True)
    family = loader.load_family(cell["model"])
    gen = type("BenchServed", (serve_driver.BenchGenerator, family.Served),
               {})(model=cell["model"], engine=cell["engine"],
                   seed=3000000019, rehearsal=True)
    for module, name in ((ray_tpu, "init"), (ray_tpu, "shutdown"),
                         (serve, "shutdown")):
        monkeypatch.setattr(module, name, lambda *a, **k: None)
    monkeypatch.setattr(serve, "run", lambda app, **k: LocalHandle(gen))
    ctx = {"seed": 3000000029, "seconds": 3.0, "trace": False,
           "rehearsal": True, "say": lambda phase, **fields: None,
           "process_start_unix": time.time(),
           "traffic": loader.load_traffic(cell)}
    try:
        res = serve_driver.run(cell, ctx)
    finally:
        gen.engine.shutdown()
    assert res["correct"], res["problems"]
    end = res["obs"]["engine_stats_end"]
    assert set(readers.SINCE_PR_38) <= set(end)
    # the warm-up request, and the window's as far as its end saw them
    assert 2 <= end["joined"] <= res["attempted"] + 1
    assert 0 < end["step_device_s"] < end["active_s"]
    view = {"obs": res["obs"], "cell": cell}
    got = {name: loader.load_reader(m)(view, m)
           for name in NAMES for m in [metric_file(name)]}
    assert all(isinstance(v, float) for v in got.values()), got
    assert 0 < got["engine_host_ms.serve"] < 1e3 * max(
        res["obs"]["model_step_s"])
    assert got["engine_active_pct.serve"] > 0
    assert 0 <= got["engine_queue_wait_ms.serve"] \
        <= got["engine_queue_wait_max_ms.serve"]


# --------------------------------------------------------------------------
# run.py --rehearsal of every serving cell, in a process of its own
# --------------------------------------------------------------------------
@pytest.mark.slow  # a cluster in a subprocess, 20-40 s a cell
@pytest.mark.parametrize("cell_name", SERVING_CELLS)
def test_the_rehearsals_would_be_line_holds_the_four(repo_root, manifest,
                                                     cell_name):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONASYNCIODEBUG")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell_name,
         "--seed", "3000000019", "--seconds", "5", "--trace", "1",
         "--rehearsal"], cwd=repo_root, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-3000:])
    head = "[bench REHEARSAL] would-be last line: "
    found = [ln for ln in proc.stdout.splitlines() if ln.startswith(head)]
    assert len(found) == 1, proc.stdout[-2000:]
    line = json.loads(found[0][len(head):])
    lastline.validate(line, manifest, cell_name, True)
    values = {n: line["metrics"][n]["value"] for n in NAMES}
    assert all(v >= 0 for v in values.values()), values
    assert values["engine_host_ms.serve"] > 0
    # over 100 is possible here: `active_s` holds the warm-up request, which
    # at the CPU's pace is no small part of a window of 5 s
    assert values["engine_active_pct.serve"] > 0
