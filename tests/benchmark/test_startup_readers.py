"""The six metrics that read a serving replica's start-up from inside
(PR 57): `startup_runtime_s.serve`, `startup_backend_s.serve`,
`startup_weights_s.serve`, `startup_trace_lower_s.serve`,
`startup_backend_compile_s.serve` and `startup_cache_hit_pct.serve`.

The readers on hand-made views, the manifest's entries against the metric
files, and the driver's own run over one serving cell's replica in this
process (no cluster), whose observations the readers turn into numbers.
"""

import time
import types

import pytest

from benchmark.drivers import serve as serve_driver
from benchmark.harness import lastline, loader
from benchmark.readers import startup_serve as readers

NAMES = {"startup_runtime_s.serve": readers.runtime_s,
         "startup_backend_s.serve": readers.backend_s,
         "startup_weights_s.serve": readers.weights_s,
         "startup_trace_lower_s.serve": readers.trace_lower_s,
         "startup_backend_compile_s.serve": readers.backend_compile_s,
         "startup_cache_hit_pct.serve": readers.cache_hit_pct}
# a replica that was RUNNING 20 s after `serve.run`: 4 s before its class was
# entered, 12.5 s of jax and the chip's client inside the 16 s of its
# constructor; 60 programs, 12 of the 48 that asked the cache found there
STARTUP = {"at": {}, "startup.boot": 0.5, "startup.chip_bind": 0.001,
           "startup.construct": 16.0, "startup.import_jax": 3.0,
           "startup.devices": 9.5, "startup.weights": 2.0,
           "weights_ready_s": 23.25, "startup.warm": 31.0,
           "warm_s": {256: 11.0, 512: 20.0}, "actor_start": "fork"}
COMPILES = {"programs": 60, "trace_s": 7.5, "lower_s": 5.0,
            "backend_s": 40.5, "cache_requests": 48, "cache_hits": 12,
            "cache_misses": 9, "cache_retrieval_s": 1.5,
            "cache_saved_s": 80.0,
            "slowest": [["jit(step_fn)", 3.0, 12.0], ["jit(<lambda>)", 1.0, 9.0],
                        ["jit(step_fn)", 2.5, 7.0], ["jit(step_fn)", 2.0, 6.0],
                        ["jit(head_fn)", 0.5, 1.0], ["jit(iota)", 0.1, 0.2]]}
STATS = {"steps": 300, "emitted": 520, "startup": STARTUP,
         "compiles": COMPILES}
WANT = {"startup_runtime_s.serve": 4.0, "startup_backend_s.serve": 12.5,
        "startup_weights_s.serve": 23.25,
        "startup_trace_lower_s.serve": 12.5,
        "startup_backend_compile_s.serve": 40.5,
        "startup_cache_hit_pct.serve": 25.0}


def view_of(stats, replica_ready_s=20.0):
    return {"obs": {"engine_stats_end": stats, "window_s": 40.0,
                    "replica_ready_s": replica_ready_s}}


def metric_file(name):
    found = [m for m in loader.load_metric_files() if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


def serving_cells(manifest):
    return [w["name"] for w in manifest["workloads"]
            if loader.load_cell(w["name"])["kind"] == "serve"]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_reader_is_the_arithmetic_its_file_states(name):
    metric = metric_file(name)
    assert loader.load_reader(metric) is NAMES[name]
    assert loader.load_reader(metric)(view_of(STATS), metric) == WANT[name]


def test_the_slowest_five_are_named_on_standard_error(capsys):
    name = "startup_backend_compile_s.serve"
    NAMES[name](view_of(STATS), metric_file(name))
    said = capsys.readouterr().err
    assert name in said and "60 programs" in said
    assert "jit(step_fn) 3.00+12.00" in said and "jit(head_fn)" in said
    assert "jit(iota)" not in said, "five, and the book keeps sixteen"


def test_nothing_asked_the_cache_reads_zero_and_not_none():
    name = "startup_cache_hit_pct.serve"
    stats = {**STATS, "compiles": {**COMPILES, "cache_requests": 0,
                                   "cache_hits": 0}}
    assert NAMES[name](view_of(stats), metric_file(name)) == 0.0


@pytest.mark.parametrize("name,book,counter", [
    ("startup_runtime_s.serve", "startup", "startup.construct"),
    ("startup_backend_s.serve", "startup", "startup.import_jax"),
    ("startup_backend_s.serve", "startup", "startup.devices"),
    ("startup_weights_s.serve", "startup", "weights_ready_s"),
    ("startup_trace_lower_s.serve", "compiles", "trace_s"),
    ("startup_trace_lower_s.serve", "compiles", "lower_s"),
    ("startup_backend_compile_s.serve", "compiles", "backend_s"),
    ("startup_backend_compile_s.serve", "compiles", "slowest"),
    ("startup_cache_hit_pct.serve", "compiles", "cache_hits"),
    ("startup_cache_hit_pct.serve", "compiles", "cache_requests"),
])
def test_a_missing_counter_is_an_error_that_names_it(name, book, counter):
    stats = {**STATS, book: {k: v for k, v in STATS[book].items()
                             if k != counter}}
    with pytest.raises(KeyError) as err:
        NAMES[name](view_of(stats), metric_file(name))
    assert counter in str(err.value) and name in str(err.value)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_program_with_half_the_books_is_an_error_that_names_the_other(name):
    reads = "startup" if name in (
        "startup_runtime_s.serve", "startup_backend_s.serve",
        "startup_weights_s.serve") else "compiles"
    half = {k: v for k, v in STATS.items() if k != reads}
    with pytest.raises(KeyError) as err:
        NAMES[name](view_of(half), metric_file(name))
    assert repr(reads) in str(err.value) and name in str(err.value)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_program_that_predates_the_books_reads_not_measured(name, capsys):
    """The parent commit under these files: its `engine_stats()` has
    neither book. `lastline` refuses a traced line that leaves a listed
    metric out, so the reader gives a number no reading can be, and says
    why on standard error."""
    old = {k: v for k, v in STATS.items() if k not in readers.BOOKS}
    assert set(old) == {"steps", "emitted"}
    assert NAMES[name](view_of(old), metric_file(name)) == -1.0
    said = capsys.readouterr().err
    assert name in said and "predates" in said and "not measured" in said


def test_the_manifests_six_entries_agree_with_their_files(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    cells = serving_cells(manifest)
    assert len(cells) >= 8 and "train_l2_seq4k" not in cells
    for name in NAMES:
        entry, f = listed[name], metric_file(name)
        assert entry["workloads"] == cells
        assert f["kinds"] == ["serve"] and "cells" not in f
        assert entry["source"] == f["source"] == "program_counter"
        assert entry["layer"] == f["layer"] == "entry points"
        assert entry["moves"] == f["moves"] == "setup_s"
        assert (entry["unit"], entry["better"]) == (f["unit"], f["better"])
        assert f["reader"].startswith("startup_serve:")
        assert "-1.0" in f["what"] and "PR 57" in f["what"]
    assert {listed[n]["unit"] for n in NAMES} == {"s", "%"}
    hit = listed["startup_cache_hit_pct.serve"]
    assert (hit["unit"], hit["better"]) == ("%", "higher")
    assert {listed[n]["better"] for n in NAMES if n != hit["name"]} \
        == {"lower"}
    # what was there stays: this PR only adds
    assert listed["replica_ready_s.serve"]["moves"] == "setup_s"
    assert listed["fit_overhead_s.train"]["workloads"] == ["train_l2_seq4k"]


def test_a_traced_line_of_a_serving_cell_needs_the_six(manifest):
    for cell_name in serving_cells(manifest):
        assert set(NAMES) <= set(lastline.required_metrics(
            manifest, cell_name, True))
    assert not set(NAMES) & set(lastline.required_metrics(
        manifest, "train_l2_seq4k", True))
    cell_name = "serve_chat_steady"
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
    # the parent under these files: -1.0 is a number, and the line stands
    values.update(dict.fromkeys(NAMES, -1.0))
    line = lastline.build(manifest, cell_name, True, values=values,
                          device=device, correct=True, attempted=19,
                          failed=0)
    lastline.validate(line, manifest, cell_name, True)


# --------------------------------------------------------------------------
# the driver's own run over a replica in this process: what the readers make
# of a program's real books
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


def test_a_cells_run_gives_the_six_as_numbers(monkeypatch):
    import cloudpickle

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import events
    from ray_tpu.serve._private.replica import Replica

    cell = loader.load_cell("serve_chat_steady", rehearsal=True)
    family = loader.load_family(cell["model"])
    served = type("BenchServed", (serve_driver.BenchGenerator,
                                  family.Served), {})
    warm_before = dict(events.startup_stats().get("warm_s", {}))
    t = time.time()
    # as the replica actor makes it: the class goes in through `Replica`
    gen = Replica(cloudpickle.dumps(served), cloudpickle.dumps(((), dict(
        model=cell["model"], engine=cell["engine"], seed=3000000019,
        rehearsal=True))), "bench", "BenchGenerator", 16, None)._callable
    made_s = time.time() - t
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
    assert set(readers.BOOKS) <= set(end)
    assert end["step_compiles"] == 0, "the window met an unwarmed shape"
    # every bucket the driver warmed is in the book by its length
    warmed = {n: s - warm_before.get(n, 0.0)
              for n, s in end["startup"]["warm_s"].items()}
    assert {n for n, s in warmed.items() if s > 0} \
        == set(res["obs"]["warm_s"])
    for n, outside_s in res["obs"]["warm_s"].items():
        assert 0 < warmed[n] <= outside_s
    # `replica_ready_s` is the lambda's here: give the view the seconds the
    # replica took to make, which is what `serve.run` waits for
    view = {"obs": {**res["obs"], "replica_ready_s": made_s}, "cell": cell}
    got = {name: loader.load_reader(m)(view, m)
           for name in NAMES for m in [metric_file(name)]}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["startup_runtime_s.serve"] >= 0
    assert got["startup_backend_s.serve"] >= 0
    assert got["startup_weights_s.serve"] > 0
    assert got["startup_trace_lower_s.serve"] > 0
    assert got["startup_backend_compile_s.serve"] > 0
    assert 0 <= got["startup_cache_hit_pct.serve"] <= 100
