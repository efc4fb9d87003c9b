"""Vectorized submission fast path (ISSUE 18; reference:
python/ray/_private/worker.py submit path + direct_task_transport.h).

Covers the contract of ``fn.map`` / ``Worker.submit_many`` /
``submit_actor_tasks_many``: ref identity and ordering, per-entry error
blast radius (one bad entry fails alone), spec-template cache
invalidation when a function is redefined (new function id — stale
templates can never serve the new body), cache cap eviction, knob-off
parity (the legacy per-call path produces identical results through the
same API), ownership/lineage bookkeeping parity with the single-call
path (PR 17), full lineage RECONSTRUCTION of batched submissions after
a node kill, kill -9 mid-batch (typed per-entry errors, no hang), and
the one-root-span-per-batch trace shape (satellite of ISSUE 18).

One module-scoped cluster head; the reconstruction test brings its own
side node keyed by a unique resource (idiom from test_lineage).
"""

import os
import signal
import time
from itertools import repeat

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import events as _ev
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.task_spec import (NORMAL_TASK, SpecTemplate, TaskSpec)
from ray_tpu._private.worker import _replay_seed
from ray_tpu.cluster_utils import Cluster
from ray_tpu.exceptions import RayTaskError, WorkerCrashedError
from ray_tpu._private.object_ref import ObjectRef


# ---------------------------------------------------------------------------
# spec-template units (no cluster)
# ---------------------------------------------------------------------------
def test_spec_template_lazy_instantiate():
    """instantiate() splices per-call fields into a copy of the frozen
    base wire dict; slots fill lazily on first read and to_wire() hands
    back the spliced dict without rebuilding."""
    tpl = SpecTemplate(
        job_id=b"j" * 4, task_type=NORMAL_TASK, function_id=b"f" * 16,
        function_name="t", num_returns=2, resources={"CPU": 1.0},
        owner_addr={"h": 1}, max_retries=3)
    spec = tpl.instantiate(b"t1" * 8, [("v", b"a")], {}, trace_ctx=None,
                           replay_seed=7)
    assert spec.task_id == b"t1" * 8
    assert spec.function_name == "t"
    assert spec.num_returns == 2
    assert spec.max_retries == 3
    assert spec.replay_seed == 7
    # omitted invariants fall to wire defaults, not AttributeError
    assert spec.seq == 0 and spec.actor_method == ""
    w = spec.to_wire()
    assert w["task_id"] == b"t1" * 8 and w["args"] == [("v", b"a")]
    # the template's base never absorbs per-call fields
    assert tpl.base["task_id"] is None
    # sched_key precomputed once matches the spec's own
    assert tpl.sched_key == spec.scheduling_key()


def test_spec_template_seq_splice():
    tpl = SpecTemplate(
        job_id=b"j" * 4, task_type=NORMAL_TASK, function_id=b"f" * 16,
        function_name="t", num_returns=1, resources={}, owner_addr={})
    assert tpl.instantiate(b"a" * 16, [], {}, seq=5).seq == 5
    assert tpl.instantiate(b"b" * 16, [], {}).seq == 0


# ---------------------------------------------------------------------------
# cluster tests: one module-scoped head, per-test side nodes
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fastpath_cluster():
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    ray_tpu.init(_node=cluster.head_node)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


def test_map_ref_identity_and_ordering(fastpath_cluster):
    """One map call yields one distinct, immediately-usable ObjectRef
    per item, results land in argument order, and every return id is
    registered with the owner (parity with per-call submission)."""
    @ray_tpu.remote
    def square(i):
        return i * i

    refs = square.map(range(40))
    assert len(refs) == 40
    assert all(isinstance(r, ObjectRef) for r in refs)
    assert len({r.id().binary() for r in refs}) == 40
    w = worker_mod.global_worker
    for r in refs:
        assert r.id().binary() in w.reference_counter._owned
    assert ray_tpu.get(refs, timeout=120) == [i * i for i in range(40)]

    @ray_tpu.remote(num_returns=2)
    def pair(i):
        return i, -i

    batches = pair.map(range(5))
    assert all(len(b) == 2 for b in batches)
    assert ray_tpu.get([b[1] for b in batches], timeout=120) == [
        0, -1, -2, -3, -4]


def test_map_zip_and_repeat_semantics(fastpath_cluster):
    """builtins.map/zip semantics: pairwise over iterables, stops at
    the shortest, constants ride itertools.repeat."""
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.map([1, 2, 3], [10, 20]), timeout=120) == [11, 22]
    assert ray_tpu.get(add.map(range(3), repeat(100)),
                       timeout=120) == [100, 101, 102]
    assert add.map() == []


def test_per_entry_error_blast_radius(fastpath_cluster):
    """A raising entry fails ONLY its own ref with the typed task
    error; every other entry in the same batch completes normally."""
    @ray_tpu.remote
    def picky(i):
        if i % 5 == 0:
            raise ValueError(f"bad {i}")
        return i

    refs = picky.map(range(20))
    ok, bad = [], []
    for i, r in enumerate(refs):
        try:
            ok.append((i, ray_tpu.get(r, timeout=120)))
        except (ValueError, RayTaskError):
            bad.append(i)
    assert bad == [0, 5, 10, 15]
    assert ok == [(i, i) for i in range(20) if i % 5]


def test_template_cache_invalidation_on_redefinition(fastpath_cluster):
    """Redefining a function produces a new function id, so the
    template cache keys the new body separately — stale templates can
    never serve it (the cache key embeds the fid)."""
    w = worker_mod.global_worker

    def make(bias):
        @ray_tpu.remote
        def biased(i):
            return i + bias

        return biased

    f1 = make(100)
    assert ray_tpu.get(f1.map(range(3)), timeout=120) == [100, 101, 102]
    n_templates = len(w._spec_templates)
    # same source, different closure constant => different blob/fid
    f2 = make(500)
    assert ray_tpu.get(f2.map(range(3)), timeout=120) == [500, 501, 502]
    assert len(w._spec_templates) > n_templates
    # the original is still live and still correct after the redefine
    assert ray_tpu.get(f1.map(range(3)), timeout=120) == [100, 101, 102]


def test_template_cache_cap_eviction(fastpath_cluster, monkeypatch):
    """The cache clears on hitting spec_template_cache_max instead of
    growing without bound (one dict per (fn, options) signature)."""
    monkeypatch.setenv("RAY_TPU_SPEC_TEMPLATE_CACHE_MAX", "4")
    w = worker_mod.global_worker

    @ray_tpu.remote
    def fid(i):
        return i

    # distinct options signatures => distinct template keys
    for k in range(10):
        assert ray_tpu.get(
            fid.options(name=f"sig{k}").map([k]), timeout=120) == [k]
        assert len(w._spec_templates) <= 4


def test_batched_ownership_and_lineage_bookkeeping(fastpath_cluster):
    """Batched submissions get the SAME owner-side bookkeeping as
    per-call ones (PR 17 parity): owned metadata with a task: creator,
    a replay_seed that is the pure function of the task id, and a
    lineage-ledger retention for retriable plasma-return tasks."""
    w = worker_mod.global_worker

    @ray_tpu.remote(max_retries=2)
    def big(i):
        return np.full(200_000, i, np.int64)  # plasma-sized

    refs = big.map(range(3))
    vals = ray_tpu.get(refs, timeout=120)
    assert [int(v[0]) for v in vals] == [0, 1, 2]
    for r in refs:
        meta = w.reference_counter._owned.get(r.id().binary())
        assert meta is not None
        assert meta.creator.startswith("task:")
        tid = r.id().task_id().binary()
        rec = w._tasks.get(tid)
        assert rec is not None, "retriable batched task must stay replayable"
        assert rec.spec.replay_seed == _replay_seed(tid)
        assert rec.spec.max_retries == 2
    del refs, vals


def _kill_and_replace(cluster, node, res_key):
    cluster.remove_node(node)
    replacement = cluster.add_node(num_cpus=2, resources={res_key: 2})
    cluster.wait_for_nodes()
    time.sleep(2.5)  # node-death detection lag (~2s health check)
    return replacement


def test_lineage_reconstruction_of_batched_submissions(fastpath_cluster):
    """Kill the node holding every return of a BATCHED submission:
    the owner replays each lost task under its original id and seed,
    reconstructing byte-identical values (acceptance: lineage
    reconstruction works for batched submissions)."""
    cluster = fastpath_cluster
    node = cluster.add_node(num_cpus=2, resources={"fp_lin": 2})
    cluster.wait_for_nodes()

    @ray_tpu.remote(max_retries=2, resources={"fp_lin": 1})
    def noisy(i):
        import random

        arr = np.zeros(200_000)
        arr[:64] = [random.random() for _ in range(64)]
        return arr + i

    @ray_tpu.remote(max_retries=2, resources={"fp_lin": 1})
    def sha(x):
        import hashlib

        return hashlib.sha256(x.tobytes()).hexdigest()

    refs = noisy.map(range(3))
    # hash on the SAME node: a driver get() would pull head-side
    # replicas and the kill below would lose nothing (test_lineage idiom)
    before_hashes = ray_tpu.get(sha.map(refs), timeout=180)
    w = worker_mod.global_worker
    before = w._lineage.reconstructions
    _kill_and_replace(cluster, node, "fp_lin")
    import hashlib

    after_vals = ray_tpu.get(refs, timeout=180)
    after_hashes = [hashlib.sha256(v.tobytes()).hexdigest()
                    for v in after_vals]
    assert after_hashes == before_hashes  # replay_seed => exact RNG replay
    assert w._lineage.reconstructions >= before + 3
    del refs, after_vals


def test_kill9_mid_batch_typed_errors_no_hang(fastpath_cluster, tmp_path):
    """SIGKILL a worker while a batch is in flight: entries on the dead
    worker fail with the typed WorkerCrashedError, entries elsewhere
    complete, and every get returns promptly — no hung futures."""
    gate = str(tmp_path)

    @ray_tpu.remote(max_retries=0)
    def stall(i, d):
        with open(os.path.join(d, f"{os.getpid()}.{i}.pid"), "w") as f:
            f.write(str(i))
        while not os.path.exists(os.path.join(d, "go")):
            time.sleep(0.05)
        return i

    refs = stall.map(range(4), repeat(gate))
    deadline = time.monotonic() + 60
    pids = set()
    while time.monotonic() < deadline:
        pids = {int(p.split(".")[0]) for p in os.listdir(gate)
                if p.endswith(".pid")}
        if pids:
            break
        time.sleep(0.05)
    assert pids, "no batch entry started within 60s"
    os.kill(sorted(pids)[0], signal.SIGKILL)
    time.sleep(0.3)
    with open(os.path.join(gate, "go"), "w") as f:
        f.write("1")

    t0 = time.monotonic()
    outcomes = []
    for i, r in enumerate(refs):
        try:
            outcomes.append(("ok", ray_tpu.get(r, timeout=90)))
        except WorkerCrashedError:
            outcomes.append(("crash", i))
        except RayTaskError as e:  # wrapped crash riding the reply path
            assert "died" in str(e).lower() or "crash" in str(e).lower()
            outcomes.append(("crash", i))
    assert time.monotonic() - t0 < 95, "mid-batch kill must not hang gets"
    crashes = [o for o in outcomes if o[0] == "crash"]
    assert crashes, "killing an executing worker must fail its entries"
    for kind, val in outcomes:
        if kind == "ok":
            assert outcomes[val] is not None  # value equals its index
    oks = [val for kind, val in outcomes if kind == "ok"]
    assert oks == [i for i in range(4)
                   if ("crash", i) not in outcomes]


def test_one_root_span_per_batch(fastpath_cluster):
    """With tracing armed, a batch records ONE submit_batch:: root span
    carrying the entry count instead of N per-task roots (satellite of
    ISSUE 18: keep trace volume proportional to batches, not entries)."""
    w = worker_mod.global_worker

    @ray_tpu.remote
    def traced(i):
        return i

    armed = _ev.configure(w.session_dir or "/tmp", w.mode, sample_rate=1.0)
    assert armed
    try:
        assert ray_tpu.get(traced.map(range(16)), timeout=120) == list(
            range(16))
        # read_ring reads the driver's mmap ring directly; no head-side
        # flush needed (and _maybe_flush_spans is loop-thread-only).
        info = _ev.read_ring(_ev.REC.path)
    finally:
        _ev.REC.enabled = False
    batch_roots = [s for s in info["spans"]
                   if s["name"].startswith("submit_batch::")
                   and s["name"].endswith("traced")]
    assert len(batch_roots) == 1
    assert batch_roots[0]["extra"] == {"count": 16}
    per_task_roots = [s for s in info["spans"]
                      if s["name"].startswith("task::")
                      and s["name"].endswith("traced")]
    assert not per_task_roots
