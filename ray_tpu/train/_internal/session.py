"""Worker-side training session (reference:
python/ray/train/_internal/session.py — report :394/:654, world-rank
accessors). One ``_TrainSession`` lives per train-worker process; the user
loop talks to it through ``ray_tpu.train.report`` / ``get_context``."""

from __future__ import annotations

import os
import queue
import shutil
import threading
import time
from typing import Any, Dict, Optional

from ray_tpu.train._checkpoint import Checkpoint, InStoreCheckpoint

_session_lock = threading.Lock()
_session: Optional["_TrainSession"] = None


class TrainingResult:
    REPORT = "report"
    DONE = "done"
    ERROR = "error"

    def __init__(self, kind: str, metrics: Optional[Dict] = None,
                 checkpoint_dir: Optional[str] = None,
                 error: Optional[str] = None,
                 shard_ref: Optional[Any] = None,
                 shard_step: Optional[int] = None,
                 shard_nbytes: int = 0):
        self.kind = kind
        self.metrics = metrics or {}
        self.checkpoint_dir = checkpoint_dir
        self.error = error
        # in-store checkpoint shard: the ObjectRef of this rank's packed
        # state at `shard_step` (rides the wire dict — refs serialize
        # through actor returns via the borrow protocol)
        self.shard_ref = shard_ref
        self.shard_step = shard_step
        self.shard_nbytes = int(shard_nbytes or 0)

    def to_wire(self) -> Dict:
        return {"kind": self.kind, "metrics": self.metrics,
                "checkpoint_dir": self.checkpoint_dir, "error": self.error,
                "shard_ref": self.shard_ref, "shard_step": self.shard_step,
                "shard_nbytes": self.shard_nbytes}

    @classmethod
    def from_wire(cls, d: Dict) -> "TrainingResult":
        return cls(d["kind"], d.get("metrics"), d.get("checkpoint_dir"),
                   d.get("error"), d.get("shard_ref"), d.get("shard_step"),
                   d.get("shard_nbytes") or 0)


class _TrainSession:
    def __init__(self, world_rank: int, world_size: int, local_rank: int,
                 local_world_size: int, node_rank: int,
                 experiment_name: str, storage_path: str,
                 trial_dir: str, config: Dict,
                 checkpoint: Optional[Checkpoint] = None,
                 dataset_shards: Optional[Dict[str, Any]] = None,
                 checkpoint_shards: Optional[Dict] = None,
                 start_iteration: int = 0):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.local_world_size = local_world_size
        self.node_rank = node_rank
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self.trial_dir = trial_dir
        self.config = config
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        # in-store resume manifest from the driver's CheckpointManager:
        # {"step": int, "world_size": int, "shards": {rank: ObjectRef}}.
        # The shard is pulled lazily on the first get_checkpoint() call so
        # N restarted workers hit the broadcast-tree pull path together.
        self.checkpoint_shards = checkpoint_shards
        self.result_queue: "queue.Queue[TrainingResult]" = queue.Queue()
        self.iteration = int(start_iteration)
        # Shard-ref keepalive: a put object's ownership record dies with
        # its last local ref, and the driver's AddBorrow registration for
        # a ref riding a return value is asynchronous — dropping our
        # handle at report time would free the shard before the driver
        # re-owns it. Held here until the driver acks (re-owned + pinned)
        # through get_next(release_upto=step).
        self._shard_refs: Dict[int, Any] = {}

    def report(self, metrics: Dict, checkpoint: Optional[Checkpoint] = None):
        ckpt_dir = None
        shard_ref = None
        shard_step = None
        shard_nbytes = 0
        if checkpoint is not None:
            if isinstance(checkpoint, InStoreCheckpoint):
                # store-only: one zero-copy put of the packed shard; the
                # driver re-owns + pins it in CheckpointManager. Nothing
                # touches disk on this path.
                import ray_tpu

                shard_ref = ray_tpu.put(checkpoint.buffer)
                shard_step = self.iteration
                shard_nbytes = len(memoryview(checkpoint.buffer).cast("B"))
                self._shard_refs[shard_step] = shard_ref
            else:
                ckpt_dir = self._persist_to_trial_dir(checkpoint)
                # disk checkpoints also get an in-store shard so a
                # restart can restore without disk reads
                import ray_tpu
                from ray_tpu.train._internal.util import pack_dir

                buf = pack_dir(checkpoint.path)
                shard_ref = ray_tpu.put(buf)
                shard_step = self.iteration
                shard_nbytes = len(memoryview(buf).cast("B"))
                self._shard_refs[shard_step] = shard_ref
        self.iteration += 1
        self.result_queue.put(
            TrainingResult(TrainingResult.REPORT, metrics, ckpt_dir,
                           shard_ref=shard_ref, shard_step=shard_step,
                           shard_nbytes=shard_nbytes))

    def _persist_to_trial_dir(self, checkpoint: Checkpoint) -> str:
        # Persist into the trial dir (StorageContext analog: reference
        # train/_internal/storage.py:99-111). Only rank 0 uploads in
        # the common fully-replicated case; other ranks may still pass
        # shard checkpoints which land in per-rank subdirs. When the
        # trial dir is a remote URI, THIS worker process uploads its
        # own shards directly (upload-from-worker: on a pod each host
        # pushes to the bucket; nothing round-trips the driver).
        from ray_tpu._private.storage import (
            get_storage_backend, is_remote_uri, join_uri)

        name = f"checkpoint_{self.iteration:06d}"
        if is_remote_uri(self.trial_dir):
            sub = [] if self.world_rank == 0 \
                else [f"rank_{self.world_rank}"]
            dest = join_uri(self.trial_dir, name, *sub)
            get_storage_backend(dest).upload_dir(checkpoint.path, dest)
            return join_uri(self.trial_dir, name)
        if self.world_rank == 0:
            dest = os.path.join(self.trial_dir, name)
        else:
            dest = os.path.join(self.trial_dir, name,
                                f"rank_{self.world_rank}")
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        if os.path.abspath(checkpoint.path) != os.path.abspath(dest):
            shutil.copytree(checkpoint.path, dest, dirs_exist_ok=True)
        return os.path.join(self.trial_dir, name)

    def release_shards(self, upto_step: int) -> None:
        """Driver ack: shards up to ``upto_step`` have been re-owned and
        pinned driver-side; this worker's copies may be reclaimed."""
        for step in [s for s in self._shard_refs if s <= upto_step]:
            del self._shard_refs[step]

    def drop_object_refs(self) -> None:
        """Release every store ref the session holds — keepalive shards,
        the restore manifest, the memoized restored checkpoint. Called
        when the train fn ends, WHILE the actor's owner connections are
        still up: a borrowed ref's RemoveBorrow rides ObjectRef GC, and
        an actor killed before GC runs would leave the driver's borrow
        count stuck forever (the owned shard bytes would never free)."""
        import gc

        self._shard_refs.clear()
        self.checkpoint_shards = None
        self.loaded_checkpoint = None
        gc.collect()

    def get_checkpoint(self) -> Optional[Checkpoint]:
        if self.checkpoint_shards:
            ckpt = self._restore_in_store()
            if ckpt is not None:
                return ckpt
        return self.loaded_checkpoint

    def _restore_in_store(self) -> Optional[Checkpoint]:
        """Pull this rank's shard from the in-store manifest (broadcast
        tree forms automatically when every restarted rank pulls the same
        large object). Falls back to rank-0's shard when this rank is new
        (elastic grow) or its old shard is missing — the replicated-state
        contract: rank 0's shard must be loadable by any rank."""
        import ray_tpu
        from ray_tpu._private.events import REC

        manifest = self.checkpoint_shards
        shards = {int(k): v
                  for k, v in (manifest.get("shards") or {}).items()}
        ref = shards.get(self.world_rank, shards.get(0))
        if ref is None:
            return None
        t0 = time.time()
        sampled = REC.sample()
        try:
            buf = ray_tpu.get(ref)
        except Exception:
            # shard lost (owner died with the old driver, store eviction
            # raced the pin): fall back to any disk checkpoint
            return None
        ckpt = InStoreCheckpoint(buf, ref=ref,
                                 step=int(manifest.get("step") or 0))
        if sampled:
            tid, sid = REC.new_trace()
            REC.record("train_resume::restore", "train", t0,
                       time.time() - t0, tid, sid,
                       extra={"rank": self.world_rank, "step": ckpt.step,
                              "nbytes": len(memoryview(buf).cast("B"))})
        # memoize: repeated get_checkpoint() calls in the loop must not
        # re-pull; the first pull already landed in the local store
        self.loaded_checkpoint = ckpt
        self.checkpoint_shards = None
        return ckpt

    def get_dataset_shard(self, name: str = "train"):
        shard = self.dataset_shards.get(name)
        if shard is None:
            raise KeyError(f"no dataset shard named {name!r}")
        return shard


class TrainContext:
    """What ``ray_tpu.train.get_context()`` returns inside a worker
    (reference: ray.train.get_context TrainContext)."""

    def get_world_rank(self) -> int:
        return get_session().world_rank

    def get_world_size(self) -> int:
        return get_session().world_size

    def get_local_rank(self) -> int:
        return get_session().local_rank

    def get_local_world_size(self) -> int:
        return get_session().local_world_size

    def get_node_rank(self) -> int:
        return get_session().node_rank

    def get_experiment_name(self) -> str:
        return get_session().experiment_name

    def get_trial_dir(self) -> str:
        return get_session().trial_dir

    def get_storage(self):
        return get_session().storage_path


def init_session(**kwargs) -> _TrainSession:
    global _session
    with _session_lock:
        _session = _TrainSession(**kwargs)
        return _session


def get_session() -> _TrainSession:
    if _session is None:
        raise RuntimeError(
            "Not inside a ray_tpu.train session — this API must be called "
            "from within train_loop_per_worker")
    return _session


def shutdown_session() -> None:
    global _session
    with _session_lock:
        _session = None


def in_session() -> bool:
    return _session is not None
