"""JaxConfig + JaxBackend — the north-star backend the reference lacks
(SURVEY §2.4 Train row: "a JaxTrainer is absent — the north star adds it as
a sibling of _TorchBackend calling jax.distributed.initialize";
reference structure: python/ray/train/torch/config.py:47-132).

Setup per worker:

1. Rank-0 publishes a coordinator address; every worker gets it plus its
   (process_id, num_processes) — the ``jax.distributed.initialize``
   rendezvous triple, mirroring the torch backend's TCP store rendezvous.
2. With ``use_jax_distributed=True`` (real multi-host TPU), workers call
   ``jax.distributed.initialize`` so the slice forms ONE global device mesh
   and all gradient traffic lowers to XLA collectives over ICI — no
   host-side allreduce exists at all.
3. Otherwise (CPU tests, single-host), each worker keeps its local devices
   and a host-level collective group ("train_default", DCN-analog) provides
   cross-worker psum for the DDP-style path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ray_tpu.train._internal.backend_executor import Backend, WorkerGroup


@dataclasses.dataclass
class JaxConfig:
    use_jax_distributed: bool = False
    collective_backend: str = "cpu"  # host-fallback group backend
    group_name: str = "train_default"
    # Platform overrides for the worker processes. On a real pod slice all
    # three stay None (the TPU runtime discovers its own topology); tests
    # form a genuine multi-process global mesh out of CPU devices the way
    # jax's own multiprocess CPU tests do: pin the platform, give each
    # process `num_local_devices` devices, and let gloo carry the
    # cross-process collectives.
    jax_platform: Optional[str] = None          # e.g. "cpu" in tests
    num_local_devices: Optional[int] = None     # devices per worker process
    cpu_collectives: Optional[str] = None       # e.g. "gloo"

    @property
    def backend_cls(self):
        return JaxBackend


def _setup_worker(rank: int, world_size: int, coordinator: str,
                  cfg_wire: dict) -> None:
    """A train worker's part of the start-up book
    (``events.startup_stats()``): the whole of it is ``startup.construct``,
    and where the workers form one jax mesh, the import and the rendezvous
    in which the device's client comes up are ``startup.import_jax`` and
    ``startup.devices``."""
    from ray_tpu._private import events

    with events.startup_span("construct", {"rank": rank}):
        _setup_worker_body(rank, world_size, coordinator, cfg_wire)


def _setup_worker_body(rank: int, world_size: int, coordinator: str,
                       cfg_wire: dict) -> None:
    import os

    from ray_tpu._private import compile_cache, events

    os.environ["RAY_TPU_TRAIN_RANK"] = str(rank)
    os.environ["RAY_TPU_TRAIN_WORLD_SIZE"] = str(world_size)
    os.environ["RAY_TPU_TRAIN_COORDINATOR"] = coordinator
    if cfg_wire["use_jax_distributed"]:
        events.time_first_import("jax", "import_jax")
        import jax

        compile_cache.watch_compiles()
        # Order matters: platform/device-count/collectives config must land
        # before the first backend touch, and a worker process recycled from
        # a previous group incarnation must drop its old coordination-service
        # connection before re-forming the mesh.
        if cfg_wire.get("jax_platform"):
            jax.config.update("jax_platforms", cfg_wire["jax_platform"])
        if cfg_wire.get("num_local_devices"):
            jax.config.update("jax_num_cpu_devices",
                              cfg_wire["num_local_devices"])
        if cfg_wire.get("cpu_collectives"):
            jax.config.update("jax_cpu_collectives_implementation",
                              cfg_wire["cpu_collectives"])
        from jax._src import distributed as _jax_dist

        if _jax_dist.global_state.client is not None:
            jax.distributed.shutdown()
        # Bounded rendezvous (the rc-124 hang class): a peer dying between
        # actor creation and its initialize() call used to park everyone
        # else on the coordination-service barrier forever. The timeout
        # turns that into a typed, retryable failure.
        with events.startup_span("devices", {"processes": world_size}):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size,
                process_id=rank,
                initialization_timeout=int(
                    cfg_wire.get("rendezvous_timeout_s") or 300),
            )
            local_devices = jax.local_device_count()
        expected = cfg_wire.get("num_local_devices")
        if expected and local_devices != expected:
            raise RuntimeError(
                f"worker {rank}: wanted {expected} local devices, got "
                f"{local_devices} — platform config landed too "
                "late (backend already initialized in this process)")
    if world_size > 1:
        from ray_tpu.util import collective as col

        col.init_collective_group(
            world_size, rank, backend=cfg_wire["collective_backend"],
            group_name=cfg_wire["group_name"],
            store_key=cfg_wire["store_key"])


class JaxBackend(Backend):
    def __init__(self):
        self._store_key: Optional[str] = None

    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        """Form the collective group with a bounded, retrying rendezvous.

        Two historical failure classes die here: (1) the free-port race —
        the port rank-0 probed can be rebound by another process before
        ``jax.distributed.initialize`` binds it, so each attempt probes a
        FRESH port instead of failing the whole start; (2) the rc-124
        hang — a peer dying mid-rendezvous parked everyone on the
        coordination barrier forever, so every attempt is bounded by
        ``train_rendezvous_timeout_s`` and peer death surfaces as a typed
        (restartable) :class:`TrainingWorkerError`. Attempts pace with
        decorrelated jitter; exhaustion raises
        :class:`TrainRendezvousError`.
        """
        import time as _time
        import uuid

        import ray_tpu
        from ray_tpu._private.async_util import DecorrelatedJitterBackoff
        from ray_tpu._private.config import CONFIG
        from ray_tpu.exceptions import (
            ActorUnavailableError, GetTimeoutError, NodeDiedError,
            RayActorError, TrainingWorkerError, TrainRendezvousError,
            WorkerCrashedError)
        from ray_tpu.train._internal.util import find_free_port

        metas = worker_group.node_metas()
        timeout_s = float(CONFIG.train_rendezvous_timeout_s)
        attempts = max(1, int(CONFIG.train_rendezvous_max_retries))
        backoff = DecorrelatedJitterBackoff(base_s=0.2, cap_s=2.0)
        coordinator = ""
        last: Optional[BaseException] = None
        for attempt in range(1, attempts + 1):
            port = worker_group.execute_single(0, find_free_port)
            coordinator = f"{metas[0]['hostname']}:{port}"
            cfg_wire = {
                "use_jax_distributed": backend_config.use_jax_distributed,
                "collective_backend": backend_config.collective_backend,
                "group_name": backend_config.group_name,
                "jax_platform": backend_config.jax_platform,
                "num_local_devices": backend_config.num_local_devices,
                "cpu_collectives": backend_config.cpu_collectives,
                "rendezvous_timeout_s": timeout_s,
                # per-incarnation store: a restarted group must not inherit
                # a dead predecessor's staged contributions
                "store_key":
                    f"{backend_config.group_name}:{uuid.uuid4().hex[:8]}",
            }
            self._store_key = cfg_wire["store_key"]
            try:
                ray_tpu.get([
                    w.execute.remote(_setup_worker, i, len(worker_group),
                                     coordinator, cfg_wire)
                    for i, w in enumerate(worker_group.workers)
                ], timeout=timeout_s + 30.0)
                return
            except (RayActorError, ActorUnavailableError, WorkerCrashedError,
                    NodeDiedError) as e:
                # a peer died mid-rendezvous: no point retrying at this
                # world size — hand the typed error to the recovery loop
                ctx = getattr(e, "context", None)
                self._cleanup_partial(worker_group,
                                      backend_config.group_name)
                raise TrainingWorkerError(
                    node_id=getattr(ctx, "node_id", ""),
                    incarnation=getattr(ctx, "incarnation", 0),
                    reason="peer died during rendezvous",
                    timeline=getattr(ctx, "timeline", None)) from e
            except GetTimeoutError as e:
                last = e
                self._cleanup_partial(worker_group,
                                      backend_config.group_name)
            except Exception as e:  # bind race, stale client, task error
                last = e
                self._cleanup_partial(worker_group,
                                      backend_config.group_name)
            if attempt < attempts:
                _time.sleep(backoff.next_delay())
        raise TrainRendezvousError(
            coordinator=coordinator, attempts=attempts,
            reason=str(last)[:300] if last else "unknown") from last

    def _cleanup_partial(self, worker_group: WorkerGroup,
                         group_name: str = "train_default") -> None:
        """Best-effort teardown of a half-formed incarnation so the next
        attempt starts clean: drop worker-side jax clients / group state,
        kill the staging store actor (unblocks peers parked on it)."""
        def reset(group_name: str):
            try:
                from ray_tpu.util import collective as col

                col.destroy_collective_group(group_name)
            except Exception:
                pass
            try:
                from jax._src import distributed as _jax_dist

                if _jax_dist.global_state.client is not None:
                    import jax

                    jax.distributed.shutdown()
            except Exception:
                pass

        import ray_tpu

        if self._store_key:
            try:
                ray_tpu.kill(ray_tpu.get_actor(
                    f"_collective_store:{self._store_key}"))
            except Exception:
                pass
        try:
            ray_tpu.get(
                [w.execute.remote(reset, group_name)
                 for w in worker_group.workers],
                timeout=10.0)
        except Exception:
            pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        def teardown(group_name: str):
            try:
                from ray_tpu.util import collective as col

                col.destroy_collective_group(group_name)
            except Exception:
                pass
            try:
                from jax._src import distributed as _jax_dist

                if _jax_dist.global_state.client is not None:
                    import jax

                    jax.distributed.shutdown()
            except Exception:
                pass

        import ray_tpu as _ray

        try:
            # BOUNDED: a worker wedged in a collective with a dead peer
            # only unblocks at jax's coordination heartbeat timeout
            # (~100s); waiting for it delays the elastic restart past the
            # next incarnation's actor-creation deadline. The group is
            # being torn down anyway — force-kill is the backstop.
            _ray.get([w.execute.remote(teardown, backend_config.group_name)
                      for w in worker_group.workers], timeout=10.0)
        except Exception:
            pass
        # Driver-side backstop: dead workers can't deregister, which would
        # strand the detached store actor of this incarnation forever.
        if self._store_key:
            import ray_tpu

            try:
                ray_tpu.kill(
                    ray_tpu.get_actor(f"_collective_store:{self._store_key}"))
            except Exception:
                pass
