"""Accelerator detection against fake sysfs/dev trees (reference:
python/ray/tests/test_accelerators/* probe their managers the same way —
no real hardware, just the filesystem contract each driver exposes)."""

import os

import pytest

from ray_tpu._private.accelerators.other import (
    AMDGPUAcceleratorManager, HPUAcceleratorManager,
    IntelGPUAcceleratorManager, NeuronAcceleratorManager,
    NPUAcceleratorManager)


@pytest.fixture(autouse=True)
def clear_overrides(monkeypatch):
    for var in ("RAY_TPU_NUM_AMD_GPUS", "RAY_TPU_NUM_INTEL_GPUS",
                "RAY_TPU_NUM_NEURON_CORES", "RAY_TPU_NUM_HPUS",
                "RAY_TPU_NUM_NPUS"):
        monkeypatch.delenv(var, raising=False)


def test_amd_counts_only_gpu_nodes(tmp_path, monkeypatch):
    nodes = tmp_path / "class/kfd/kfd/topology/nodes"
    for i, gpu_id in enumerate(["0", "1234", "777"]):  # node 0 is the CPU
        d = nodes / str(i)
        d.mkdir(parents=True)
        (d / "gpu_id").write_text(gpu_id + "\n")
    monkeypatch.setattr(AMDGPUAcceleratorManager, "SYS_ROOT",
                        str(tmp_path))
    assert AMDGPUAcceleratorManager.get_current_node_num_accelerators() == 2


def test_intel_matches_vendor(tmp_path, monkeypatch):
    for name, vendor in [("renderD128", "0x8086"), ("renderD129", "0x10de"),
                         ("renderD130", "0x8086")]:
        d = tmp_path / "class/drm" / name / "device"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
    monkeypatch.setattr(IntelGPUAcceleratorManager, "SYS_ROOT",
                        str(tmp_path))
    assert IntelGPUAcceleratorManager.\
        get_current_node_num_accelerators() == 2


def test_neuron_two_cores_per_device(tmp_path, monkeypatch):
    for name in ("neuron0", "neuron1", "neuron_monitor"):  # last not a dev
        (tmp_path / name).touch()
    monkeypatch.setattr(NeuronAcceleratorManager, "DEV_ROOT", str(tmp_path))
    assert NeuronAcceleratorManager.get_current_node_num_accelerators() == 4


def test_hpu_discriminates_from_tpu_accel_nodes(tmp_path, monkeypatch):
    drivers = tmp_path / "drivers"
    drivers.mkdir(parents=True)
    for name, drv in [("accel0", "habanalabs"), ("accel1", "tpu_common")]:
        d = tmp_path / "class/accel" / name / "device"
        d.mkdir(parents=True)
        (drivers / drv).mkdir(exist_ok=True)
        os.symlink(drivers / drv, d / "driver")
    monkeypatch.setattr(HPUAcceleratorManager, "SYS_ROOT", str(tmp_path))
    assert HPUAcceleratorManager.get_current_node_num_accelerators() == 1


def test_npu_davinci_nodes(tmp_path, monkeypatch):
    for name in ("davinci0", "davinci1", "davinci_manager"):
        (tmp_path / name).touch()
    monkeypatch.setattr(NPUAcceleratorManager, "DEV_ROOT", str(tmp_path))
    assert NPUAcceleratorManager.get_current_node_num_accelerators() == 2


def test_env_override_wins(tmp_path, monkeypatch):
    monkeypatch.setattr(NPUAcceleratorManager, "DEV_ROOT", str(tmp_path))
    (tmp_path / "davinci0").touch()
    monkeypatch.setenv("RAY_TPU_NUM_NPUS", "8")
    assert NPUAcceleratorManager.get_current_node_num_accelerators() == 8
    monkeypatch.setenv("RAY_TPU_NUM_NPUS", "0")
    assert NPUAcceleratorManager.get_current_node_num_accelerators() == 0


def test_node_detection_advertises_probed_families(monkeypatch):
    """The probe results must reach the node's resource advertisement
    (review finding: detection that never feeds scheduling is dead
    code). Uses env overrides as the probe stand-in."""
    from ray_tpu._private.node import _detect_resources

    monkeypatch.setenv("RAY_TPU_NUM_NEURON_CORES", "4")
    monkeypatch.setenv("RAY_TPU_NUM_NPUS", "2")
    resources = _detect_resources()
    assert resources["neuron_cores"] == 4.0
    assert resources["NPU"] == 2.0


def test_gpu_chain_falls_through_to_amd(tmp_path, monkeypatch):
    from ray_tpu._private.accelerators import _GPUChain

    nodes = tmp_path / "class/kfd/kfd/topology/nodes/1"
    nodes.mkdir(parents=True)
    (nodes / "gpu_id").write_text("777\n")
    monkeypatch.setattr(AMDGPUAcceleratorManager, "SYS_ROOT",
                        str(tmp_path))
    assert _GPUChain.get_current_node_num_accelerators() == 1
    assert _GPUChain.get_visible_accelerator_ids_env_var() == \
        "HIP_VISIBLE_DEVICES"


def test_visible_ids_env(monkeypatch):
    monkeypatch.setenv("HIP_VISIBLE_DEVICES", "")  # register for teardown
    AMDGPUAcceleratorManager.set_visible_accelerator_ids([0, 2])
    assert os.environ["HIP_VISIBLE_DEVICES"] == "0,2"


def test_intel_skips_boot_vga_igpu(tmp_path, monkeypatch):
    d = tmp_path / "class/drm/renderD128/device"
    d.mkdir(parents=True)
    (d / "vendor").write_text("0x8086\n")
    (d / "boot_vga").write_text("1\n")
    monkeypatch.setattr(IntelGPUAcceleratorManager, "SYS_ROOT",
                        str(tmp_path))
    assert IntelGPUAcceleratorManager.\
        get_current_node_num_accelerators() == 0


# ---------------------------------------------------------------------------
# Chip leases on a fake four-chip node (RAY_TPU_NUM_CHIPS=4). The workers
# report their environment only — nothing here imports jax, so the same
# assertions hold on the CPU box and on a TPU host.
# ---------------------------------------------------------------------------
def _chip_env():
    return {"chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "platforms": os.environ.get("JAX_PLATFORMS"),
            "process_bounds": os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS"),
            "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "pid": os.getpid()}


@pytest.fixture(scope="module")
def four_fake_chips():
    import ray_tpu

    os.environ["RAY_TPU_NUM_CHIPS"] = "4"
    try:
        ray_tpu.init(num_cpus=8)
        yield
    finally:
        ray_tpu.shutdown()
        del os.environ["RAY_TPU_NUM_CHIPS"]


def _bundle(pg, index):
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy)

    return PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=index)


def test_bundle_placed_work_owns_its_chip(four_fake_chips):
    """An actor and a task placed in {"TPU": 1} bundles get the bundle's
    chip and the TPU platform named; two bundles hold distinct chips; once
    the group is removed and its tenants are gone, all four chips are
    leasable again."""
    import ray_tpu
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)

    assert ray_tpu.cluster_resources()["TPU"] == 4.0

    @ray_tpu.remote
    class Holder:
        def env(self):
            return _chip_env()

    env_task = ray_tpu.remote(_chip_env)

    pg = placement_group([{"TPU": 1, "CPU": 1}, {"TPU": 1, "CPU": 1}])
    assert pg.wait(timeout_seconds=30)
    actor = Holder.options(num_tpus=1, num_cpus=1,
                           scheduling_strategy=_bundle(pg, 0)).remote()
    in_actor = ray_tpu.get(actor.env.remote(), timeout=60)
    in_task = ray_tpu.get(env_task.options(
        num_tpus=1, num_cpus=1,
        scheduling_strategy=_bundle(pg, 1)).remote(), timeout=60)
    for seen in (in_actor, in_task):
        assert seen["chips"] in ("0", "1", "2", "3"), seen
        assert seen["platforms"].split(",")[0] == "tpu", seen
    assert in_actor["chips"] != in_task["chips"]

    ray_tpu.kill(actor)
    remove_placement_group(pg)
    actors = [Holder.options(num_tpus=1, num_cpus=1).remote()
              for _ in range(4)]
    seen = ray_tpu.get([a.env.remote() for a in actors], timeout=120)
    assert sorted(s["chips"] for s in seen) == ["0", "1", "2", "3"]
    for a in actors:
        ray_tpu.kill(a)


def test_chip_lease_ends_with_its_worker(four_fake_chips):
    """A chip lease is served by a process that has run nothing else and
    that is retired with the lease: the next chip task gets another
    process, and a task without chips never lands on a chip holder."""
    import time

    import ray_tpu

    env_task = ray_tpu.remote(_chip_env)
    plain = ray_tpu.get(env_task.remote(), timeout=60)
    assert plain["chips"] is None and plain["platforms"] == "cpu"
    first = ray_tpu.get(env_task.options(num_tpus=1).remote(), timeout=60)
    assert first["platforms"].split(",")[0] == "tpu"
    assert first["pid"] != plain["pid"]
    time.sleep(1.0)  # past lease_idle_ttl_ms: the lease is returned
    second = ray_tpu.get(env_task.options(num_tpus=1).remote(), timeout=60)
    assert second["pid"] != first["pid"]
    again = ray_tpu.get(env_task.remote(), timeout=60)
    assert again["chips"] is None and again["platforms"] == "cpu"


def test_jax_trainer_worker_reports_its_chip(four_fake_chips):
    import time

    import ray_tpu
    from ray_tpu import train
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    def loop(config):
        train.report(_chip_env())

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"TPU": 1, "CPU": 1})).fit()
    assert result.error is None
    assert result.metrics["chips"] in ("0", "1", "2", "3"), result.metrics
    assert result.metrics["platforms"].split(",")[0] == "tpu"
    # one chip of four: the process is told the box it sees
    assert result.metrics["process_bounds"] == "1,1,1"
    # the compile cache is placed from outside, else under the checkout
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert result.metrics["compile_cache"] == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(repo, ".jax_cache"))
    # the trainer's group is returned with fit(): nothing leaked
    deadline = time.monotonic() + 30
    while ray_tpu.available_resources().get("TPU") != 4.0:
        assert time.monotonic() < deadline, ray_tpu.available_resources()
        time.sleep(0.2)
