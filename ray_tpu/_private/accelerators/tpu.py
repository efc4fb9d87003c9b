"""TPU topology detection and pod-slice resource advertising.

Behavioral parity with the reference's TPU support (reference:
``python/ray/_private/accelerators/tpu.py:75-398``): chips are detected from
``/dev/accel*`` / ``/dev/vfio`` or env overrides; a process is bound to its
leased chips via ``TPU_VISIBLE_CHIPS`` (+ the process-bounds vars when it sees
fewer chips than the host has) and the TPU platform by name; multi-host pod slices
advertise a ``{slice_name}: 1`` resource on every host plus a
``TPU-{pod_type}-head: 1`` resource on worker 0, so a driver can schedule one
task on the slice head and fan SPMD tasks out to every host of the slice.

TPU-first deviation: TPU is a *predefined* resource in the scheduler's
resource algebra (see ``ray_tpu/_private/resources.py``), not a custom
resource bolted on after the fact.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

# Env-var inputs (same contract the reference reads before GCE/GKE metadata,
# which makes fake-TPU-topology tests trivial):
ENV_NUM_CHIPS = "RAY_TPU_NUM_CHIPS"            # override chip count
ENV_ACCEL_TYPE = "TPU_ACCELERATOR_TYPE"        # e.g. "v5litepod-16"
ENV_WORKER_ID = "TPU_WORKER_ID"                # host index within the slice
ENV_SLICE_NAME = "TPU_NAME"                    # slice/pod name
ENV_CHIPS_PER_HOST_BOUNDS = "TPU_CHIPS_PER_HOST_BOUNDS"
ENV_HOST_BOUNDS = "TPU_HOST_BOUNDS"
ENV_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"
ENV_CHIPS_PER_PROCESS_BOUNDS = "TPU_CHIPS_PER_PROCESS_BOUNDS"
ENV_PROCESS_BOUNDS = "TPU_PROCESS_BOUNDS"

# A process that sees fewer chips than the host has must be told the box
# they form; libtpu 0.0.34 aborts on a box that does not match. One chip is
# 1,1,1 whichever it is (measured: four such processes side by side on a
# v5litepod-4). Pairs are for the 2x2 host, whose chips sit at (x, y) =
# (0,0) (1,0) (0,1) (1,1) in id order (jax device coords): chips 2,3 ->
# 2,1,1 was measured, the others follow from the coordinates.
_PAIR_BOUNDS_2X2 = {(0, 1): "2,1,1", (2, 3): "2,1,1",
                    (0, 2): "1,2,1", (1, 3): "1,2,1"}

VALID_CHIP_REQUESTS = (1, 2, 4, 8)


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return ENV_VISIBLE_CHIPS

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        if ENV_NUM_CHIPS in os.environ:
            return int(os.environ[ENV_NUM_CHIPS])
        accel = glob.glob("/dev/accel*")
        if accel:
            return len(accel)
        try:
            vfio = glob.glob("/dev/vfio/[0-9]*")
            return len(vfio)
        except OSError:
            return 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        accel_type = os.environ.get(ENV_ACCEL_TYPE)
        if accel_type:
            # "v5litepod-16" -> "TPU-V5LITEPOD"
            return "TPU-" + accel_type.split("-")[0].upper()
        return None

    @staticmethod
    def get_current_pod_type() -> Optional[str]:
        accel_type = os.environ.get(ENV_ACCEL_TYPE)
        return accel_type

    @staticmethod
    def get_current_pod_worker_count() -> Optional[int]:
        """Hosts in the current slice, derived from the accelerator type
        (e.g. v5litepod-16 => 16 chips / 4 chips-per-host = 4 hosts)."""
        pod_type = os.environ.get(ENV_ACCEL_TYPE)
        if not pod_type or "-" not in pod_type:
            return None
        try:
            total_chips = int(pod_type.rsplit("-", 1)[1])
        except ValueError:
            return None
        chips_per_host = TPUAcceleratorManager._chips_per_host()
        return max(1, total_chips // chips_per_host)

    @staticmethod
    def _chips_per_host() -> int:
        bounds = os.environ.get(ENV_CHIPS_PER_HOST_BOUNDS)
        if bounds:
            dims = [int(x) for x in bounds.split(",")]
            out = 1
            for d in dims:
                out *= d
            return out
        from ray_tpu._private.config import CONFIG

        return CONFIG.tpu_chips_per_host_default

    @staticmethod
    def chips_per_host_for_topology(topology: str) -> Optional[int]:
        """Chips per host for a named slice topology (e.g. "v5e-8" → 8,
        "v5p-16" → 4). Single-host v5e slices put all chips on one host;
        multi-host slices are 4 chips/host across generations
        (reference: tpu.py pod-type accounting, tpu.py:198-287)."""
        try:
            gen, total_s = topology.rsplit("-", 1)
            total = int(total_s)
        except ValueError:
            return None
        if gen.lower() in ("v5e", "v5litepod", "v6e") and total <= 8:
            return total
        return min(total, 4)

    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> Tuple[bool, Optional[str]]:
        if quantity != int(quantity):
            return False, "TPU request must be a whole number of chips"
        if int(quantity) not in VALID_CHIP_REQUESTS and int(quantity) % 4 != 0:
            return (
                False,
                f"TPU request must be one of {VALID_CHIP_REQUESTS} or a "
                "multiple of 4 (whole hosts)",
            )
        return True, None

    @staticmethod
    def set_visible_accelerator_ids(ids: List[int]) -> None:
        """Bind this process to its leased chips. Must run before the
        process first imports jax. The platform is named, not left to
        jax's own choice: a chip that does not come up then raises instead
        of giving way to the CPU (``cpu`` stays listed for host arrays; an
        explicit list fails loudly on every entry)."""
        ids = sorted(int(i) for i in ids)
        os.environ[ENV_VISIBLE_CHIPS] = ",".join(str(i) for i in ids)
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
        on_node = TPUAcceleratorManager.get_current_node_num_accelerators()
        if len(ids) < on_node:
            bounds = ("1,1,1" if len(ids) == 1
                      else _PAIR_BOUNDS_2X2.get(tuple(ids)))
            if bounds is None:
                raise ValueError(
                    f"TPU chips {ids} of {on_node} form no box libtpu can "
                    "be given; lease 1, an aligned pair, or the whole host")
            os.environ[ENV_CHIPS_PER_PROCESS_BOUNDS] = bounds
            os.environ[ENV_PROCESS_BOUNDS] = "1,1,1"

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Pod-slice resources (reference: tpu.py:335-398): every host in a
        slice gets `{slice_name}: 1`; host 0 additionally gets
        `TPU-{pod_type}-head: 1` so drivers can target the slice head."""
        out: Dict[str, float] = {}
        slice_name = os.environ.get(ENV_SLICE_NAME)
        pod_type = os.environ.get(ENV_ACCEL_TYPE)
        if slice_name:
            out[slice_name] = 1.0
        worker_id = os.environ.get(ENV_WORKER_ID)
        if pod_type and worker_id is not None and int(worker_id) == 0:
            out[f"TPU-{pod_type}-head"] = 1.0
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if accel_type:
            out[accel_type] = 1.0
        return out
