"""Resource algebra for scheduling.

Behavioral parity with the reference's resource model (reference:
``src/ray/common/scheduling/resource_set.h``,
``cluster_resource_data.h``, ``fixed_point.h``): resource amounts are
fixed-point integers (1/10000 granularity) so fractional CPUs/TPUs compare
exactly; a node advertises *total* and *available* sets; requests subtract and
add back atomically. TPU is a predefined resource alongside CPU/GPU/memory —
the TPU-first deviation from the reference, where TPU rode the custom-resource
path (reference: ``python/ray/_private/accelerators/tpu.py:335-398``).
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping, Optional

GRANULARITY = 10_000

CPU = "CPU"
GPU = "GPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"

PREDEFINED = (CPU, GPU, TPU, MEMORY, OBJECT_STORE_MEMORY)

# Resources that are "unit" resources: requests must map to whole device
# instances when being assigned ids (CPU may be fractional for scheduling but
# accelerators are assigned as whole chips unless the request is < 1).
UNIT_INSTANCE_RESOURCES = (GPU, TPU)


def _to_fixed(value: float) -> int:
    return round(value * GRANULARITY)


def _from_fixed(value: int) -> float:
    return value / GRANULARITY


class ResourceSet:
    """A bag of named resource quantities with fixed-point arithmetic."""

    __slots__ = ("_amounts",)

    def __init__(self, amounts: Optional[Mapping[str, float]] = None):
        self._amounts: Dict[str, int] = {}
        if amounts:
            for name, qty in amounts.items():
                fp = _to_fixed(qty)
                if fp != 0:
                    self._amounts[name] = fp

    @classmethod
    def _from_fixed_map(cls, amounts: Dict[str, int]) -> "ResourceSet":
        rs = cls()
        rs._amounts = {k: v for k, v in amounts.items() if v != 0}
        return rs

    def get(self, name: str) -> float:
        return _from_fixed(self._amounts.get(name, 0))

    def has(self, name: str) -> bool:
        return self._amounts.get(name, 0) > 0

    def names(self) -> Iterable[str]:
        return self._amounts.keys()

    def is_empty(self) -> bool:
        return not self._amounts

    def to_dict(self) -> Dict[str, float]:
        return {k: _from_fixed(v) for k, v in self._amounts.items()}

    def copy(self) -> "ResourceSet":
        return ResourceSet._from_fixed_map(dict(self._amounts))

    # -- algebra -------------------------------------------------------------
    def fits(self, available: "ResourceSet") -> bool:
        """True if `available` can satisfy this request."""
        for name, qty in self._amounts.items():
            if qty > 0 and available._amounts.get(name, 0) < qty:
                return False
        return True

    def feasible_on(self, total: "ResourceSet") -> bool:
        """True if a node with `total` resources could *ever* run this."""
        return self.fits(total)

    def add(self, other: "ResourceSet") -> None:
        for name, qty in other._amounts.items():
            self._amounts[name] = self._amounts.get(name, 0) + qty
            if self._amounts[name] == 0:
                del self._amounts[name]

    def subtract(self, other: "ResourceSet", allow_negative: bool = False) -> bool:
        """Subtract in place. Returns False (and leaves self unchanged) if it
        would go negative and allow_negative is False."""
        if not allow_negative and not other.fits(self):
            return False
        for name, qty in other._amounts.items():
            self._amounts[name] = self._amounts.get(name, 0) - qty
            if self._amounts[name] == 0:
                del self._amounts[name]
        return True

    def __eq__(self, other) -> bool:
        return isinstance(other, ResourceSet) and self._amounts == other._amounts

    def __repr__(self) -> str:
        return f"ResourceSet({self.to_dict()})"

    # -- (de)serialization ---------------------------------------------------
    def to_wire(self) -> Dict[str, int]:
        return dict(self._amounts)

    @classmethod
    def from_wire(cls, wire: Dict[str, int]) -> "ResourceSet":
        return cls._from_fixed_map(dict(wire))


def normalize_label_constraints(d) -> Dict[str, Dict]:
    """Normalize user label constraints into wire form.

    Accepts values that are a string, a list of strings, or the
    In/NotIn/Exists/DoesNotExist helper objects
    (ray_tpu.util.scheduling_strategies); emits
    ``{key: {"op": ..., "values": [...]}}``.
    """
    out: Dict[str, Dict] = {}
    for k, v in (d or {}).items():
        tname = type(v).__name__
        if isinstance(v, str):
            out[k] = {"op": "in", "values": [v]}
        elif tname == "In":
            out[k] = {"op": "in", "values": list(v.values)}
        elif tname == "NotIn":
            out[k] = {"op": "not_in", "values": list(v.values)}
        elif tname == "Exists":
            out[k] = {"op": "exists", "values": []}
        elif tname == "DoesNotExist":
            out[k] = {"op": "not_exists", "values": []}
        else:
            out[k] = {"op": "in", "values": list(v)}
    return out


def label_constraints_match(labels: Mapping[str, str], constraints) -> bool:
    """Evaluate wire-form label constraints against a node's labels."""
    for key, c in (constraints or {}).items():
        op, values = c.get("op", "in"), c.get("values", [])
        present = key in labels
        if op == "in":
            if labels.get(key) not in values:
                return False
        elif op == "not_in":
            if present and labels[key] in values:
                return False
        elif op == "exists":
            if not present:
                return False
        elif op == "not_exists":
            if present:
                return False
    return True


class NodeResources:
    """Total + available resources of one node, plus labels.

    Parity with reference ``cluster_resource_data.h:289`` (NodeResources with
    total/available/labels) in a single class; per-instance accounting for
    accelerator chip ids lives here too (reference: resource_instance_set.h).
    """

    def __init__(
        self,
        total: ResourceSet,
        labels: Optional[Dict[str, str]] = None,
        accelerator_ids: Optional[Dict[str, list]] = None,
    ):
        self.total = total.copy()
        self.available = total.copy()
        self.labels = dict(labels or {})
        # resource name -> list of free device indices, e.g. {"TPU": [0,1,2,3]}
        self.free_instances: Dict[str, list] = {
            k: list(v) for k, v in (accelerator_ids or {}).items()
        }
        self.assigned_instances: Dict[str, Dict[str, list]] = {}  # owner -> name -> ids

    def utilization(self) -> float:
        """Critical-resource utilization in [0,1] — drives the hybrid policy."""
        worst = 0.0
        for name, total_fp in self.total.to_wire().items():
            if total_fp <= 0:
                continue
            avail_fp = self.available.to_wire().get(name, 0)
            worst = max(worst, 1.0 - avail_fp / total_fp)
        return worst

    def allocate(self, request: ResourceSet, owner: str = "") -> Optional[Dict[str, list]]:
        """Try to allocate; returns {resource: [instance ids]} for unit
        resources (empty lists for non-instance resources) or None."""
        if not request.fits(self.available):
            return None
        self.available.subtract(request)
        assigned: Dict[str, list] = {}
        for name in request.names():
            qty = request.get(name)
            if name in self.free_instances and qty >= 1:
                n = int(qty)
                ids = self.free_instances[name][:n]
                self.free_instances[name] = self.free_instances[name][n:]
                assigned[name] = ids
        if owner:
            self.assigned_instances.setdefault(owner, {})
            for name, ids in assigned.items():
                self.assigned_instances[owner].setdefault(name, []).extend(ids)
        return assigned

    def release(self, request: ResourceSet, owner: str = "",
                instances: Optional[Dict[str, list]] = None) -> None:
        """Give ``request`` back, with the instance ids recorded under
        ``owner`` or, for a holder that kept its own ids (a placement-group
        bundle), the ``instances`` it names."""
        self.available.add(request)
        # Clamp: never exceed total (defensive against double-release).
        for name, total_fp in self.total.to_wire().items():
            avail = self.available.to_wire().get(name, 0)
            if avail > total_fp:
                self.available = ResourceSet._from_fixed_map(
                    {**self.available.to_wire(), name: total_fp}
                )
        if owner and owner in self.assigned_instances:
            instances = self.assigned_instances.pop(owner)
        for name, ids in (instances or {}).items():
            self.free_instances[name] = sorted(
                self.free_instances.get(name, []) + list(ids))

    def to_wire(self) -> Dict:
        return {
            "total": self.total.to_wire(),
            "available": self.available.to_wire(),
            "labels": self.labels,
            "free_instances": self.free_instances,
        }

    @classmethod
    def from_wire(cls, wire: Dict) -> "NodeResources":
        nr = cls(ResourceSet.from_wire(wire["total"]), wire.get("labels"))
        nr.available = ResourceSet.from_wire(wire["available"])
        nr.free_instances = {k: list(v) for k, v in wire.get("free_instances", {}).items()}
        return nr
