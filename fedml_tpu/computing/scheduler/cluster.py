"""Local cluster capacity registry + job/resource matcher (component #29).

Reference semantics:
``computing/scheduler/scheduler_core/scheduler_matcher.py:79-124``
(match_and_assign_gpu_resources_to_devices) — a job asking for N slots is
spread over the active edges: first an equal share per edge (clamped to
each edge's availability), then the remainder greedily; a total
availability below the ask refuses the match. The reference resolves this
against its cloud inventory over REST (``scheduler_entry/launch_manager.py``);
here the inventory is the agents' sqlite journal (``agent_db.py`` capacity
table) — N local agents register cores/memory/accelerator slots and
``fedml launch`` matches against them with the same spread algorithm.

"Slot" is deliberately abstract: on the reference it is a CUDA device; on
a TPU pod deployment it is a chip (a v5e-8 host registers 8), and the
per-edge assignment count is what a multi-host runner feeds into its mesh
partitioning (parallel/multihost.py).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional

from ...core.distributed.device_specs import local_chip_count
from .agent_db import AgentDatabase


class ClusterMatchError(RuntimeError):
    """The cluster cannot satisfy the job's resource request. The message
    states ask vs availability — the reference's silent ``return None, None``
    surfaced as a generic launch failure."""


@dataclass
class EdgeCapacity:
    edge_id: int
    cores: int
    memory_mb: int
    slots_total: int
    slots_available: int
    accelerator_kind: str = ""


def detect_local_capacity(edge_id: int) -> EdgeCapacity:
    """Best-effort inventory of THIS host (the reference's slave agent
    reports the same trio via hardware probing — ``slave/client_data_
    interface.py``): cores from the scheduler, memory from /proc, one slot
    per attached TPU chip. Chips are counted from their device nodes, never
    through JAX: a chip belongs to one process, and an agent that
    initialised JAX would hold the chip its own job is about to need."""
    cores = os.cpu_count() or 1
    memory_mb = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    memory_mb = int(line.split()[1]) // 1024
                    break
    except OSError:
        pass
    slots = local_chip_count()
    return EdgeCapacity(edge_id=edge_id, cores=cores, memory_mb=memory_mb,
                        slots_total=slots, slots_available=slots,
                        accelerator_kind="tpu" if slots else "")


def match_and_assign(request_slots: int,
                     capacities: Dict[int, EdgeCapacity],
                     edge_ids: Optional[List[int]] = None) -> Dict[int, int]:
    """Spread ``request_slots`` over the edges; returns {edge_id: slots}
    containing ONLY edges that received work.

    Algorithm is the reference's (scheduler_matcher.py:101-117): equal
    share first (request // n_edges, clamped per edge), remainder greedily
    in edge order. Raises ClusterMatchError when the ask exceeds the total.
    """
    # `is not None`, not truthiness: an explicitly EMPTY edge list (a
    # manager running zero local edges) must match nothing — falling back
    # to every journal row would dispatch onto phantom edges
    pool = {eid: capacities[eid]
            for eid in (edge_ids if edge_ids is not None else sorted(capacities))
            if eid in capacities}
    if request_slots <= 0:
        return {}
    if not pool:
        raise ClusterMatchError(
            f"job requests {request_slots} slot(s) but no agents have "
            "registered capacity — run cluster_register/agent daemons first")
    total = sum(c.slots_available for c in pool.values())
    if total < request_slots:
        detail = ", ".join(
            f"edge {eid}: {c.slots_available}/{c.slots_total}"
            f"{' ' + c.accelerator_kind if c.accelerator_kind else ''}"
            for eid, c in sorted(pool.items()))
        raise ClusterMatchError(
            f"job requests {request_slots} slot(s) but the cluster has only "
            f"{total} available across {len(pool)} agent(s) ({detail})")
    assigned: Dict[int, int] = {}
    share = request_slots // len(pool)
    given = 0
    for eid, cap in sorted(pool.items()):
        take = min(cap.slots_available, share)
        assigned[eid] = take
        given += take
    for eid, cap in sorted(pool.items()):
        if given >= request_slots:
            break
        add = min(cap.slots_available - assigned[eid], request_slots - given)
        assigned[eid] += add
        given += add
    return {eid: n for eid, n in assigned.items() if n > 0}


class ClusterRegistry:
    """The launch-side view of registered agent capacity, persisted in the
    agents' sqlite journal so it survives agent restarts (same durability
    contract as runs/requests — tests/test_agent_durability.py)."""

    def __init__(self, db_path: str):
        self._db = AgentDatabase(db_path)

    def register(self, cap: EdgeCapacity) -> None:
        self._db.register_capacity(
            cap.edge_id, cap.cores, cap.memory_mb, cap.slots_total,
            slots_available=cap.slots_available,
            accelerator_kind=cap.accelerator_kind)

    def announce(self, cap: EdgeCapacity) -> None:
        """First-contact default registration: writes ONLY when the edge has
        no capacity row yet. A manual cluster_register (or a previous
        session's row) always wins — the startup auto-inventory must never
        clobber declared capacity (slots_total=0 from a no-accelerator host
        would strand any in-flight slots_available forever)."""
        self._db.register_capacity_if_absent(
            cap.edge_id, cap.cores, cap.memory_mb, cap.slots_total,
            slots_available=cap.slots_available,
            accelerator_kind=cap.accelerator_kind)

    def capacities(self) -> Dict[int, EdgeCapacity]:
        return {eid: EdgeCapacity(edge_id=eid, cores=row["cores"],
                                  memory_mb=row["memory_mb"],
                                  slots_total=row["slots_total"],
                                  slots_available=row["slots_available"],
                                  accelerator_kind=row["accelerator_kind"])
                for eid, row in self._db.list_capacity().items()}

    def acquire(self, assignment: Dict[int, int]) -> None:
        """Debit assigned slots ATOMICALLY (called at dispatch). The match
        ran outside any transaction, so a concurrent launcher sharing the
        journal may have debited the same slots since — the conditional
        one-transaction debit detects the lost race and raises instead of
        clamping the count into silent over-commit."""
        if not self._db.debit_slots(assignment):
            raise ClusterMatchError(
                f"slots were claimed by a concurrent launch before dispatch "
                f"(assignment {assignment}); re-run to re-match")

    def release(self, assignment: Dict[int, int]) -> None:
        """Credit slots back (terminal run status) — atomic, clamped at
        each edge's total (see AgentDatabase.credit_slots)."""
        caps = self.capacities()
        self._db.credit_slots({eid: n for eid, n in assignment.items()
                               if eid in caps})

    def status(self) -> Dict[str, int]:
        caps = self.capacities()
        return {
            "agents": len(caps),
            "slots_total": sum(c.slots_total for c in caps.values()),
            "slots_available": sum(c.slots_available for c in caps.values()),
        }

    def close(self) -> None:
        self._db.close()
