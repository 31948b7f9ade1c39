"""Local cluster capacity matcher (component #29; VERDICT r4 next #3).

Reference semantics under test:
``scheduler_core/scheduler_matcher.py:79-124`` — equal spread then greedy
remainder; refuse when the ask exceeds total availability. Here the
inventory is the agents' sqlite journal and ``fedml launch`` consumes it.
"""

from __future__ import annotations

import textwrap
import time

import pytest

from fedml_tpu.computing.scheduler.cluster import (
    ClusterMatchError,
    ClusterRegistry,
    EdgeCapacity,
    detect_local_capacity,
    match_and_assign,
)
from fedml_tpu.computing.scheduler.launch_manager import FedMLLaunchManager


def _caps(*slots):
    return {i: EdgeCapacity(edge_id=i, cores=4, memory_mb=1024,
                            slots_total=s, slots_available=s)
            for i, s in enumerate(slots)}


# --- pure matcher ----------------------------------------------------------

def test_two_slot_job_lands_on_the_two_agents_with_capacity():
    """VERDICT's acceptance: 3 agents, one has no capacity — a 2-slot job
    lands one slot on each of the two that do."""
    assignment = match_and_assign(2, _caps(1, 0, 1))
    assert assignment == {0: 1, 2: 1}


def test_over_ask_fails_with_clear_error():
    with pytest.raises(ClusterMatchError) as exc:
        match_and_assign(5, _caps(1, 0, 1))
    msg = str(exc.value)
    assert "requests 5" in msg and "only 2 available" in msg and "3 agent(s)" in msg


def test_no_registered_agents_is_its_own_error():
    with pytest.raises(ClusterMatchError, match="no agents have registered"):
        match_and_assign(1, {})


def test_explicit_empty_edge_list_matches_nothing():
    """edge_ids=[] (a manager with zero local runners) must NOT fall back
    to every journal row — phantom-edge dispatch (code-review r5)."""
    with pytest.raises(ClusterMatchError, match="no agents have registered"):
        match_and_assign(1, _caps(4, 4), edge_ids=[])


def test_equal_spread_then_greedy_remainder():
    # 8 slots over (4, 4, 4): equal share 2 each, remainder 2 greedily in
    # edge order -> first edge tops up to 4 (reference lines 101-117)
    assert match_and_assign(8, _caps(4, 4, 4)) == {0: 4, 1: 2, 2: 2}
    # uneven availability clamps the equal share per edge
    assert match_and_assign(6, _caps(1, 8, 1)) == {0: 1, 1: 4, 2: 1}


def test_zero_ask_matches_nothing():
    assert match_and_assign(0, _caps(2, 2)) == {}


# --- registry durability ---------------------------------------------------

def test_registry_persists_and_tracks_slots(tmp_path):
    db = str(tmp_path / "cluster.db")
    reg = ClusterRegistry(db)
    reg.register(EdgeCapacity(edge_id=0, cores=8, memory_mb=2048,
                              slots_total=4, slots_available=4,
                              accelerator_kind="tpu-v5e"))
    reg.acquire({0: 3})
    reg.close()
    # a fresh process sees the in-flight debit (sqlite durability), and the
    # startup announce() must NOT clobber the registered row — a detected
    # slots_total=0 next to slots_available=3-in-flight would strand the
    # capacity forever (code-review r5 finding)
    reg2 = ClusterRegistry(db)
    reg2.announce(EdgeCapacity(edge_id=0, cores=8, memory_mb=2048,
                               slots_total=0, slots_available=0))
    caps = reg2.capacities()
    assert caps[0].slots_available == 1 and caps[0].slots_total == 4
    reg2.release({0: 3})
    assert reg2.capacities()[0].slots_available == 4
    assert reg2.status() == {"agents": 1, "slots_total": 4, "slots_available": 4}
    reg2.close()


def test_reregistration_preserves_inflight_debits(tmp_path):
    """An agent check-in (re-register) mid-run must not restore slots a
    running job still occupies (code-review r5): new available =
    new_total - in_flight, floored at 0."""
    reg = ClusterRegistry(str(tmp_path / "cluster.db"))
    cap = EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                       slots_total=2, slots_available=2)
    reg.register(cap)
    reg.acquire({0: 2})  # both slots busy
    reg.register(cap)  # check-in refresh with the same declared capacity
    assert reg.capacities()[0].slots_available == 0  # debits preserved
    # growing the declared total grants only the NEW headroom
    reg.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                              slots_total=3, slots_available=3))
    assert reg.capacities()[0].slots_available == 1
    # shrinking below in-flight floors at 0 (never negative)
    reg.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                              slots_total=1, slots_available=1))
    assert reg.capacities()[0].slots_available == 0
    reg.close()


def test_release_is_clamped_and_idempotent_at_total(tmp_path):
    """Double releases (finally + reaper racing) must not overshoot the
    total; the credit is one atomic clamped SQL update."""
    reg = ClusterRegistry(str(tmp_path / "cluster.db"))
    reg.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                              slots_total=2, slots_available=2))
    reg.acquire({0: 1})
    reg.release({0: 1})
    reg.release({0: 1})  # late duplicate credit
    assert reg.capacities()[0].slots_available == 2  # clamped at total
    reg.close()


def test_acquire_detects_concurrent_claim(tmp_path):
    """Two launchers sharing the journal both match the same single slot:
    the second acquire's atomic conditional debit refuses instead of
    clamping the count into silent over-commit."""
    db = str(tmp_path / "cluster.db")
    reg = ClusterRegistry(db)
    reg.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                              slots_total=1, slots_available=1))
    reg.acquire({0: 1})  # launcher A wins
    with pytest.raises(ClusterMatchError, match="concurrent launch"):
        reg.acquire({0: 1})  # launcher B matched stale availability
    assert reg.capacities()[0].slots_available == 0  # not driven negative
    reg.close()


def test_detect_local_capacity_reports_host_without_touching_jax(monkeypatch):
    """Chips are counted from device nodes: an agent that initialised JAX
    would hold the chip its own job is about to need."""
    import jax

    from fedml_tpu.computing.scheduler import cluster

    def no_jax(*a, **k):
        raise AssertionError("capacity detection must not initialise JAX")

    monkeypatch.setattr(jax, "devices", no_jax)
    cap = detect_local_capacity(3)
    assert cap.edge_id == 3 and cap.cores >= 1 and cap.memory_mb > 0
    assert cap.slots_total == 0 and cap.accelerator_kind == ""  # this host has no chip
    monkeypatch.setattr(cluster, "local_chip_count", lambda: 4)
    cap = detect_local_capacity(3)
    assert cap.slots_total == cap.slots_available == 4 and cap.accelerator_kind == "tpu"


# --- launch integration ----------------------------------------------------

def _slot_job(tmp_path, n_slots):
    ws = tmp_path / "ws"
    ws.mkdir(exist_ok=True)
    (ws / "main.py").write_text(
        "import os\nprint('SLOTS', os.environ.get('FEDML_MATCHED_SLOTS'),"
        " 'NODES', os.environ.get('FEDML_NUM_NODES'))\n")
    job_yaml = tmp_path / "job.yaml"
    job_yaml.write_text(textwrap.dedent(f"""
        job_name: slots
        workspace: ws
        job: python main.py
        computing:
          minimum_num_gpus: {n_slots}
    """))
    return str(job_yaml)


def test_launch_matches_slots_and_passes_scheduler_info(tmp_path):
    mgr = FedMLLaunchManager(num_edges=3, base_dir=str(tmp_path / "agent"))
    # agents 0 and 2 have one slot each; agent 1 none (local hosts register
    # zero accelerator slots by default)
    mgr.cluster.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                                      slots_total=1, slots_available=1))
    mgr.cluster.register(EdgeCapacity(edge_id=2, cores=4, memory_mb=1024,
                                      slots_total=1, slots_available=1))
    statuses = mgr.launch_job(_slot_job(tmp_path, 2), timeout_s=120)
    assert set(statuses) == {0, 2}  # agent 1 got no work
    assert all(st.status == "FINISHED" for st in statuses.values())
    # each matched edge's job saw its own slot count + the topology
    for st in statuses.values():
        assert "SLOTS 1 NODES 2" in open(st.log_path).read()
    # slots were released after the terminal statuses
    caps = mgr.cluster.capacities()
    assert caps[0].slots_available == 1 and caps[2].slots_available == 1


def test_api_grow_path_announces_capacity(tmp_path, monkeypatch):
    """api._launch_manager's on-demand pool growth must announce each new
    edge's inventory (the renamed announce() — a drive of
    examples/launch/cluster_job caught the stale refresh() call here)."""
    from fedml_tpu import api

    mgr = FedMLLaunchManager(num_edges=1, base_dir=str(tmp_path / "agent"))
    monkeypatch.setattr(FedMLLaunchManager, "_instance", mgr)
    api._launch_manager(num_edges=3)
    assert set(mgr.cluster.capacities()) == {0, 1, 2}


def test_launch_over_ask_raises_before_dispatch(tmp_path):
    mgr = FedMLLaunchManager(num_edges=3, base_dir=str(tmp_path / "agent"))
    mgr.cluster.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                                      slots_total=1, slots_available=1))
    with pytest.raises(ClusterMatchError, match="requests 4 slot"):
        mgr.launch_job(_slot_job(tmp_path, 4))
    assert not mgr.master.statuses  # nothing was dispatched


def test_launch_ignores_capacity_rows_without_local_runner(tmp_path):
    """A journal row for an edge id this manager doesn't run (stale
    topology / remote agent) must not be dispatched to — the run would
    strand in a dead thread (code-review r5 finding)."""
    mgr = FedMLLaunchManager(num_edges=1, base_dir=str(tmp_path / "agent"))
    mgr.cluster.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                                      slots_total=1, slots_available=1))
    mgr.cluster.register(EdgeCapacity(edge_id=7, cores=4, memory_mb=1024,
                                      slots_total=8, slots_available=8))
    statuses = mgr.launch_job(_slot_job(tmp_path, 1), timeout_s=120)
    assert set(statuses) == {0}
    # and an ask only edge 7 could satisfy refuses rather than dispatching
    # to the phantom edge
    with pytest.raises(ClusterMatchError):
        mgr.launch_job(_slot_job(tmp_path, 2))


def test_dispatch_timeout_keeps_slots_until_terminal_then_reaps(tmp_path):
    """A RUNNING placeholder (dispatch deadline passed, job alive) keeps
    its slots debited — releasing would double-book the chip; the reaper
    credits them when the run ends (code-review r5 finding)."""
    ws = tmp_path / "ws"
    ws.mkdir()
    (ws / "main.py").write_text("import time; time.sleep(6)\n")
    job_yaml = tmp_path / "job.yaml"
    job_yaml.write_text(textwrap.dedent("""
        job_name: slow
        workspace: ws
        job: python main.py
        computing:
          minimum_num_gpus: 1
    """))
    mgr = FedMLLaunchManager(num_edges=1, base_dir=str(tmp_path / "agent"))
    mgr.cluster.register(EdgeCapacity(edge_id=0, cores=4, memory_mb=1024,
                                      slots_total=1, slots_available=1))
    statuses = mgr.launch_job(str(job_yaml), timeout_s=2.0)
    assert statuses[0].status == "RUNNING"
    assert mgr.cluster.capacities()[0].slots_available == 0  # still busy
    deadline = time.time() + 30
    while time.time() < deadline:
        if mgr.cluster.capacities()[0].slots_available == 1:
            break
        time.sleep(0.5)
    assert mgr.cluster.capacities()[0].slots_available == 1  # reaped
    assert statuses[0].status == "FINISHED"
