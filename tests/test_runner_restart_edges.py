"""Runner restart-policy edges + chip-grant stability under crash loops.

The serving resilience chain ends at the runner: a watchdog-tripped cell
exits nonzero and the restart policy must bring it back — with ITS chips,
within its retry budget, after its backoff — or the recovery story has a
hole. These pin the edges the main controller suite doesn't."""

import time

import pytest

from kukeon_tpu.runtime import model
from kukeon_tpu.runtime.api import types as t
from kukeon_tpu.runtime.cells import FakeBackend
from kukeon_tpu.runtime.controller import Controller
from kukeon_tpu.runtime.devices import TPUDeviceManager
from kukeon_tpu.runtime.metadata import MetadataStore
from kukeon_tpu.runtime.runner import (
    OUTCOME_RESTARTED,
    Runner,
    RunnerOptions,
)
from kukeon_tpu.runtime.store import ResourceStore


@pytest.fixture
def ctl(tmp_path):
    store = ResourceStore(MetadataStore(str(tmp_path)))
    backend = FakeBackend()
    devices = TPUDeviceManager(store.ms, chips=[0, 1, 2, 3])
    runner = Runner(store, backend, cgroups=None, devices=devices,
                    options=RunnerOptions(stop_grace_s=0.2))
    c = Controller(store, runner)
    c.bootstrap()
    return c, backend, store, devices


def _cell_doc(name="c1", **cell_kw):
    return t.Document(
        kind=t.KIND_CELL,
        metadata=t.Metadata(name=name),
        spec=t.CellSpec(
            containers=[t.ContainerSpec(name="main", command=["/bin/true"])],
            **cell_kw,
        ),
    )


def _refresh(c, name="c1"):
    return c.runner.refresh_cell("default", "default", "default", name)


def test_never_policy_leaves_cell_stopped(ctl):
    c, backend, store, _ = ctl
    doc = _cell_doc()
    doc.spec.containers[0].restart_policy = t.RestartPolicy(policy="never")
    c.create_cell(doc)
    cdir = store.container_dir("default", "default", "default", "c1", "main")
    backend.exit(cdir, 1)

    for _ in range(3):
        _, outcome = _refresh(c)
        assert outcome != OUTCOME_RESTARTED
    rec = store.read_cell("default", "default", "default", "c1")
    st = rec.status.container("main")
    assert st.restarts == 0
    assert st.state == model.C_EXITED
    assert rec.status.phase == model.FAILED        # nonzero exit, no revival
    assert backend.entries[cdir].starts == 1       # the original start only


def test_never_policy_clean_exit_is_stopped_not_failed(ctl):
    c, backend, store, _ = ctl
    doc = _cell_doc()
    doc.spec.containers[0].restart_policy = t.RestartPolicy(policy="never")
    c.create_cell(doc)
    cdir = store.container_dir("default", "default", "default", "c1", "main")
    backend.exit(cdir, 0)
    _, outcome = _refresh(c)
    assert outcome != OUTCOME_RESTARTED
    rec = store.read_cell("default", "default", "default", "c1")
    assert rec.status.phase == model.STOPPED


def test_backoff_is_honored_between_restarts(ctl):
    """No restart inside the backoff window; a prompt restart right after
    it elapses — the crash-loop damper actually damps, and recovery is not
    deferred past the window."""
    c, backend, store, _ = ctl
    doc = _cell_doc()
    doc.spec.containers[0].restart_policy = t.RestartPolicy(
        policy="always", backoff_seconds=0.3
    )
    c.create_cell(doc)
    cdir = store.container_dir("default", "default", "default", "c1", "main")
    backend.exit(cdir, 1)

    # Inside the window: repeated reconcile ticks must not restart.
    for _ in range(2):
        _, outcome = _refresh(c)
        assert outcome != OUTCOME_RESTARTED
    assert backend.entries[cdir].starts == 1

    time.sleep(0.35)
    _, outcome = _refresh(c)
    assert outcome == OUTCOME_RESTARTED
    assert backend.entries[cdir].starts == 2

    # Second crash: the window re-anchors at the RESTART time, not the
    # first crash's — an immediate refresh stays put again.
    backend.exit(cdir, 1)
    _, outcome = _refresh(c)
    assert outcome != OUTCOME_RESTARTED
    time.sleep(0.35)
    _, outcome = _refresh(c)
    assert outcome == OUTCOME_RESTARTED
    assert backend.entries[cdir].starts == 3


def test_on_failure_budget_exhaustion_reports_reason(ctl):
    c, backend, store, _ = ctl
    doc = _cell_doc()
    doc.spec.containers[0].restart_policy = t.RestartPolicy(
        policy="on-failure", backoff_seconds=0.0, max_retries=1
    )
    c.create_cell(doc)
    cdir = store.container_dir("default", "default", "default", "c1", "main")

    backend.exit(cdir, 7)
    _, outcome = _refresh(c)
    assert outcome == OUTCOME_RESTARTED

    backend.exit(cdir, 7)
    _, outcome = _refresh(c)
    assert outcome != OUTCOME_RESTARTED
    rec = store.read_cell("default", "default", "default", "c1")
    assert rec.status.container("main").restarts == 1
    assert "restart budget exhausted" in (rec.status.reason or "")
    # Further ticks stay put — no zombie restarts past the budget.
    _, outcome = _refresh(c)
    assert outcome != OUTCOME_RESTARTED
    assert backend.entries[cdir].starts == 2


def test_crash_looping_model_cell_keeps_its_chip_grant(ctl):
    """A serving cell that crash-loops (e.g. the TPU watchdog exiting
    WEDGED_EXIT_CODE) must be restarted with the SAME chip grant every
    time: visibility env identical across restarts, and a neighbor cell's
    grant never raided."""
    c, backend, store, devices = ctl
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=2, port=9123)),
    )
    c.create_cell(doc)
    cdir = store.container_dir(
        "default", "default", "default", "llm", "model-server")
    first_env = backend.started[-1].env
    assert first_env["TPU_VISIBLE_DEVICES"] == "0,1"

    # A neighbor takes the remaining chips — nothing is free anymore.
    doc2 = _cell_doc("other")
    doc2.spec.containers[0].resources = t.Resources(tpu_chips=2)
    c.create_cell(doc2)
    assert devices.free_chips() == []

    # Crash-loop the model cell through several restarts (the model
    # container's policy is always/backoff=2.0; the first refresh records
    # the exit and honors the backoff, so the test crosses the window by
    # rewinding the recorded timestamps rather than sleeping).
    for i in range(3):
        backend.exit(cdir, 86)
        _, outcome = _refresh(c, "llm")          # records exit; inside backoff
        assert outcome != OUTCOME_RESTARTED
        rec = store.read_cell("default", "default", "default", "llm")
        st = rec.status.container("model-server")
        if st.last_restart_at:
            st.last_restart_at -= 10.0
        if st.finished_at:
            st.finished_at -= 10.0
        store.write_cell(rec)
        _, outcome = _refresh(c, "llm")
        assert outcome == OUTCOME_RESTARTED, f"restart #{i + 1} did not happen"
        env = backend.started[-1].env
        assert env["TPU_VISIBLE_DEVICES"] == "0,1", "chip grant drifted"

    # The allocation record never changed hands.
    rec = store.read_cell("default", "default", "default", "llm")
    assert rec.status.tpu_chips == [0, 1]
    assert devices.allocated()[0] == "default/default/default/llm"
    assert devices.allocated()[2] == "default/default/default/other"


@pytest.mark.parametrize("grant,visible,want_ids,want_bounds", [
    # A process that sees the host's /dev: positions in the host's list.
    ([1], [0, 1, 2, 3], "1", "1,1,1"),
    ([2, 3], [0, 1, 2, 3], "2,3", "1,2,1"),
    ([0, 1, 2, 3], [0, 1, 2, 3], "0,1,2,3", "2,2,1"),
    # A namespaced cell: /dev holds only the grant, numbered from 0.
    ([1], None, "0", "1,1,1"),
    ([2, 3], None, "0,1", "1,2,1"),
    # A host whose one chip is node /dev/vfio/2 (what the sealed one-chip
    # machine hands out): that chip is libtpu's device 0, either way.
    ([2], [2], "0", "1,1,1"),
    # Not a rectangle of the 2x2 grid: visibility only.
    ([0, 1, 2], [0, 1, 2, 3], "0,1,2", None),
])
def test_visibility_env_names_positions_and_2x2_bounds(
        grant, visible, want_ids, want_bounds):
    env = TPUDeviceManager.visibility_env(grant, visible)
    assert env["TPU_VISIBLE_DEVICES"] == want_ids
    assert env.get("TPU_CHIPS_PER_PROCESS_BOUNDS") == want_bounds
    assert env.get("TPU_PROCESS_BOUNDS") == ("1,1,1" if want_bounds else None)
    assert set(env) <= {"TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                        "TPU_PROCESS_BOUNDS"}
