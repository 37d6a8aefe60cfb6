"""TPU device manager: chip discovery, per-cell affinity, visibility env.

The first TPU-native piece (SURVEY.md section 7 step 5; BASELINE.json north
star: "internal/ctr grows a libtpu device manager"). Chips are a schedulable
resource like the reference's memory limits: the runner asks for N chips at
cell start, the manager hands out concrete chip ids, persists the allocation
in the metadata store, and produces the env that makes libtpu/JAX see ONLY
those chips (libtpu is single-process-per-chip-set with no virtualization —
partitioning must be airtight; SURVEY.md "hard parts").

Discovery order: explicit override (KUKEON_TPU_CHIPS — used by tests and CI
hosts without TPUs), /dev/accel* device nodes (TPU-VM), /dev/vfio groups.
"""

from __future__ import annotations

import glob
import os
import re

from kukeon_tpu.runtime.errors import FailedPrecondition
from kukeon_tpu.runtime.metadata import MetadataStore

ALLOC_FILE = "tpu-allocations.json"

# TPU_CHIPS_PER_PROCESS_BOUNDS by grant size on a 2x2-chip host (see
# TPUDeviceManager.visibility_env). Settled on a four-chip v5e host: chips
# 0,1 and 2,3 come up under "1,2,1" and fail under "2,1,1".
_GRANT_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def discover_chips() -> list[int]:
    override = os.environ.get("KUKEON_TPU_CHIPS")
    if override is not None:
        override = override.strip()
        if not override:
            return []
        return [int(x) for x in override.split(",")]
    nodes = glob.glob("/dev/accel*")
    chips = []
    for n in nodes:
        m = re.search(r"accel(?:_)?(\d+)$", n)
        if m:
            chips.append(int(m.group(1)))
    if chips:
        return sorted(chips)
    vfio = glob.glob("/dev/vfio/[0-9]*")
    return sorted(int(os.path.basename(v)) for v in vfio)


def _probe_fault() -> tuple[str, str] | None:
    """Fault seam shared by both probes: KUKEON_FAULTS=devices.probe_wedged:1
    makes a probe report a wedged runtime without needing a chip to
    actually wedge — the watchdog/restart path is tested by injection, not
    by timing."""
    from kukeon_tpu import faults

    try:
        faults.maybe_fail("devices.probe_wedged")
    except faults.FaultInjected as e:
        return "wedged", f"fault-injected: {e}"
    return None


def probe_tpu_runtime(timeout_s: float = 20.0) -> tuple[str, str]:
    """Live-runtime health probe for a process that holds NO chip (`kuke
    doctor`): ('ok'|'wedged'|'unavailable', detail).

    Visible device nodes prove nothing about the runtime plane — a wedged
    libtpu accepts the client and then blocks the first transfer forever.
    The probe runs a tiny device_put in a throwaway subprocess (only a
    subprocess is reliably killable mid-hang) and reports wall time, so
    `kuke doctor` distinguishes "no TPU" from "TPU present but the runtime
    is wedged". A chip belongs to one process at a time: from inside a
    process that already holds it the child can never open the device —
    that caller uses :func:`probe_tpu_in_process` instead."""
    import subprocess
    import sys

    injected = _probe_fault()
    if injected is not None:
        return injected

    code = (
        "import time, numpy, jax;"
        "t0 = time.monotonic();"
        "d = jax.device_put(numpy.ones((1024, 1024), numpy.int8));"
        "jax.block_until_ready(d);"
        "print(jax.default_backend(), round(time.monotonic() - t0, 2))"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return ("wedged",
                f"1MB device_put did not finish in {timeout_s:.0f}s "
                "(runtime hung — model cells will crash-loop)")
    if out.returncode != 0:
        err = out.stderr.strip().splitlines()
        return "unavailable", (err[-1][:200] if err else f"rc={out.returncode}")
    backend, dt = out.stdout.split()[-2:]
    if backend != "tpu" and discover_chips():
        # TPU init failed non-fatally and JAX fell back to another backend:
        # chips are visible but NOT usable — "ok backend=cpu" would read as
        # healthy while model cells pinned to the TPU crash-loop.
        return ("unavailable",
                f"chips visible but backend={backend} (TPU init failed; "
                "check libtpu / driver versions)")
    return "ok", f"backend={backend}, 1MB device_put in {dt}s"


def probe_tpu_in_process(timeout_s: float = 20.0) -> tuple[str, str]:
    """The same verdict from INSIDE the process that holds the chip (the
    serving cell's watchdog): a second process cannot open an attached
    chip its parent owns, so the question "does the runtime still answer"
    is put to this process's own client — a tiny host->device transfer
    issued from a probe-owned thread, waited on for at most ``timeout_s``.

    A transfer needs neither the engine thread nor a compile: it completes
    while the engine sits in a long jit compile or a long device program
    (slow, not wedged -> 'ok'), and it hangs exactly when the runtime
    does ('wedged'). A transfer that raises is 'unavailable': the engine
    loop's own error path owns that failure."""
    import threading
    import time

    injected = _probe_fault()
    if injected is not None:
        return injected

    done = threading.Event()
    result: dict[str, str] = {}

    def transfer():
        try:
            import jax
            import numpy

            t0 = time.monotonic()
            jax.block_until_ready(
                jax.device_put(numpy.ones((1024, 1024), numpy.int8)))
            result["ok"] = (f"backend={jax.default_backend()}, 1MB "
                            f"device_put in {time.monotonic() - t0:.2f}s")
        except Exception as e:  # noqa: BLE001 — the verdict carries it
            result["error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            done.set()

    # Daemon: a transfer that never returns must not keep the process
    # from exiting once the watchdog has its verdict.
    threading.Thread(target=transfer, daemon=True, name="tpu-probe").start()
    if not done.wait(timeout_s):
        return ("wedged",
                f"in-process 1MB device_put did not finish in "
                f"{timeout_s:.0f}s (runtime hung)")
    if "error" in result:
        return "unavailable", result["error"]
    return "ok", result["ok"]


class TPUDeviceManager:
    """Chip accounting, persisted so daemon restarts keep allocations."""

    def __init__(self, store: MetadataStore, chips: list[int] | None = None):
        self.store = store
        self.chips = chips if chips is not None else discover_chips()

    # allocations: {str(chip_id): "realm/space/stack/cell"}

    def _load(self) -> dict[str, str]:
        return self.store.read_json_or({}, ALLOC_FILE)

    def _save(self, allocs: dict[str, str]) -> None:
        self.store.write_json(allocs, ALLOC_FILE)

    def allocated(self) -> dict[int, str]:
        return {int(k): v for k, v in self._load().items()}

    def free_chips(self) -> list[int]:
        used = set(self.allocated())
        return [c for c in self.chips if c not in used]

    def allocate(self, owner: str, n: int) -> list[int]:
        """Grant n chips to ``owner`` (idempotent: an existing grant of the
        right size is returned as-is; a wrong-size grant is resized)."""
        with self.store.lock():
            allocs = self._load()
            mine = sorted(int(k) for k, v in allocs.items() if v == owner)
            if len(mine) == n:
                return mine
            for c in mine:   # resize: release then re-grant
                del allocs[str(c)]
            free = [c for c in self.chips if str(c) not in allocs]
            if len(free) < n:
                raise FailedPrecondition(
                    f"not enough TPU chips: want {n}, free {len(free)} of {len(self.chips)}"
                )
            grant = free[:n]
            for c in grant:
                allocs[str(c)] = owner
            self._save(allocs)
            return grant

    def release(self, owner: str) -> None:
        with self.store.lock():
            allocs = self._load()
            remaining = {k: v for k, v in allocs.items() if v != owner}
            if len(remaining) != len(allocs):
                self._save(remaining)

    @staticmethod
    def device_nodes(chips: list[int]) -> list[str]:
        """Host /dev nodes backing these chips (for namespace injection:
        the namespace backend's /dev contains ONLY what this returns plus
        the standard nodes — reference: internal/ctr/devices.go:23-171).
        Empty on hosts with no TPU device nodes (CPU hosts, fake-chip
        KUKEON_TPU_CHIPS overrides)."""
        out = []
        for c in chips:
            for cand in (f"/dev/accel{c}", f"/dev/accel_{c}", f"/dev/vfio/{c}"):
                if os.path.exists(cand):
                    out.append(cand)
        if out and os.path.exists("/dev/vfio/vfio"):
            out.append("/dev/vfio/vfio")
        return out

    @staticmethod
    def visibility_env(chips: list[int],
                       visible: list[int] | None = None) -> dict[str, str]:
        """Env that restricts libtpu/JAX to exactly these chips.

        TPU_VISIBLE_DEVICES is the libtpu chip-visibility knob on TPU-VMs.
        libtpu numbers the chips whose device nodes the process can open
        from 0, in node order — so the variable names POSITIONS in that
        list, not node numbers (on a host whose only node is /dev/vfio/2
        the chip is device 0; asking for "2" finds no device).
        ``visible`` is that list: every chip of the host for a process
        that sees the host's /dev, and — the default — the grant itself
        for a namespaced cell, whose /dev holds only the granted nodes.

        TPU_CHIPS_PER_PROCESS_BOUNDS/TPU_PROCESS_BOUNDS pin the topology of
        the subset (the several-processes-per-host recipe): one process,
        whose chips are a rectangle of the host's chip grid. A v5e host's
        four chips are a 2x2 grid and the allocator grants consecutive
        ids — a pair (0,1 or 2,3) is one column of it, four are the whole
        grid. Other sizes get visibility only.
        """
        order = sorted(visible if visible is not None else chips)
        env = {"TPU_VISIBLE_DEVICES":
               ",".join(str(order.index(c)) for c in chips)}
        bounds = _GRANT_BOUNDS.get(len(chips))
        if bounds:
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        return env
