"""The imperative resource engine beneath the controller.

Reference: internal/controller/runner (provision.go, start.go, refresh.go,
cell_lock.go — 33.7k LoC of Go). Responsibilities here:

- provision realm/space/stack/cell trees (metadata dirs + cgroups),
- cell lifecycle: create/start/stop/kill/delete with per-cell locking and a
  10s SIGTERM->SIGKILL stop window (reference: ctr/container.go:173),
- TPU chip affinity: allocate chips at start, inject visibility env,
  release at stop (the libtpu device-manager seam, BASELINE north star),
- secret staging (files 0400 + env injection; reference ctr/secrets.go),
- model cells: materialize the in-tree serving container,
- refresh: re-derive status from the backend, enforce restart policy
  (always/on-failure/never + backoff + max retries; refresh.go:1110-1458)
  and AutoDelete reaping.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

from kukeon_tpu import obs, sanitize
from kukeon_tpu.runtime import consts, model
from kukeon_tpu.runtime.api import types as t
from kukeon_tpu.runtime.cells.backend import CellBackend, ContainerContext
from kukeon_tpu.runtime.cgroups import CgroupManager
from kukeon_tpu.runtime.devices import TPUDeviceManager
from kukeon_tpu.runtime.errors import (
    DiskPressure,
    FailedPrecondition,
    InvalidArgument,
    NotFound,
)
from kukeon_tpu.runtime.store import ResourceStore

# Reconcile outcomes (reference: runner/runner.go:33-56).
OUTCOME_STEADY = "steady"
OUTCOME_HEALED = "healed"
OUTCOME_RESTARTED = "restarted"
OUTCOME_AUTO_DELETED = "auto-deleted"
OUTCOME_VANISHED = "vanished"


@dataclasses.dataclass
class RunnerOptions:
    stop_grace_s: float = consts.DEFAULT_STOP_GRACE_S
    disk_pressure_block_pct: float = consts.DISK_PRESSURE_BLOCK_PCT
    serving_python: str = sys.executable


@sanitize.guard_class
class Runner:
    def __init__(
        self,
        store: ResourceStore,
        backend: CellBackend,
        cgroups: CgroupManager | None = None,
        devices: TPUDeviceManager | None = None,
        options: RunnerOptions | None = None,
        netman=None,
        registry: "obs.Registry | None" = None,
    ):
        self.store = store
        self.backend = backend
        self.cgroups = cgroups
        self.devices = devices or TPUDeviceManager(store.ms, chips=[])
        self.opts = options or RunnerOptions()
        self.netman = netman
        self._cell_locks: dict[tuple, threading.Lock] = {}
        self._locks_guard = sanitize.lock("Runner._locks_guard")
        # (owner, container, repo idx) -> last failed clone attempt time.
        self._repo_failures: dict[tuple, float] = {}
        # Cell-lifecycle metrics (daemon Metrics RPC / `kuke daemon
        # metrics` scrape them). Default registry is process-global: one
        # daemon process, one scrape; tests inject a fresh Registry.
        self.registry = registry or obs.get_default()
        reg = self.registry
        self._m_cell_starts = reg.counter(
            "kukeon_runner_cell_starts_total",
            "Cell start operations (initial starts; restarts count "
            "separately).", labels=("cell",))
        self._m_restarts = reg.counter(
            "kukeon_runner_container_restarts_total",
            "Restart-policy container restarts.",
            labels=("cell", "container"))
        self._m_exits = reg.counter(
            "kukeon_runner_container_exits_total",
            "Observed container exits by exit code.",
            labels=("cell", "container", "code"))
        self._m_uptime = reg.gauge(
            "kukeon_runner_container_uptime_seconds",
            "Continuous uptime of a running container (refreshed every "
            "reconcile tick; 0 when not running).",
            labels=("cell", "container"))
        self._m_backoff = reg.gauge(
            "kukeon_runner_restart_backoff_seconds",
            "Remaining restart backoff for an exited container "
            "(0 = no restart pending).", labels=("cell", "container"))
        self._m_exhausted = reg.gauge(
            "kukeon_runner_restart_budget_exhausted",
            "1 when a container crash-looped past restartMaxRetries.",
            labels=("cell", "container"))
        reg.register_collector(obs.faults_collector)

    # --- locking (reference: runner/cell_lock.go) --------------------------

    def cell_lock(self, realm: str, space: str, stack: str, cell: str) -> threading.Lock:
        # Every cell's lock shares ONE sanitizer identity
        # ("Runner._cell_locks"): the lock-order graph aggregates the
        # family into a single node (same-name edges are skipped, so
        # nesting two different cells' locks is invisible to kukesan —
        # an accepted blind spot; nothing in the runner nests them).
        key = (realm, space, stack, cell)
        with self._locks_guard:
            lk = self._cell_locks.get(key)
            if lk is None:
                lk = sanitize.lock("Runner._cell_locks")
                self._cell_locks[key] = lk
            return lk

    # --- provisioning ------------------------------------------------------

    def ensure_realm(self, name: str, spec: t.RealmSpec | None = None,
                     labels: dict | None = None) -> None:
        self.store.ms.ensure_dir(*self.store.realm_parts(name))
        if not self.store.ms.exists(*self.store.realm_parts(name), "realm.json"):
            rec = model.ScopeRecord(kind="Realm", name=name, labels=labels or {},
                                    spec_json=model.spec_to_json(spec or t.RealmSpec()))
            self.store.write_scope(rec)
        if self.cgroups:
            self.cgroups.ensure(name)

    def ensure_space(self, realm: str, name: str, spec: t.SpaceSpec | None = None,
                     labels: dict | None = None) -> None:
        self.store.read_realm(realm)
        self.store.ms.ensure_dir(*self.store.space_parts(realm, name))
        existing = self.store.ms.read_json_or(None, *self.store.space_parts(realm, name), "space.json")
        if existing is None or spec is not None:
            # Provision the network BEFORE persisting the spec: a rejected
            # subnet change must not leave a stored spec the reconcile loop
            # can never converge on.
            if self.netman is not None:
                self.netman.ensure_space_network(realm, name, spec or t.SpaceSpec())
            rec = model.ScopeRecord(kind="Space", name=name, realm=realm,
                                    labels=labels or {},
                                    spec_json=model.spec_to_json(spec or t.SpaceSpec()))
            self.store.write_scope(rec)
        if self.cgroups:
            self.cgroups.ensure(realm, name)

    def teardown_space_network(self, realm: str, name: str,
                               spec: t.SpaceSpec | None = None) -> None:
        if self.netman is not None:
            self.netman.teardown_space_network(realm, name, spec)

    def ensure_stack(self, realm: str, space: str, name: str,
                     spec: t.StackSpec | None = None, labels: dict | None = None) -> None:
        self.store.read_space(realm, space)
        self.store.ms.ensure_dir(*self.store.stack_parts(realm, space, name))
        if not self.store.ms.exists(*self.store.stack_parts(realm, space, name), "stack.json"):
            rec = model.ScopeRecord(kind="Stack", name=name, realm=realm, space=space,
                                    labels=labels or {},
                                    spec_json=model.spec_to_json(spec or t.StackSpec()))
            self.store.write_scope(rec)
        if self.cgroups:
            self.cgroups.ensure(realm, space, name)

    # --- disk pressure (reference: runner/create_cell.go:166) --------------

    def guard_disk_pressure(self, ignore: bool = False) -> None:
        if ignore:
            return
        try:
            st = os.statvfs(self.store.ms.root)
        except OSError:
            return
        used_pct = 100.0 * (1 - st.f_bavail / max(st.f_blocks, 1))
        if used_pct >= self.opts.disk_pressure_block_pct:
            raise DiskPressure(
                f"disk {used_pct:.1f}% full >= block threshold "
                f"{self.opts.disk_pressure_block_pct}%; refusing new cells"
            )

    # --- cell lifecycle ----------------------------------------------------

    # --- host-port registry -------------------------------------------------
    #
    # Host-network containers (and host-network model cells) bind REAL host
    # ports; two cells claiming the same port would fail at runtime with an
    # unhelpful EADDRINUSE inside the workload. The registry makes the claim
    # at create, where it can be rejected with a pointer to the holder
    # (VERDICT r3 item 7). Isolated cells need no claim: their ports live on
    # the cell IP in the sandbox netns.

    def _host_ports_of(self, rec: model.CellRecord) -> list[str]:
        ports: list[str] = []
        for c in self.cell_containers(rec):
            if not c.host_network:
                continue
            for p in c.ports:
                ports.append(f"{p.port}/{(p.protocol or 'tcp').lower()}")
        return ports

    def claim_host_ports(self, rec: model.CellRecord) -> None:
        ports = self._host_ports_of(rec)
        owner = self._owner_key(rec)
        with self.store.ms.lock():
            claims = self.store.ms.read_json_or({}, consts.HOST_PORTS_FILE)
            # Re-claim from scratch: an update that drops a port must also
            # drop its claim.
            claims = {k: o for k, o in claims.items() if o != owner}
            for key in ports:
                holder = claims.get(key)
                if holder is not None:
                    raise FailedPrecondition(
                        f"host port {key} already claimed by cell {holder}"
                    )
                claims[key] = owner
            self.store.ms.write_json(claims, consts.HOST_PORTS_FILE)

    def _release_host_ports(self, rec: model.CellRecord) -> None:
        owner = self._owner_key(rec)
        with self.store.ms.lock():
            claims = self.store.ms.read_json_or({}, consts.HOST_PORTS_FILE)
            remaining = {k: o for k, o in claims.items() if o != owner}
            if len(remaining) != len(claims):
                self.store.ms.write_json(remaining, consts.HOST_PORTS_FILE)

    def create_cell(self, rec: model.CellRecord) -> model.CellRecord:
        with self.cell_lock(rec.realm, rec.space, rec.stack, rec.name):
            self.store.read_stack(rec.realm, rec.space, rec.stack)
            self.guard_disk_pressure(rec.spec.ignore_disk_pressure)
            self.claim_host_ports(rec)
            try:
                self.store.ms.ensure_dir(
                    *self.store.cell_parts(rec.realm, rec.space, rec.stack, rec.name)
                )
                if self.cgroups:
                    self.cgroups.ensure(rec.realm, rec.space, rec.stack, rec.name)
                rec.status = model.CellStatus(
                    phase=model.PENDING,
                    containers=[
                        model.ContainerStatus(name=c.name)
                        for c in self.cell_containers(rec)
                    ],
                )
                self.store.write_cell(rec)
            except Exception:
                # A failed create must not strand its port claims: the cell
                # record does not exist, so no delete will ever release them.
                self._release_host_ports(rec)
                raise
            return rec

    def cell_containers(self, rec: model.CellRecord) -> list[t.ContainerSpec]:
        """Declared containers plus the materialized serving container(s)
        for model cells (N replicas + a gateway when ``replicas > 1``)."""
        containers = list(rec.spec.containers)
        if rec.spec.model is not None:
            containers.extend(self._model_containers(rec.spec.model))
        return containers

    def _model_containers(self, m: t.ModelSpec) -> list[t.ContainerSpec]:
        """The base-port scheme: a single engine keeps today's shape (one
        ``model-server`` on ``m.port``); ``replicas: N`` materializes
        ``model-server-0..N-1`` on ``port+1..port+N`` (each with its own
        ``chips`` grant — declaration order partitions the cell's chips
        deterministically, so a restarted replica gets ITS chips back) plus
        one chip-less ``gateway`` container on ``m.port`` so the
        client-facing endpoint never moves. An autoscaled cell
        (``maxReplicas``) materializes the FULL bound — replicas above the
        active target stay parked (never started) but keep their name,
        port, and chip slice, so the scaler's scale-up is just "start
        container i on its grant", never a re-partition."""
        from kukeon_tpu.runtime.apply.validate import (
            model_roles,
            model_scale_bound,
        )

        n = model_scale_bound(m)
        roles = model_roles(m)
        if n <= 1:
            return [self._model_container(m, role=roles[0])]
        out = [
            self._model_container(
                m, name=f"model-server-{i}", port=m.port + 1 + i,
                # Autoscaled cells are validated role="mixed"; a static
                # replica set keeps its per-replica role atoms.
                role=roles[i] if i < len(roles) else "mixed")
            for i in range(n)
        ]
        out.append(self._gateway_container(m))
        return out

    def _gateway_container(self, m: t.ModelSpec) -> t.ContainerSpec:
        cmd = [
            self.opts.serving_python, "-m", "kukeon_tpu.gateway.cell",
            "--model", m.model, "--port", str(m.port),
        ]
        if not m.host_network and self.backend.isolated:
            cmd += ["--host", "0.0.0.0"]
        # Replicas share the cell's netns (or the host loopback on the
        # process backend), so the gateway always reaches them on 127.0.0.1.
        # The gateway learns the FULL scale bound: a parked replica simply
        # polls unready until the scaler starts it, then joins rotation on
        # the next poll tick with no gateway restart.
        from kukeon_tpu.runtime.apply.validate import model_scale_bound

        for i in range(model_scale_bound(m)):
            cmd += ["--replica", f"http://127.0.0.1:{m.port + 1 + i}"]
        return t.ContainerSpec(
            name="gateway",
            command=cmd,
            restart_policy=t.RestartPolicy(policy="always",
                                           backoff_seconds=1.0),
            ports=[t.PortSpec(port=m.port, name="http")],
            host_network=m.host_network,
        )

    def _model_container(self, m: t.ModelSpec, *, name: str = "model-server",
                         port: int | None = None,
                         role: str = "mixed") -> t.ContainerSpec:
        port = m.port if port is None else port
        cmd = [
            self.opts.serving_python, "-m", "kukeon_tpu.runtime.serving_cell",
            "--model", m.model, "--port", str(port),
            "--num-slots", str(m.num_slots),
        ]
        if role != "mixed":
            # Disaggregation role (per replica, declaration order). The
            # gateway discovers pools from each cell's /v1/stats census, so
            # the gateway container itself needs no role flags.
            cmd += ["--role", role]
        if not m.host_network and self.backend.isolated:
            # In-space serving: bind all interfaces so in-space clients reach
            # the server on the cell's bridge IP (the sandbox netns has no
            # other route in); the space's default-deny egress still governs
            # every packet the cell originates (BASELINE config 4). Gated on
            # isolation: on the process backend 0.0.0.0 would be the REAL
            # host interfaces — strictly wider than the loopback default.
            cmd += ["--host", "0.0.0.0"]
        if m.max_seq_len:
            cmd += ["--max-seq-len", str(m.max_seq_len)]
        if m.checkpoint:
            cmd += ["--checkpoint", m.checkpoint]
        if m.dtype:
            cmd += ["--dtype", m.dtype]
        if m.kv_cache_int8:
            cmd += ["--kv-cache-int8"]
        if m.kv_page_tokens is not None:
            # 0 is meaningful (pin the legacy contiguous layout even when a
            # tuning profile prefers pages) — pass it through.
            cmd += ["--kv-page-tokens", str(m.kv_page_tokens)]
        if m.max_pending is not None:
            # 0 is meaningful (explicit unbounded opt-out) — pass it through.
            cmd += ["--max-pending", str(m.max_pending)]
        if m.deadline_s:
            cmd += ["--deadline-s", str(m.deadline_s)]
        if m.slo_ttft_p95_ms:
            cmd += ["--slo-ttft-p95-ms", str(m.slo_ttft_p95_ms)]
        if m.slo_availability:
            cmd += ["--slo-availability", str(m.slo_availability)]
        # The chip grant is always explicit: the cell builds an exactly-N
        # serving mesh (parallel/mesh.serving_mesh) instead of auto-meshing
        # over whatever it can see. On TPU hosts TPU_VISIBLE_DEVICES already
        # narrows visibility to the grant; on CPU hosts (forced multi-device
        # smokes) this flag is the only thing that makes the grant real.
        cmd += ["--chips", str(m.chips)]
        return t.ContainerSpec(
            name=name,
            command=cmd,
            resources=t.Resources(tpu_chips=m.chips),
            restart_policy=t.RestartPolicy(policy="always", backoff_seconds=2.0),
            ports=[t.PortSpec(port=port, name="http")],
            # Spec-visible decision (ModelSpec.host_network): default is the
            # space network + egress policy; true exempts the cell for hosts
            # whose TPU runtime plane requires the host net.
            host_network=m.host_network,
        )

    def _owner_key(self, rec: model.CellRecord) -> str:
        return f"{rec.realm}/{rec.space}/{rec.stack}/{rec.name}"

    @staticmethod
    def model_target(rec: model.CellRecord) -> int:
        """The ACTIVE replica count of a model cell: the scaler-written
        ``status.target_replicas`` when set, else the spec's static
        ``replicas`` — always clamped into [minReplicas, scale bound] so a
        stale record can never park the whole fleet or start past the
        bound."""
        from kukeon_tpu.runtime.apply.validate import model_scale_bound

        m = rec.spec.model
        if m is None:
            return 0
        bound = model_scale_bound(m)
        target = rec.status.target_replicas
        if target is None:
            target = m.replicas or 1
        return max(max(1, m.min_replicas or 1), min(target, bound))

    def _parked_names(self, rec: model.CellRecord) -> set[str]:
        """Container names of replicas scaled out of the active range:
        materialized (name/port/chip slice reserved) but intentionally not
        running — start, heal, and phase derivation all skip them."""
        from kukeon_tpu.runtime.apply.validate import model_scale_bound

        m = rec.spec.model
        if m is None:
            return set()
        bound = model_scale_bound(m)
        if bound <= 1:
            return set()
        target = self.model_target(rec)
        return {f"model-server-{i}" for i in range(target, bound)}

    def start_cell(self, realm: str, space: str, stack: str, name: str) -> model.CellRecord:
        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            return self._start_cell_locked(rec)

    def _start_cell_locked(self, rec: model.CellRecord) -> model.CellRecord:
        containers = self.cell_containers(rec)
        # Multi-chip composition check (validate_cell is static and cannot
        # see the host): a grant that does not divide the host's chip count
        # can never partition into whole N-chip replica slices — fail loudly
        # here instead of letting a later replica starve mid-scale-up.
        m = rec.spec.model
        host_chips = len(self.devices.chips)
        if m is not None and m.chips > 1 and host_chips % m.chips:
            raise FailedPrecondition(
                f"model chip grant chips={m.chips} does not divide this "
                f"host's {host_chips} chips; replicas cannot partition into "
                "whole slices"
            )
        total_chips = sum(
            c.resources.tpu_chips or 0 for c in containers
        )
        chips: list[int] = []
        if total_chips:
            chips = self.devices.allocate(self._owner_key(rec), total_chips)
        rec.status.tpu_chips = chips
        self._ensure_cell_network(rec)

        slices = self._chip_slices(containers, chips)
        parked = self._parked_names(rec)
        new_statuses = []
        for spec in containers:
            ctx = self._container_context(rec, spec)
            grant = slices.get(spec.name, [])
            if grant:
                self._grant_chips(ctx, grant)
            st = rec.status.container(spec.name) or model.ContainerStatus(name=spec.name)
            live = self.backend.container_state(ctx)
            if not live.running and spec.name not in parked:
                self.backend.start_container(ctx)
                live = self.backend.container_state(ctx)
                st.started_at = time.time()
            st.state = live.state
            st.pid = live.pid
            st.exit_code = live.exit_code
            new_statuses.append(st)

        rec.status.containers = new_statuses
        rec.desired_state = "running"
        self._derive_phase(rec)
        self.store.write_cell(rec)
        self._m_cell_starts.inc(cell=self._owner_key(rec))
        return rec

    def _grant_chips(self, ctx: ContainerContext, grant: list[int]) -> None:
        """Make a container's chip grant real: the env that restricts
        libtpu to those chips, and the device nodes a namespaced cell's
        /dev will hold. A process-backend container sees the host's whole
        /dev, a namespaced one only its grant — which is what libtpu's
        device numbering counts (TPUDeviceManager.visibility_env)."""
        ctx.env.update(self.devices.visibility_env(
            grant, None if self.backend.isolated else self.devices.chips))
        ctx.devices = self.devices.device_nodes(grant)

    @staticmethod
    def _chip_slices(containers: list[t.ContainerSpec], chips: list[int]) -> dict[str, list[int]]:
        """Deterministic per-container chip assignment: declaration order
        partitions the cell's grant. Start and restart paths share this so a
        restarted container gets back ITS chips, not a sibling's."""
        out: dict[str, list[int]] = {}
        cursor = 0
        for spec in containers:
            n = spec.resources.tpu_chips or 0
            if n:
                out[spec.name] = chips[cursor : cursor + n]
                cursor += n
        return out

    def _cell_dir(self, rec: model.CellRecord) -> str:
        return self.store.ms.ensure_dir(
            *self.store.cell_parts(rec.realm, rec.space, rec.stack, rec.name)
        )

    def _ensure_cell_network(self, rec: model.CellRecord) -> None:
        """Attach the cell's sandbox netns to its space bridge (idempotent;
        reference: CNI ADD on cell start, runner/start.go:474-560)."""
        if not self.backend.isolated:
            return
        containers = self.cell_containers(rec)
        if containers and all(c.host_network for c in containers):
            # Nothing will use the sandbox netns; don't burn a bridge IP or
            # publish an address nothing listens on.
            return
        if self.netman is None or not self.netman.enforcing:
            # The sandbox netns exists but no bridge will ever reach it: a
            # Ready cell with a server bound in a disconnected netns is a
            # dead end that MUST be named in status (a silent no-IP cell is
            # undebuggable; use hostNetwork or enable net enforcement).
            rec.status.reason = (
                "cell is network-isolated but net enforcement is off: no "
                "bridge/IP will be attached (set hostNetwork: true or run "
                "with root + iptables/kukenet)"
            )
            return
        try:
            pid = self.backend.ensure_sandbox(self._cell_dir(rec), rec.name)
            rec.status.ip = self.netman.attach_cell(
                rec.realm, rec.space, self._owner_key(rec), pid
            )
            if rec.status.reason and rec.status.reason.startswith("network attach failed"):
                rec.status.reason = None
        except Exception as e:  # noqa: BLE001 — cells without a bridge still run
            import logging

            logging.getLogger("kukeon.runner").warning(
                "cell network attach failed for %s: %s", rec.name, e
            )
            # Surface the failure: a Ready cell with no IP and no recorded
            # reason is undebuggable from `kuke get/status` (VERDICT r3
            # weak 5). The record is written by the caller's status flush.
            rec.status.reason = f"network attach failed: {e}"

    def _container_context(self, rec: model.CellRecord, spec: t.ContainerSpec) -> ContainerContext:
        cdir = self.store.container_dir(rec.realm, rec.space, rec.stack, rec.name, spec.name)
        env: dict[str, str] = {
            "KUKEON_REALM": rec.realm,
            "KUKEON_SPACE": rec.space,
            "KUKEON_STACK": rec.stack,
            "KUKEON_CELL": rec.name,
            "KUKEON_CONTAINER": spec.name,
        }
        image_entrypoint: list[str] = []
        image_cmd: list[str] = []
        workdir = spec.workdir
        if spec.image:
            # Image-backed container: inherit the image's env/entry/workdir
            # (spec wins on conflict) + expose the bundle tree.
            from kukeon_tpu.runtime.images import ImageStore

            istore = ImageStore(self.store.ms.root)
            manifest = istore.get(spec.image)
            env.update(manifest.env)
            env["KUKEON_IMAGE"] = manifest.ref
            env["KUKEON_IMAGE_ROOTFS"] = istore.rootfs(manifest.ref)
            image_entrypoint = list(manifest.entrypoint)
            image_cmd = list(manifest.cmd)
            workdir = workdir or manifest.workdir or None
        for e in spec.env:
            env[e.name] = e.value
        binds: list[tuple[str, str, bool]] = []
        tmpfs: list[str] = []
        self._stage_secrets(rec, spec, cdir, env, binds)
        self._mount_volumes(rec, spec, cdir, env, binds, tmpfs)

        sandbox_pid = None
        if self.backend.isolated:
            # Cell-shared namespace set (idempotent; restart-safe pid file).
            sandbox_pid = self.backend.ensure_sandbox(self._cell_dir(rec), rec.name)

        cgroup_dir = None
        if self.cgroups and self.cgroups.available():
            cgroup_dir = self.cgroups.ensure(
                rec.realm, rec.space, rec.stack, rec.name, spec.name
            )
            self.cgroups.apply_limits(
                cgroup_dir,
                memory=spec.resources.memory,
                cpu=spec.resources.cpu,
                pids=spec.resources.pids,
            )
        self._stage_repos(rec, spec, cdir, env, binds)

        command = list(spec.command) + list(spec.args)
        if not spec.command and spec.image:
            # Docker/k8s semantics: spec.args replaces the image CMD while
            # keeping its entrypoint; with no args, entrypoint+cmd run.
            if spec.args:
                command = image_entrypoint + list(spec.args)
            else:
                command = image_entrypoint + image_cmd
        return ContainerContext(
            container_dir=cdir,
            spec=spec,
            env=env,
            command=command,
            cgroup_dir=cgroup_dir,
            workdir=workdir,
            sandbox_pid=sandbox_pid,
            binds=binds,
            tmpfs=tmpfs,
        )

    def _stage_secrets(self, rec: model.CellRecord, spec: t.ContainerSpec,
                       cdir: str, env: dict[str, str],
                       binds: list[tuple[str, str, bool]]) -> None:
        """Stage referenced secrets (reference: ctr/secrets.go:30-60,
        mode 0400) and/or export env vars. Under the namespace backend the
        staged file is bind-mounted read-only at its in-cell path
        (/run/kukeon/secrets/<name>.env or ref.path); the env pointer then
        names the in-cell path."""
        if not spec.secrets:
            return
        isolated = self.backend.isolated
        sdir = os.path.join(cdir, "secrets")
        os.makedirs(sdir, mode=0o700, exist_ok=True)
        for ref in spec.secrets:
            doc = self.store.resolve_scoped(
                consts.SECRETS_DIR, rec.realm, rec.space, rec.stack, ref.name
            )
            if doc is None:
                raise NotFound(
                    f"secret {ref.name!r} not found in scope "
                    f"{rec.realm}/{rec.space}/{rec.stack}"
                )
            data: dict[str, str] = doc.get("data", {})
            if ref.env:
                if len(data) == 1:
                    env[ref.env] = next(iter(data.values()))
                else:
                    for k, v in data.items():
                        env[f"{ref.env}_{k}"] = v
            staged = os.path.join(sdir, f"{ref.name}.env")
            if not isolated and ref.path:
                # Process backend honors an explicit host staging path.
                staged = ref.path
            content = "".join(f"{k}={v}\n" for k, v in sorted(data.items()))
            # The staged file is 0400; restaging (stop/start, restart policy)
            # must replace it, not reopen it (O_TRUNC on a 0400 file EACCESes
            # for non-root daemons).
            try:
                os.unlink(staged)
            except FileNotFoundError:
                pass
            fd = os.open(staged, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o400)
            try:
                os.write(fd, content.encode())
            finally:
                os.close(fd)
            cell_path = staged
            if isolated:
                cell_path = ref.path or os.path.join(
                    consts.SECRETS_MOUNT, f"{ref.name}.env"
                )
                binds.append((staged, cell_path, True))
            env[f"KUKEON_SECRET_{ref.name.upper().replace('-', '_')}"] = cell_path

    def _stage_repos(self, rec: model.CellRecord, spec: t.ContainerSpec,
                     cdir: str, env: dict[str, str],
                     binds: list[tuple[str, str, bool]]) -> None:
        """Pre-start git clone of declared repos, with setup-status reporting
        (reference: cmd/kuketty/repos.go clone stages +
        internal/kuketty/setupstatus typed reports).

        Clones land under the container dir and are bind-mounted at the
        declared in-cell path (namespace backend) or exposed via env pointer
        (process backend). Failures are REPORTED, not fatal: the cell still
        starts and `kuke get` shows state=failed with the git error, matching
        the reference's report-don't-block stage semantics. Existing clones
        are reused (restart-safe)."""
        if not spec.repos:
            return
        import subprocess

        rdir = os.path.join(cdir, "repos")
        os.makedirs(rdir, exist_ok=True)
        # Drop stale entries for this container (restart rewrites them).
        rec.status.setup = [
            s for s in rec.status.setup if s.container != spec.name
        ]
        for i, repo in enumerate(spec.repos):
            st = model.SetupStatus(
                container=spec.name, url=repo.url, path=repo.path,
                state="cloning",
            )
            rec.status.setup.append(st)
            base = os.path.basename(repo.path.rstrip("/")) or f"repo{i}"
            dest = os.path.join(rdir, f"{i}-{base}")
            # Failure cache: clone runs under the cell lock, and the restart
            # path re-enters here from the reconcile tick — a dead remote
            # must not stall daemon-wide supervision for its full timeout on
            # EVERY restart of a crash-looping sibling.
            # url/ref in the key: editing the spec to fix a bad repo must
            # bust the cache immediately, not serve the stale failure.
            fail_key = (self._owner_key(rec), spec.name, i, repo.url, repo.ref)
            last = self._repo_failures.get(fail_key, 0.0)
            if time.time() - last < consts.REPO_RETRY_SECONDS:
                st.state = "failed"
                st.error = "previous clone attempt failed; retry pending"
                continue
            try:
                if not os.path.isdir(os.path.join(dest, ".git")):
                    # `--`: a dash-prefixed url/dest must never parse as a
                    # git option (defense in depth; validate.py rejects them).
                    p = subprocess.run(
                        ["git", "clone", "--", repo.url, dest],
                        capture_output=True, text=True,
                        timeout=consts.REPO_CLONE_TIMEOUT_S,
                    )
                    if p.returncode != 0:
                        raise RuntimeError(p.stderr.strip()[-500:])
                if repo.ref:
                    p = subprocess.run(
                        ["git", "-C", dest, "checkout", "--quiet", repo.ref],
                        capture_output=True, text=True, timeout=60,
                    )
                    if p.returncode != 0:
                        raise RuntimeError(p.stderr.strip()[-500:])
                st.state = "ready"
                self._repo_failures.pop(fail_key, None)
            except (RuntimeError, OSError, subprocess.TimeoutExpired) as e:
                st.state = "failed"
                st.error = str(e)
                self._repo_failures[fail_key] = time.time()
                continue
            key = f"KUKEON_REPO_{i}"
            if self.backend.isolated:
                binds.append((dest, repo.path, False))
                env[key] = repo.path
            else:
                env[key] = dest
        # In-cell setup-status report, as the reference's kuketty writes for
        # attach clients; bound read-only at a fixed path.
        status_file = os.path.join(cdir, consts.SETUP_STATUS_FILE)
        with open(status_file, "w") as f:
            import json

            json.dump([dataclasses.asdict(s) for s in rec.status.setup
                       if s.container == spec.name], f, indent=1)
        if self.backend.isolated:
            binds.append((status_file, consts.SETUP_STATUS_MOUNT, True))

    def _mount_volumes(self, rec: model.CellRecord, spec: t.ContainerSpec,
                       cdir: str, env: dict[str, str],
                       binds: list[tuple[str, str, bool]],
                       tmpfs: list[str] | None = None) -> None:
        """Volume binding. Namespace backend: real bind mounts at the
        declared in-cell path honoring read_only, and tmpfs paths as real
        private tmpfs mounts (reference: ctr/spec.go volume + tmpfs
        mounts). Process backend: env pointer / scratch-dir fallback."""
        import shutil as _shutil

        tmpfs = tmpfs if tmpfs is not None else []
        for idx, vm in enumerate(spec.volumes):
            if vm.tmpfs:
                if self.backend.isolated:
                    tmpfs.append(vm.path)
                else:
                    # Process backend has no mount namespace: a private
                    # scratch dir (wiped each start) + env pointer. Indexed
                    # dir names: path mangling is lossy (/a/b vs /a-b) and
                    # colliding scratch dirs would alias "private" mounts.
                    scratch = os.path.join(cdir, f"tmpfs-{idx}")
                    _shutil.rmtree(scratch, ignore_errors=True)
                    os.makedirs(scratch, exist_ok=True)
                    env[f"KUKEON_TMPFS_{idx}"] = scratch
                continue
            if vm.host_path and self.backend.isolated:
                # Direct host bind (trusted manifests only).
                if vm.path:
                    binds.append((vm.host_path, vm.path, vm.read_only))
                continue
            if vm.name is None:
                continue
            vol = self.store.resolve_scoped(
                consts.VOLUMES_DIR + "-meta", rec.realm, rec.space, rec.stack, vm.name
            ) or self.store.resolve_scoped(
                consts.VOLUMES_DIR, rec.realm, rec.space, rec.stack, vm.name
            )
            if vol is None:
                raise NotFound(f"volume {vm.name!r} not found in scope")
            data_dir = vol.get("dataDir")
            if data_dir:
                key = f"KUKEON_VOLUME_{vm.name.upper().replace('-', '_')}"
                env[key] = data_dir
                if self.backend.isolated:
                    # Image-backed cells lose host-path visibility after
                    # pivot_root, so a path-less volume gets a default
                    # in-cell mount point; host-rootfs cells without an
                    # explicit path keep the host dir via env.
                    path = vm.path or (f"/mnt/{vm.name}" if spec.image else None)
                    if path:
                        binds.append((data_dir, path, vm.read_only))
                        env[key] = path

    def stop_cell(self, realm: str, space: str, stack: str, name: str,
                  grace_s: float | None = None) -> model.CellRecord:
        import signal as _signal

        grace = self.opts.stop_grace_s if grace_s is None else grace_s
        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            contexts = [
                self._container_context_bare(rec, spec)
                for spec in self.cell_containers(rec)
            ]
            for ctx in contexts:
                if self.backend.container_state(ctx).running:
                    self.backend.signal_container(ctx, _signal.SIGTERM)
            deadline = time.monotonic() + grace
            while time.monotonic() < deadline:
                if not any(self.backend.container_state(c).running for c in contexts):
                    break
                time.sleep(0.05)
            for ctx in contexts:
                if self.backend.container_state(ctx).running:
                    self.backend.signal_container(ctx, _signal.SIGKILL)
            self._finish_stop(rec, contexts)
            return rec

    def kill_cell(self, realm: str, space: str, stack: str, name: str) -> model.CellRecord:
        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            return self._kill_cell_locked(rec)

    def _kill_cell_locked(self, rec: model.CellRecord) -> model.CellRecord:
        import signal as _signal

        contexts = [
            self._container_context_bare(rec, spec)
            for spec in self.cell_containers(rec)
        ]
        for ctx in contexts:
            if self.backend.container_state(ctx).running:
                self.backend.signal_container(ctx, _signal.SIGKILL)
        self._finish_stop(rec, contexts)
        return rec

    def restart_container(self, realm: str, space: str, stack: str,
                          name: str, container: str) -> model.CellRecord:
        """Immediate single-container restart on the SAME chip grant — the
        rolling-restart primitive (`kuke rollout`). Unlike the reconcile
        path this honors no backoff: the caller already drained the replica
        and is gating on /readyz, so waiting out a crash-loop damper would
        only stretch the capacity hole. A container still running (drain
        wedged short of exit) gets the stop grace window, then SIGKILL —
        the drain already emptied it."""
        import signal as _signal

        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            containers = self.cell_containers(rec)
            spec = next((c for c in containers if c.name == container), None)
            if spec is None:
                raise NotFound(
                    f"container {container!r} not found in cell {name!r}"
                )
            bare = self._container_context_bare(rec, spec)
            if self.backend.container_state(bare).running:
                self.backend.signal_container(bare, _signal.SIGTERM)
                deadline = time.monotonic() + self.opts.stop_grace_s
                while (time.monotonic() < deadline
                       and self.backend.container_state(bare).running):
                    time.sleep(0.05)
                if self.backend.container_state(bare).running:
                    self.backend.signal_container(bare, _signal.SIGKILL)
            self._ensure_cell_network(rec)
            ctx = self._container_context(rec, spec)
            grant = self._chip_slices(containers,
                                      rec.status.tpu_chips).get(spec.name, [])
            if grant:
                # The cell's grant partition is deterministic by declaration
                # order: the replica comes back on exactly its chips.
                self._grant_chips(ctx, grant)
            self.backend.start_container(ctx)
            live = self.backend.container_state(ctx)
            st = rec.status.container(spec.name)
            if st is None:
                st = model.ContainerStatus(name=spec.name)
                rec.status.containers.append(st)
            st.state = live.state
            st.pid = live.pid
            st.exit_code = live.exit_code
            st.restarts += 1
            st.last_restart_at = time.time()
            st.finished_at = None
            self._m_restarts.inc(cell=self._owner_key(rec),
                                 container=spec.name)
            self._derive_phase(rec)
            self.store.write_cell(rec)
            return rec

    def scale_model_cell(self, realm: str, space: str, stack: str,
                         name: str, target: int) -> model.CellRecord:
        """Set the ACTIVE replica count of an autoscaled model cell — the
        FleetScaler's one write primitive. Scale-up starts the newly
        in-range replicas on their pre-partitioned chip grants (the cell's
        whole ``maxReplicas`` grant was allocated at start, so no device
        negotiation happens here); scale-down stops the now-out-of-range
        replicas — the caller MUST have drained them through the gateway
        first, this method only finishes the exit. The record (target and
        statuses together) is written once at the end, so a crash mid-call
        degrades to "replica still active under the old target" — the
        reconcile loop heals it back to serving — never to a capacity
        hole. Starts are idempotent: a replica a crashed earlier attempt
        left running is simply adopted."""
        import signal as _signal

        from kukeon_tpu.runtime.apply.validate import model_scale_bound

        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            m = rec.spec.model
            if m is None:
                raise InvalidArgument(f"cell {name!r} is not a model cell")
            bound = model_scale_bound(m)
            lo = max(1, m.min_replicas or 1)
            if bound <= 1:
                raise InvalidArgument(
                    f"cell {name!r} has no replica range to scale "
                    "(set model.maxReplicas)")
            if not (lo <= target <= bound):
                raise InvalidArgument(
                    f"cell {name!r}: target {target} outside "
                    f"[{lo}, {bound}]")
            old = self.model_target(rec)
            containers = self.cell_containers(rec)
            by_name = {c.name: c for c in containers}
            if target > old:
                for i in range(old, target):
                    spec = by_name[f"model-server-{i}"]
                    self._ensure_cell_network(rec)
                    ctx = self._container_context(rec, spec)
                    grant = self._chip_slices(
                        containers, rec.status.tpu_chips).get(spec.name, [])
                    if grant:
                        self._grant_chips(ctx, grant)
                    if not self.backend.container_state(ctx).running:
                        self.backend.start_container(ctx)
                    live = self.backend.container_state(ctx)
                    st = rec.status.container(spec.name)
                    if st is None:
                        st = model.ContainerStatus(name=spec.name)
                        rec.status.containers.append(st)
                    st.state = live.state
                    st.pid = live.pid
                    st.exit_code = live.exit_code
                    st.started_at = time.time()
                    st.finished_at = None
            else:
                for i in range(target, old):
                    spec = by_name[f"model-server-{i}"]
                    bare = self._container_context_bare(rec, spec)
                    if self.backend.container_state(bare).running:
                        # Normally already exited (the drain shuts the
                        # cell down); the grace window covers a cell that
                        # drained but wedged short of exit.
                        self.backend.signal_container(bare, _signal.SIGTERM)
                        deadline = time.monotonic() + self.opts.stop_grace_s
                        while (time.monotonic() < deadline
                               and self.backend.container_state(bare).running):
                            time.sleep(0.05)
                        if self.backend.container_state(bare).running:
                            self.backend.signal_container(bare,
                                                          _signal.SIGKILL)
                    live = self.backend.container_state(bare)
                    st = rec.status.container(spec.name)
                    if st is not None:
                        st.state = live.state
                        st.pid = None
                        st.exit_code = live.exit_code
                        if st.finished_at is None:
                            st.finished_at = time.time()
            rec.status.target_replicas = target
            self._derive_phase(rec)
            self.store.write_cell(rec)
            return rec

    def start_parked_replica(self, realm: str, space: str, stack: str,
                             name: str) -> tuple[model.CellRecord, str]:
        """Boot the FIRST parked replica of an autoscaled model cell on its
        pre-partitioned chip grant WITHOUT touching ``target_replicas`` —
        the standby pre-warm primitive (rollout standby, scaler warm pool).
        The replica serves and answers /readyz but stays outside the active
        range: the gateway census, phase derivation, and the scaler all
        keep ignoring it, and reconcile never heals or stops it (parked
        containers are recorded, never managed). Idempotent — a standby
        already running is adopted, not restarted. Returns the record and
        the started container's name."""
        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            m = rec.spec.model
            if m is None:
                raise InvalidArgument(f"cell {name!r} is not a model cell")
            parked = self._parked_names(rec)
            if not parked:
                raise FailedPrecondition(
                    f"cell {name!r} has no parked replica to pre-warm "
                    "(active target is already at the scale bound)")
            # Lowest parked index = the next scale-up promotion target, so
            # the scaler's first scale-up adopts the warm standby in place.
            cname = f"model-server-{self.model_target(rec)}"
            containers = self.cell_containers(rec)
            spec = next(c for c in containers if c.name == cname)
            self._ensure_cell_network(rec)
            ctx = self._container_context(rec, spec)
            grant = self._chip_slices(containers,
                                      rec.status.tpu_chips).get(spec.name, [])
            if grant:
                self._grant_chips(ctx, grant)
            if not self.backend.container_state(ctx).running:
                self.backend.start_container(ctx)
            live = self.backend.container_state(ctx)
            st = rec.status.container(spec.name)
            if st is None:
                st = model.ContainerStatus(name=spec.name)
                rec.status.containers.append(st)
            st.state = live.state
            st.pid = live.pid
            st.exit_code = live.exit_code
            st.started_at = time.time()
            st.finished_at = None
            self.store.write_cell(rec)
            return rec, cname

    def stop_parked_replica(self, realm: str, space: str, stack: str,
                            name: str, container: str) -> model.CellRecord:
        """Park a pre-warmed standby again: stop the named container iff it
        is OUTSIDE the active range (a replica scale-up promoted into the
        target is live capacity — stopping it would punch the hole the
        standby existed to prevent, so that's a silent no-op here).
        ``target_replicas`` is untouched either way."""
        import signal as _signal

        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            if container not in self._parked_names(rec):
                return rec
            containers = self.cell_containers(rec)
            spec = next((c for c in containers if c.name == container), None)
            if spec is None:
                raise NotFound(
                    f"container {container!r} not found in cell {name!r}")
            bare = self._container_context_bare(rec, spec)
            if self.backend.container_state(bare).running:
                self.backend.signal_container(bare, _signal.SIGTERM)
                deadline = time.monotonic() + self.opts.stop_grace_s
                while (time.monotonic() < deadline
                       and self.backend.container_state(bare).running):
                    time.sleep(0.05)
                if self.backend.container_state(bare).running:
                    self.backend.signal_container(bare, _signal.SIGKILL)
            live = self.backend.container_state(bare)
            st = rec.status.container(spec.name)
            if st is not None:
                st.state = live.state
                st.pid = None
                st.exit_code = live.exit_code
                if st.finished_at is None:
                    st.finished_at = time.time()
            self.store.write_cell(rec)
            return rec

    def _container_context_bare(self, rec: model.CellRecord, spec: t.ContainerSpec) -> ContainerContext:
        """Context sufficient for signal/state/cleanup (no env building)."""
        cdir = self.store.container_dir(rec.realm, rec.space, rec.stack, rec.name, spec.name)
        return ContainerContext(container_dir=cdir, spec=spec, command=list(spec.command))

    def _finish_stop(self, rec: model.CellRecord, contexts: list[ContainerContext]) -> None:
        for ctx, st in zip(contexts, rec.status.containers):
            live = self.backend.container_state(ctx)
            st.state = live.state
            st.exit_code = live.exit_code
            st.pid = None
            st.finished_at = time.time()
        rec.desired_state = "stopped"
        rec.status.phase = model.STOPPED
        if rec.status.tpu_chips:
            self.devices.release(self._owner_key(rec))
            rec.status.tpu_chips = []
        if self.backend.isolated:
            if self.netman is not None:
                self.netman.detach_cell(rec.realm, rec.space, self._owner_key(rec))
            rec.status.ip = None
            self.backend.teardown_sandbox(self._cell_dir(rec))
        self.store.write_cell(rec)

    def delete_cell(self, realm: str, space: str, stack: str, name: str,
                    force: bool = False) -> None:
        # Read and running-check INSIDE the cell lock: every mutating verb
        # serializes on it, and checking outside raced a concurrent
        # start_cell — a cell observed stopped could be started by another
        # thread and then have its tree deleted around a live sandbox
        # (VERDICT r3 weak 6).
        with self.cell_lock(realm, space, stack, name):
            rec = self.store.read_cell(realm, space, stack, name)
            running = any(
                self.backend.container_state(
                    self._container_context_bare(rec, spec)
                ).running
                for spec in self.cell_containers(rec)
            )
            if running:
                if not force:
                    raise FailedPrecondition(
                        f"cell {name!r} is running; stop it first or use force"
                    )
                self._kill_cell_locked(rec)
            for spec in self.cell_containers(rec):
                self.backend.cleanup_container(self._container_context_bare(rec, spec))
            if self.backend.isolated:
                if self.netman is not None:
                    self.netman.detach_cell(realm, space, self._owner_key(rec))
                self.backend.teardown_sandbox(self._cell_dir(rec))
            self.devices.release(self._owner_key(rec))
            self._release_host_ports(rec)
            self.store.delete_cell_tree(realm, space, stack, name)
            if self.cgroups:
                self.cgroups.remove(realm, space, stack, name)

    # --- refresh / restart policy (reference: refresh.go:1110-1458) --------

    def refresh_cell(self, realm: str, space: str, stack: str, name: str) -> tuple[model.CellRecord | None, str]:
        with self.cell_lock(realm, space, stack, name):
            try:
                rec = self.store.read_cell(realm, space, stack, name)
            except NotFound:
                return None, OUTCOME_VANISHED
            return self._refresh_locked(rec)

    def _refresh_locked(self, rec: model.CellRecord) -> tuple[model.CellRecord, str]:
        outcome = OUTCOME_STEADY
        containers = self.cell_containers(rec)
        changed = False
        owner = self._owner_key(rec)
        parked = self._parked_names(rec)

        for spec in containers:
            st = rec.status.container(spec.name)
            if st is None:
                st = model.ContainerStatus(name=spec.name)
                rec.status.containers.append(st)
            ctx = self._container_context_bare(rec, spec)
            live = self.backend.container_state(ctx)
            if spec.name in parked:
                # Scaled out of the active range: record what the backend
                # sees (a drained replica exits 0) but never heal it — the
                # restart policy below would tug against the scaler's
                # scale-down forever.
                if (live.state, live.pid, live.exit_code) != (
                        st.state, st.pid, st.exit_code):
                    changed = changed or st.state != live.state
                    st.state = live.state
                    st.pid = live.pid
                    st.exit_code = live.exit_code
                    if live.exited and st.finished_at is None:
                        st.finished_at = time.time()
                continue
            if (live.state, live.pid, live.exit_code) != (st.state, st.pid, st.exit_code):
                if st.state != live.state:
                    changed = True
                st.state = live.state
                st.pid = live.pid
                st.exit_code = live.exit_code
                if live.exited and st.finished_at is None:
                    st.finished_at = time.time()
                    # Newly observed exit: count it by code so a crash
                    # loop's signature (e.g. the watchdog's 86) is visible
                    # on the daemon scrape, not only in `kuke get`.
                    self._m_exits.inc(cell=owner, container=spec.name,
                                      code=str(live.exit_code or 0))
                if live.exited and (live.exit_code or 0) != 0:
                    # Capture WHY before the restart path wipes the run
                    # artifacts: the log tail at a non-clean exit is the
                    # operator's only evidence in a crash loop (reference:
                    # markCellFailed with reason, runner/start.go:186,414).
                    tail = self._container_log_tail(ctx)
                    if tail:
                        st.last_error = tail
                        changed = True

            # Lifecycle gauges, refreshed every reconcile tick: uptime for
            # running containers, remaining restart backoff for exited
            # ones waiting on their window, budget-exhaustion as a flag.
            anchor = st.last_restart_at or st.started_at
            self._m_uptime.set(
                (time.time() - anchor) if (live.running and anchor) else 0.0,
                cell=owner, container=spec.name)
            self._m_backoff.set(
                self._backoff_remaining(spec, st) if live.exited else 0.0,
                cell=owner, container=spec.name)
            self._m_exhausted.set(
                1.0 if (live.exited
                        and spec.restart_policy.policy != "never"
                        and spec.restart_policy.max_retries is not None
                        and st.restarts >= spec.restart_policy.max_retries)
                else 0.0,
                cell=owner, container=spec.name)

            if live.running:
                # Restart-budget replenishment: a container that has stayed
                # up for a healthy-uptime window earns its budget back, so a
                # bounded `restartMaxRetries` guards against crash LOOPS, not
                # against a month of uptime with occasional crashes
                # (reference keeps a windowed restart-state map,
                # runner/refresh.go:1224-1458).
                anchor = st.last_restart_at or st.started_at
                if (
                    st.restarts > 0
                    and anchor is not None
                    and (time.time() - anchor) >= self.RESTART_RESET_UPTIME_S
                ):
                    st.restarts = 0
                    st.last_error = None
                    changed = True
                    # The crash is history now; stop alarming the operator.
                    if rec.status.reason and rec.status.reason.startswith(
                        f"container {spec.name} crash"
                    ):
                        rec.status.reason = None

            if (
                rec.desired_state == "running"
                and live.exited
                and self._restart_due(spec, st)
            ):
                self._ensure_cell_network(rec)   # sandbox may be recreated
                ctx_full = self._container_context(rec, spec)
                grant = self._chip_slices(containers, rec.status.tpu_chips).get(spec.name, [])
                if grant:
                    # Reuse the cell's grant (stable across restarts).
                    self._grant_chips(ctx_full, grant)
                self.backend.start_container(ctx_full)
                prev_exit = st.exit_code
                live = self.backend.container_state(ctx_full)
                st.state = live.state
                st.pid = live.pid
                st.exit_code = live.exit_code
                st.restarts += 1
                st.last_restart_at = time.time()
                st.finished_at = None
                self._m_restarts.inc(cell=owner, container=spec.name)
                self._m_backoff.set(0.0, cell=owner, container=spec.name)
                if (prev_exit or 0) != 0:
                    why = f": {st.last_error}" if st.last_error else ""
                    rec.status.reason = (
                        f"container {spec.name} crashed (exit {prev_exit}, "
                        f"restart #{st.restarts}){why}"
                    )
                outcome = OUTCOME_RESTARTED
                changed = True
            elif (
                rec.desired_state == "running"
                and live.exited
                and (st.exit_code or 0) != 0
                and spec.restart_policy.policy != "never"
                and spec.restart_policy.max_retries is not None
                and st.restarts >= spec.restart_policy.max_retries
            ):
                why = f": {st.last_error}" if st.last_error else ""
                reason = (
                    f"container {spec.name} crash-looped: restart budget "
                    f"exhausted ({st.restarts}/{spec.restart_policy.max_retries}, "
                    f"last exit {st.exit_code}){why}"
                )
                if rec.status.reason != reason:
                    rec.status.reason = reason
                    changed = True

        # AutoDelete: reap once every container has exited
        # (reference: runner/runner.go:33-45).
        if (
            rec.spec.auto_delete
            and rec.desired_state == "running"
            and rec.status.containers
            and all(c.state == model.C_EXITED for c in rec.status.containers)
        ):
            self._finish_stop(rec, [
                self._container_context_bare(rec, spec) for spec in containers
            ])
            for spec in containers:
                self.backend.cleanup_container(self._container_context_bare(rec, spec))
            self._release_host_ports(rec)
            self.store.delete_cell_tree(rec.realm, rec.space, rec.stack, rec.name)
            if self.cgroups:
                self.cgroups.remove(rec.realm, rec.space, rec.stack, rec.name)
            return rec, OUTCOME_AUTO_DELETED

        old_phase = rec.status.phase
        self._derive_phase(rec)
        if changed or rec.status.phase != old_phase:
            self.store.write_cell(rec)
            if outcome == OUTCOME_STEADY:
                outcome = OUTCOME_HEALED
        return rec, outcome

    # Continuous uptime after which a container's restart count resets.
    RESTART_RESET_UPTIME_S = 300.0

    def _container_log_tail(self, ctx: ContainerContext, limit: int = 500) -> str | None:
        """Last few lines of the container's log (shim log, or the capture
        transcript for attachable containers) for crash-reason reporting."""
        names = [consts.CAPTURE_FILE] if ctx.spec.attachable else [consts.SHIM_LOG]
        for name in names:
            path = os.path.join(ctx.container_dir, name)
            try:
                with open(path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    size = f.tell()
                    f.seek(max(0, size - 4096))
                    data = f.read().decode(errors="replace")
            except OSError:
                continue
            lines = [ln.strip() for ln in data.splitlines() if ln.strip()]
            if lines:
                return "\n".join(lines[-6:])[-limit:]
        return None

    def cell_metrics(self, rec: model.CellRecord) -> dict[str, dict]:
        """Live per-container cgroup metrics (memory_bytes, cpu_usec, pids)
        for `kuke get`/`status` (reference: internal/ctr/cgroups.go:484,
        task.go:50 feed cgroup/task metrics into status). Read-only: never
        creates cgroups, returns {} when the tree isn't managed."""
        if not self.cgroups:
            return {}
        out: dict[str, dict] = {}
        for spec in self.cell_containers(rec):
            d = self.cgroups.path(rec.realm, rec.space, rec.stack, rec.name, spec.name)
            if os.path.isdir(d):
                m = self.cgroups.metrics(d)
                if m:
                    out[spec.name] = m
        return out

    def _backoff_remaining(self, spec: t.ContainerSpec,
                           st: model.ContainerStatus) -> float:
        """Seconds until an exited container's restart window opens; 0 when
        no restart is pending (policy says no, budget spent, or due now)."""
        rp = spec.restart_policy
        if rp.policy == "never":
            return 0.0
        if rp.policy == "on-failure" and (st.exit_code == 0):
            return 0.0
        if rp.max_retries is not None and st.restarts >= rp.max_retries:
            return 0.0
        anchor = st.last_restart_at or st.finished_at
        if anchor is None:
            return 0.0
        return max(0.0, rp.backoff_seconds - (time.time() - anchor))

    def _restart_due(self, spec: t.ContainerSpec, st: model.ContainerStatus) -> bool:
        rp = spec.restart_policy
        if rp.policy == "never":
            return False
        if rp.policy == "on-failure" and (st.exit_code == 0):
            return False
        if rp.max_retries is not None and st.restarts >= rp.max_retries:
            return False
        anchor = st.last_restart_at or st.finished_at
        if anchor is not None and (time.time() - anchor) < rp.backoff_seconds:
            return False
        return True

    def _derive_phase(self, rec: model.CellRecord) -> None:
        # Parked (scaled-down) replicas are intentionally not running: a
        # cell at its autoscale minimum is READY, not degraded.
        parked = self._parked_names(rec)
        states = [c.state for c in rec.status.containers
                  if c.name not in parked]
        if not states:
            rec.status.phase = model.PENDING
            return
        if rec.desired_state == "stopped":
            rec.status.phase = model.STOPPED
            return
        running = sum(1 for s in states if s == model.C_RUNNING)
        if running == len(states):
            rec.status.phase = model.READY
        elif running > 0:
            rec.status.phase = model.DEGRADED
        elif all(s == model.C_EXITED for s in states):
            failed = any(
                (c.exit_code or 0) != 0 for c in rec.status.containers
            )
            rec.status.phase = model.FAILED if failed else model.STOPPED
        else:
            rec.status.phase = model.PENDING
