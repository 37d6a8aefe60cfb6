"""Wire/YAML types for every manifest kind (v1beta1 equivalent).

Parity surface with the reference's pkg/api/model/v1beta1 (11 kinds,
consts.go:24-80; ContainerSpec field list container.go:34-237; SpaceSpec
space.go:38-104; Volume volume.go:61-83), re-designed for a TPU-VM host:

- ``Resources.tpu_chips`` is first-class: a container can request N chips;
  the runner's device manager partitions chip visibility per cell the way
  the reference partitions memory/cpu via cgroups (SURVEY.md section 5.8).
- ``CellSpec.model`` declares an in-tree model-serving cell (the JetStream
  analog from BASELINE.json's north star): the runner materializes a
  serving container running kukeon_tpu.serving with the requested chips.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

API_VERSION = "kukeon.io/v1beta1"
TEAMS_API_VERSION = "kuketeams.io/v1"

KIND_REALM = "Realm"
KIND_SPACE = "Space"
KIND_STACK = "Stack"
KIND_CELL = "Cell"
KIND_CONTAINER = "Container"
KIND_SECRET = "Secret"
KIND_CELL_BLUEPRINT = "CellBlueprint"
KIND_CELL_CONFIG = "CellConfig"
KIND_VOLUME = "Volume"
KIND_SERVER_CONFIGURATION = "ServerConfiguration"
KIND_CLIENT_CONFIGURATION = "ClientConfiguration"

ALL_KINDS = (
    KIND_REALM, KIND_SPACE, KIND_STACK, KIND_CELL, KIND_CONTAINER,
    KIND_SECRET, KIND_CELL_BLUEPRINT, KIND_CELL_CONFIG, KIND_VOLUME,
    KIND_SERVER_CONFIGURATION, KIND_CLIENT_CONFIGURATION,
)

# Apply order: parents before children (reference: documents.go:30).
KIND_APPLY_ORDER = (
    KIND_REALM, KIND_SPACE, KIND_STACK, KIND_VOLUME, KIND_SECRET,
    KIND_CELL_BLUEPRINT, KIND_CELL_CONFIG, KIND_CELL, KIND_CONTAINER,
)


@dataclass
class Metadata:
    name: str = ""
    realm: str | None = None
    space: str | None = None
    stack: str | None = None
    cell: str | None = None
    labels: dict[str, str] = field(default_factory=dict)


# --- container -----------------------------------------------------------


@dataclass
class EnvVar:
    name: str = ""
    value: str = ""


@dataclass
class SecretRef:
    """Mount a scoped Secret; staged read-only at /run/kukeon/secrets/<name>
    (reference: ctr/secrets.go:30-60) and/or exported as env."""

    name: str = ""
    env: str | None = None           # export as this env var
    path: str | None = None          # or stage at this path


@dataclass
class VolumeMount:
    name: str | None = None          # reference to a Volume kind
    host_path: str | None = None     # direct bind (trusted manifests only)
    path: str = ""                   # mount point inside the workload
    read_only: bool = False
    tmpfs: bool = False


@dataclass
class PortSpec:
    port: int = 0
    protocol: str = "tcp"
    name: str | None = None


@dataclass
class RepoSpec:
    """Git repo cloned into the workload before start (kuketty runOn:create
    stages; reference: cmd/kuketty/repos.go)."""

    url: str = ""
    path: str = ""
    ref: str | None = None


@dataclass
class Resources:
    memory: str | None = None        # e.g. "2Gi"
    cpu: float | None = None         # cores
    pids: int | None = None
    tpu_chips: int | None = None     # TPU-native: chips granted to this container


@dataclass
class RestartPolicy:
    policy: str = "never"            # always | on-failure | never
    backoff_seconds: float = 1.0
    max_retries: int | None = None


@dataclass
class TTYSpec:
    prompt: str | None = None
    on_init: list[str] = field(default_factory=list)   # stage commands
    log_file: str | None = None
    log_level: str | None = None


@dataclass
class ContainerSpec:
    name: str = ""
    image: str | None = None         # image-backed (containerd backend) or
    command: list[str] = field(default_factory=list)   # process-backed
    args: list[str] = field(default_factory=list)
    env: list[EnvVar] = field(default_factory=list)
    workdir: str | None = None
    user: str | None = None
    ports: list[PortSpec] = field(default_factory=list)
    volumes: list[VolumeMount] = field(default_factory=list)
    networks: list[str] = field(default_factory=list)
    privileged: bool = False
    host_network: bool = False
    host_pid: bool = False
    read_only_root_filesystem: bool = False
    capabilities: list[str] = field(default_factory=list)
    # reference: ContainerSpec.securityOpts (container.go) / OCI seccomp.
    # Supported: "seccomp=default" (denylist filter) | "seccomp=unconfined".
    security_opts: list[str] = field(default_factory=list)
    devices: list[str] = field(default_factory=list)
    resources: Resources = field(default_factory=Resources)
    secrets: list[SecretRef] = field(default_factory=list)
    repos: list[RepoSpec] = field(default_factory=list)
    restart_policy: RestartPolicy = field(default_factory=RestartPolicy)
    attachable: bool = False
    tty: TTYSpec | None = None


# --- model-serving cell (TPU-native) -------------------------------------


@dataclass
class ModelSpec:
    """In-tree serving cell: the runner materializes a container running the
    kukeon_tpu serving engine with these settings (north-star JetStream
    analog; no reference equivalent — kukeon has no model cells)."""

    model: str = ""                  # e.g. "llama3-8b", "llama3-1b", "tiny"
    # Chips per replica. 1 = single-chip (the classic shape); N > 1 builds
    # an N-chip tensor-parallel serving mesh inside each replica (params +
    # KV pool sharded over the tensor axis). The runner checks at start
    # that N divides the host's chip count so every replica's grant is a
    # whole N-chip slice; validate keeps the static >= 1 floor.
    chips: int = 1
    port: int = 9000
    # Scale-out: N > 1 materializes N serving containers (each granted
    # ``chips`` chips, listening on port+1 .. port+N) plus one gateway
    # container on ``port`` that routes by least queue depth with
    # prefix-id affinity (kukeon_tpu/gateway). The client-facing endpoint
    # is ``port`` either way; replicas=1 keeps the single-engine shape.
    replicas: int = 1
    # SLO-driven autoscaling bounds (runtime/scaler.py): setting
    # ``maxReplicas`` arms the daemon's FleetScaler for this cell — the
    # runner materializes the full port range and chip partition up to the
    # bound, and the scaler moves the ACTIVE replica count between
    # ``minReplicas`` (default 1) and ``maxReplicas`` from windowed SLO
    # burn rate + aggregate queue depth, debounced through the alert
    # engine's pending->firing state machine. ``replicas`` is the initial
    # active count and must sit inside the bounds. Scale-up starts a
    # parked replica on its pre-partitioned chip grant; scale-down drains
    # through the gateway first, so no in-flight request is lost. Unset =
    # the static replica set, byte-identical to before autoscaling.
    min_replicas: int | None = None
    max_replicas: int | None = None
    # Disaggregated prefill/decode serving (FlexNPU-style): "mixed" (the
    # default — every replica serves both phases, byte-identical to the
    # pre-role behavior), or a comma-separated per-replica role list
    # ("prefill,decode,decode", one atom per replica in declaration order)
    # splitting the replica set into a prefill pool and a decode pool
    # behind the same gateway. The gateway then routes /v1/generate as a
    # two-stage KV handoff: prefill pool by queue depth, decode pool by
    # prefix affinity, with page-granular KV transfer between them and
    # graceful fallback to local decode on a prefill-capable replica when
    # the decode pool is unavailable. Roles are policy, not capability —
    # every replica keeps the full engine.
    role: str = "mixed"
    num_slots: int = 8
    max_seq_len: int | None = None
    checkpoint: str | None = None    # orbax checkpoint dir; random-init if None
    dtype: str | None = None
    # int8 KV cache: halves the decode-time cache HBM stream (dequant fused
    # into the attention dots). Weights are governed by ``dtype``; this
    # governs only the per-request KV cache.
    kv_cache_int8: bool = False
    # Paged KV cache (serving/kv_pages.py): > 0 serves from a block-table
    # page pool with pages of this many KV rows instead of reserving
    # numSlots * maxSeqLen contiguous rows per slot — mixed-length agent
    # traffic packs HBM page-granularly, with preemption + requeue under
    # pressure and refcounted prefix sharing. 0 forces the legacy
    # contiguous layout; None defers to the persisted tune file.
    kv_page_tokens: int | None = None
    # Admission control (serving resilience): bound on queued-not-yet-
    # slotted requests — past it the cell sheds with 429 + Retry-After
    # instead of growing an unbounded backlog. None = the serving cell's
    # own default; 0 = unbounded (explicit operator opt-out).
    max_pending: int | None = None
    # Default per-request deadline in seconds (a request's own deadlineS
    # wins). Expired requests get an in-band timeout terminal event and
    # free their slot. None/0 = no default deadline.
    deadline_s: float | None = None
    # Serving objectives (obs/slo.py): the cell evaluates availability and
    # TTFT burn rates against these at scrape time and exposes them as
    # kukeon_slo_* on /metrics. sloTtftP95Ms bounds the 95th-percentile
    # time-to-first-token (milliseconds); sloAvailability is the required
    # success fraction (e.g. 0.999). Unset = the cell's loose defaults.
    slo_ttft_p95_ms: float | None = None
    slo_availability: float | None = None
    # Model cells live INSIDE the space network by default: the server binds
    # the cell's bridge IP, in-space agent cells reach it there, and the
    # space's default-deny egress governs its traffic (BASELINE config 4).
    # hostNetwork: true is the spec-visible opt-out for hosts whose TPU
    # runtime plane needs host networking (multi-host pod slices; sandboxed
    # hosts where the fresh sysfs of a cell's own netns lacks the PCI
    # devices libtpu enumerates) — it exempts the cell from the space
    # egress policy, so it must be an explicit manifest decision.
    host_network: bool = False


# --- cell / hierarchy ----------------------------------------------------


@dataclass
class CellSpec:
    containers: list[ContainerSpec] = field(default_factory=list)
    model: ModelSpec | None = None
    auto_delete: bool = False        # reap when root task exits (kuke run --rm)
    ignore_disk_pressure: bool = False


@dataclass
class EgressRule:
    host: str | None = None          # hostname, resolved at apply/reconcile
    cidr: str | None = None
    ports: list[int] = field(default_factory=list)
    # tcp | udp; None = unset (all protocols for a port-less rule, tcp once
    # ports are given). DNS allowlists say `ports: [53], protocol: udp`.
    protocol: str | None = None


@dataclass
class NetworkSpec:
    egress_default: str = "allow"    # allow | deny
    egress_allow: list[EgressRule] = field(default_factory=list)


@dataclass
class SpaceSpec:
    network: NetworkSpec = field(default_factory=NetworkSpec)
    subnet: str | None = None        # auto-allocated from the pool if unset
    container_defaults: ContainerSpec | None = None


@dataclass
class RealmSpec:
    description: str | None = None


@dataclass
class StackSpec:
    description: str | None = None


# --- secrets / volumes ---------------------------------------------------


@dataclass
class SecretSpec:
    data: dict[str, str] = field(default_factory=dict)   # plain values
    # (the store chmods the staged file 0400 root-only, like the reference)


@dataclass
class VolumeSpec:
    reclaim_policy: str = "delete"   # retain | delete (volume.go:61-83)
    size: str | None = None


# --- blueprints / configs ------------------------------------------------


@dataclass
class BlueprintParam:
    name: str = ""
    default: str | None = None
    required: bool = False


@dataclass
class CellBlueprintSpec:
    """Parametrized cell template; ``${param}`` scalars substituted at
    materialization (reference: internal/cellblueprint/params.go:47-174)."""

    params: list[BlueprintParam] = field(default_factory=list)
    cell: CellSpec = field(default_factory=CellSpec)
    name_prefix: str | None = None


@dataclass
class ConfigSecretBinding:
    slot: str = ""                   # secret slot name in the blueprint
    secret: str = ""                 # concrete Secret name


@dataclass
class CellConfigSpec:
    """Binds a CellBlueprint to a concrete cell identity
    (reference: internal/cellconfig/materialize.go:63-317)."""

    blueprint: str = ""
    values: dict[str, str] = field(default_factory=dict)
    secrets: list[ConfigSecretBinding] = field(default_factory=list)
    env: list[EnvVar] = field(default_factory=list)
    cell_name: str | None = None     # deterministic name of the one live cell


# --- configurations ------------------------------------------------------


@dataclass
class ServerConfigurationSpec:
    run_path: str | None = None
    socket: str | None = None
    reconcile_interval_seconds: float | None = None
    subnet_pool: str | None = None
    disk_pressure_warn_pct: float | None = None
    disk_pressure_block_pct: float | None = None
    log_level: str | None = None


@dataclass
class ClientConfigurationSpec:
    socket: str | None = None
    default_realm: str | None = None
    default_space: str | None = None
    default_stack: str | None = None


# --- document envelope ---------------------------------------------------

SPEC_BY_KIND = {
    KIND_REALM: RealmSpec,
    KIND_SPACE: SpaceSpec,
    KIND_STACK: StackSpec,
    KIND_CELL: CellSpec,
    KIND_CONTAINER: ContainerSpec,
    KIND_SECRET: SecretSpec,
    KIND_CELL_BLUEPRINT: CellBlueprintSpec,
    KIND_CELL_CONFIG: CellConfigSpec,
    KIND_VOLUME: VolumeSpec,
    KIND_SERVER_CONFIGURATION: ServerConfigurationSpec,
    KIND_CLIENT_CONFIGURATION: ClientConfigurationSpec,
}


@dataclass
class Document:
    api_version: str = API_VERSION
    kind: str = ""
    metadata: Metadata = field(default_factory=Metadata)
    spec: object = None

    def clone(self) -> "Document":
        return dataclasses.replace(
            self,
            metadata=dataclasses.replace(self.metadata, labels=dict(self.metadata.labels)),
            spec=dataclasses.replace(self.spec) if dataclasses.is_dataclass(self.spec) else self.spec,
        )
