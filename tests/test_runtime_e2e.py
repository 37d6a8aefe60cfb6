"""Black-box e2e: real daemon process, real CLI, real supervised workloads.

Mirrors the reference's e2e harness (e2e/harness_daemon_test.go:26-60):
per-test daemon on a temp run-path with a SUN_PATH-safe /tmp socket, <=10s
startup budget, SIGTERM + 5s -> SIGKILL teardown. This is BASELINE config 1:
"single Interactive cell via kuke apply + kuke attach (CPU e2e harness)".
"""

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
import uuid

import pytest

import host_lock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = [sys.executable, "-m", "kukeon_tpu.runtime.cli"]


class Daemon:
    def __init__(self, chips: str = "0,1", env_overrides: dict | None = None,
                 run_path: str | None = None):
        # its cells are what tests/test_netpolicy_e2e.py would kill
        host_lock.share()
        self.run_path = run_path or tempfile.mkdtemp(prefix="kuke-e2e-")
        self.socket_path = f"/tmp/kuked-{uuid.uuid4().hex[:8]}.sock"
        env = dict(os.environ)
        env.update({
            "KUKEON_TPU_CHIPS": chips,
            "KUKEOND_RECONCILE_INTERVAL": "1.0",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO,
        })
        env.update(env_overrides or {})
        self.env = env
        self.proc = subprocess.Popen(
            CLI + ["daemon", "serve", "--run-path", self.run_path,
                   "--socket", self.socket_path],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if os.path.exists(self.socket_path):
                try:
                    s = socket.socket(socket.AF_UNIX)
                    s.connect(self.socket_path)
                    s.close()
                    return
                except OSError:
                    pass
            if self.proc.poll() is not None:
                out = self.proc.stdout.read().decode()
                raise RuntimeError(f"daemon died at startup:\n{out}")
            time.sleep(0.05)
        raise RuntimeError("daemon socket did not appear within 10s")

    def kuke(self, *args, check=True, stdin_data=None) -> subprocess.CompletedProcess:
        p = subprocess.run(
            CLI + ["--socket", self.socket_path, "--run-path", self.run_path] + list(args),
            env=self.env, capture_output=True, text=True, timeout=60,
            input=stdin_data,
        )
        if check and p.returncode != 0:
            raise AssertionError(
                f"kuke {' '.join(args)} rc={p.returncode}\nstdout:{p.stdout}\nstderr:{p.stderr}"
            )
        return p

    def stop_daemon_only(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)

    def stop(self):
        self.stop_daemon_only()
        import shutil

        shutil.rmtree(self.run_path, ignore_errors=True)


@pytest.fixture
def daemon():
    d = Daemon()
    yield d
    d.stop()


CELL_MANIFEST = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: web}
spec:
  containers:
    - name: main
      command: ["/bin/sh", "-c", "while true; do echo tick; sleep 0.2; done"]
"""

ATTACH_MANIFEST = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: term}
spec:
  containers:
    - name: shell
      command: ["/bin/sh", "-i"]
      attachable: true
      tty:
        onInit: ["echo stage-one-done"]
"""


def test_cell_lifecycle_e2e(daemon):
    d = daemon
    d.kuke("apply", "-f", "-", stdin_data=CELL_MANIFEST)

    out = d.kuke("get", "cells").stdout
    assert "web" in out and "ready" in out

    # Logs flow from the supervised workload.
    time.sleep(0.6)
    log = d.kuke("log", "web").stdout
    assert "tick" in log

    # Re-apply: unchanged.
    out = d.kuke("apply", "-f", "-", stdin_data=CELL_MANIFEST).stdout
    assert "unchanged" in out

    d.kuke("stop", "web")
    out = d.kuke("--json", "get", "cells", "web").stdout
    rec = json.loads(out)
    assert rec["status"]["phase"] == "stopped"
    assert rec["status"]["containers"][0]["state"] == "exited"

    d.kuke("start", "web")
    rec = json.loads(d.kuke("--json", "get", "cells", "web").stdout)
    assert rec["status"]["phase"] == "ready"

    d.kuke("delete", "cell", "web", "--force")
    out = d.kuke("get", "cells").stdout
    assert "web" not in out


def test_run_rm_autodelete_and_restart_policy(daemon):
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: oneshot}
spec:
  containers:
    - {name: main, command: ["/bin/sh", "-c", "exit 0"]}
"""
    d.kuke("run", "-d", "--rm", "-f", "-", stdin_data=manifest)
    # The 1s reconcile ticker reaps the exited autoDelete cell.
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if "oneshot" not in d.kuke("get", "cells").stdout:
            break
        time.sleep(0.5)
    assert "oneshot" not in d.kuke("get", "cells").stdout

    # Restart policy: always-restart keeps a crashing container coming back.
    crash = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: crashy}
spec:
  containers:
    - name: main
      command: ["/bin/sh", "-c", "sleep 0.1; exit 1"]
      restartPolicy: {policy: always, backoffSeconds: 0.1}
"""
    d.kuke("apply", "-f", "-", stdin_data=crash)
    deadline = time.monotonic() + 20.0
    restarts = 0
    while time.monotonic() < deadline:
        rec = json.loads(d.kuke("--json", "get", "cells", "crashy").stdout)
        restarts = rec["status"]["containers"][0].get("restarts", 0)
        if restarts >= 2:
            break
        time.sleep(0.5)
    assert restarts >= 2
    d.kuke("delete", "cell", "crashy", "--force")


def test_attach_e2e(daemon):
    d = daemon
    d.kuke("apply", "-f", "-", stdin_data=ATTACH_MANIFEST)

    info = None
    # Resolve the attach socket via the daemon (AttachContainer RPC path).
    import json as _json

    rec = _json.loads(d.kuke("--json", "get", "cells", "term").stdout)
    assert rec["status"]["phase"] == "ready"
    sock_path = os.path.join(
        d.run_path, "realms", "default", "spaces", "default", "stacks", "default",
        "cells", "term", "containers", "shell", "tty.sock",
    )
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not os.path.exists(sock_path):
        time.sleep(0.1)
    s = socket.socket(socket.AF_UNIX)
    s.connect(sock_path)
    s.sendall(b"D" + struct.pack(">I", 22) + b"echo marker-$((41+1))\n")
    time.sleep(0.8)
    s.settimeout(2.0)
    out = b""
    try:
        while True:
            c = s.recv(4096)
            if not c:
                break
            out += c
    except socket.timeout:
        pass
    s.close()
    assert b"marker-42" in out

    # Capture transcript includes the init stage and survives detach.
    cap = d.kuke("log", "term").stdout
    assert "stage-one-done" in cap

    # Daemon restart does NOT kill the attached workload (supervisor owns it).
    rec_before = _json.loads(d.kuke("--json", "get", "cells", "term").stdout)
    pid = rec_before["status"]["containers"][0]["pid"]
    os.kill(pid, 0)   # alive
    d.kuke("delete", "cell", "term", "--force")


def test_model_cell_e2e(daemon):
    """BASELINE config 2 analog on CPU: a model cell comes up via kuke apply;
    the runner materializes the in-tree serving container; generation works
    over its HTTP port; chips are granted and released."""
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: llm}
spec:
  model: {model: tiny, chips: 1, port: 9471, numSlots: 2, maxSeqLen: 128,
          hostNetwork: true}
"""
    # hostNetwork: the explicit opt-out of the space network (this suite
    # runs with net enforcement disabled, so an in-space model cell would
    # have no bridge; the in-policy path is tests/test_netpolicy_e2e.py).
    d.kuke("apply", "-f", "-", stdin_data=manifest)
    rec = json.loads(d.kuke("--json", "get", "cells", "llm").stdout)
    assert rec["status"]["tpuChips"] == [0]
    assert rec["status"]["containers"][0]["name"] == "model-server"

    import urllib.request

    deadline = time.monotonic() + 90.0
    healthy = False
    while time.monotonic() < deadline:
        try:
            r = urllib.request.urlopen("http://127.0.0.1:9471/v1/health", timeout=1)
            healthy = json.loads(r.read())["status"] == "ok"
            break
        except OSError:
            rec = json.loads(d.kuke("--json", "get", "cells", "llm").stdout)
            st = rec["status"]["containers"][0]
            if st["state"] == "exited":
                log = d.kuke("log", "llm", "--container", "model-server", check=False).stdout
                raise AssertionError(f"model server exited ({st['exitCode']}):\n{log}")
            time.sleep(1.0)
    assert healthy, "model server did not become healthy in 90s"

    body = json.dumps({"prompt": "hi", "maxNewTokens": 4}).encode()
    r = urllib.request.urlopen(
        urllib.request.Request("http://127.0.0.1:9471/v1/generate", data=body,
                               headers={"Content-Type": "application/json"}),
        timeout=60,
    )
    out = json.loads(r.read())
    assert out["numTokens"] == 4

    # Streaming: newline-delimited JSON, one record per token + a terminal
    # record that matches the non-streaming aggregate shape.
    body = json.dumps({"prompt": "hi", "maxNewTokens": 4, "stream": True}).encode()
    r = urllib.request.urlopen(
        urllib.request.Request("http://127.0.0.1:9471/v1/generate", data=body,
                               headers={"Content-Type": "application/json"}),
        timeout=60,
    )
    assert r.headers.get("Content-Type") == "application/x-ndjson"
    records = [json.loads(ln) for ln in r.read().splitlines() if ln.strip()]
    tok_records, final = records[:-1], records[-1]
    assert len(tok_records) == 4
    assert all("token" in t for t in tok_records)
    assert final["done"] is True and final["numTokens"] == 4
    assert final["tokens"] == [t["token"] for t in tok_records]
    # Prefix-diff contract: concatenated deltas == the final decode (BPE
    # merging must not be broken by per-token decoding).
    assert "".join(t["text"] for t in tok_records) == final["text"]

    d.kuke("delete", "cell", "llm", "--force")
    status = json.loads(d.kuke("--json", "status").stdout)
    assert status["tpuChips"]["free"] == 2


def test_tpu_chip_accounting_e2e(daemon):
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: tpuweb}
spec:
  containers:
    - name: main
      command: ["/bin/sh", "-c", "echo chips=$TPU_VISIBLE_DEVICES; sleep 30"]
      resources: {tpuChips: 2}
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest)
    rec = json.loads(d.kuke("--json", "get", "cells", "tpuweb").stdout)
    assert rec["status"]["tpuChips"] == [0, 1]

    status = json.loads(d.kuke("--json", "status").stdout)
    assert status["tpuChips"]["total"] == 2
    assert status["tpuChips"]["free"] == 0

    # The workload actually sees the visibility env.
    time.sleep(0.5)
    log = d.kuke("log", "tpuweb").stdout
    assert "chips=0,1" in log

    d.kuke("delete", "cell", "tpuweb", "--force")
    status = json.loads(d.kuke("--json", "status").stdout)
    assert status["tpuChips"]["free"] == 2


def test_create_verb_and_autocomplete_e2e(daemon):
    # Imperative scope creates.
    daemon.kuke("create", "realm", "prod")
    daemon.kuke("create", "space", "edge", "--realm", "prod")
    daemon.kuke("create", "stack", "web", "--realm", "prod", "--space", "edge")
    assert "prod" in daemon.kuke("get", "realms").stdout

    # Cell with --no-start stays pending; then start brings it up.
    daemon.kuke("create", "cell", "idle", "--no-start",
                "--command", "/bin/sleep", "30")
    out = daemon.kuke("get", "cell", "idle", "--json").stdout
    rec = json.loads(out)
    assert rec["status"]["phase"] == "pending"
    daemon.kuke("start", "idle")
    rec = json.loads(daemon.kuke("get", "cell", "idle", "--json").stdout)
    assert rec["status"]["phase"] == "ready"

    # Secret + volume imperative creates land in their stores.
    daemon.kuke("create", "secret", "tok", "--data", "API_KEY=abc")
    assert "tok" in daemon.kuke("get", "secrets").stdout
    daemon.kuke("create", "volume", "scratch", "--reclaim-policy", "retain")
    assert "scratch" in daemon.kuke("get", "volumes").stdout

    # Autocomplete lists live resources; bash emits the script.
    assert "idle" in daemon.kuke("autocomplete", "cells").stdout.split()
    assert "prod" in daemon.kuke("autocomplete", "realms").stdout.split()
    assert "_kuke_complete" in daemon.kuke("autocomplete", "bash").stdout

    daemon.kuke("delete", "cell", "idle", "--force")


def test_server_configuration_written_and_effective(daemon):
    # First daemon start wrote the commented ServerConfiguration document.
    cfg = os.path.join(daemon.run_path, "kukeond.yaml")
    assert os.path.exists(cfg)
    text = open(cfg).read()
    assert "kind: ServerConfiguration" in text
    assert "reconcileInterval" in text
    # The doc carries the values the daemon actually bound to (env said 1.0).
    assert "reconcileInterval: 1.0" in text


def test_embedding_cell_e2e(daemon):
    """BASELINE config 5 analog on CPU: an embedding model cell (bge shape)
    comes up beside the runtime and serves /v1/embed."""
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: embedder}
spec:
  model: {model: bge-tiny, chips: 1, port: 9473, numSlots: 4,
          hostNetwork: true}
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest)

    import urllib.request

    deadline = time.monotonic() + 90.0
    healthy = False
    while time.monotonic() < deadline:
        try:
            r = urllib.request.urlopen("http://127.0.0.1:9473/v1/health", timeout=1)
            healthy = json.loads(r.read())["status"] == "ok"
            break
        except OSError:
            rec = json.loads(d.kuke("--json", "get", "cells", "embedder").stdout)
            st = rec["status"]["containers"][0]
            if st["state"] == "exited":
                log = d.kuke("log", "embedder", "--container", "model-server",
                             check=False).stdout
                raise AssertionError(f"embedder exited ({st['exitCode']}):\n{log}")
            time.sleep(1.0)
    assert healthy, "embedding server did not become healthy in 90s"

    body = json.dumps({"inputs": ["hello world", "tpu native"]}).encode()
    r = urllib.request.urlopen(
        urllib.request.Request("http://127.0.0.1:9473/v1/embed", data=body,
                               headers={"Content-Type": "application/json"}),
        timeout=60,
    )
    out = json.loads(r.read())
    assert out["numSequences"] == 2
    assert len(out["embeddings"]) == 2
    assert len(out["embeddings"][0]) == out["dim"]
    import math

    norm = math.sqrt(sum(x * x for x in out["embeddings"][0]))
    assert abs(norm - 1.0) < 1e-3

    # The generate route must clearly reject on an embedding cell.
    req = urllib.request.Request("http://127.0.0.1:9473/v1/generate",
                                 data=b"{}",
                                 headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=10)
        raise AssertionError("generate on an embedding cell should 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404

    d.kuke("delete", "cell", "embedder", "--force")


def test_host_port_conflict_rejected(daemon):
    """VERDICT r3 item 7: host-network cells claim real host ports at create;
    a second cell claiming the same port/proto must be rejected with a
    pointer to the holder, not fail later with EADDRINUSE in the workload."""
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {{name: {name}}}
spec:
  containers:
    - name: main
      command: ["sleep", "30"]
      hostNetwork: true
      ports: [{{port: 9777}}]
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest.format(name="portsa"))
    p = d.kuke("apply", "-f", "-", stdin_data=manifest.format(name="portsb"),
               check=False)
    assert p.returncode != 0
    assert "9777" in (p.stdout + p.stderr)
    assert "portsa" in (p.stdout + p.stderr)

    # UDP on the same number is a distinct claim; and deleting the holder
    # frees the TCP claim.
    udp = manifest.format(name="portsc").replace(
        "ports: [{port: 9777}]", "ports: [{port: 9777, protocol: udp}]")
    d.kuke("apply", "-f", "-", stdin_data=udp)
    d.kuke("delete", "cell", "portsa", "--force")
    d.kuke("apply", "-f", "-", stdin_data=manifest.format(name="portsb"))

    # Compatible update (ports are a compatible field) must move the claim:
    # portsb drops 9777 for 9778, freeing 9777 for a new cell.
    moved = manifest.format(name="portsb").replace("port: 9777", "port: 9778")
    out = d.kuke("apply", "-f", "-", stdin_data=moved).stdout
    assert "updated" in out
    d.kuke("apply", "-f", "-", stdin_data=manifest.format(name="portsd"))


def test_repo_clone_and_setup_status(daemon, tmp_path):
    """VERDICT r3 item 7: a cell with a repo spec sees the clone at its
    declared path and the setup status is reported (reference:
    cmd/kuketty/repos.go + internal/kuketty/setupstatus)."""
    d = daemon
    import subprocess as sp

    src = tmp_path / "srcrepo"
    src.mkdir()
    (src / "hello.txt").write_text("from-the-repo\n")
    for argv in (["git", "init", "-q"],
                 ["git", "add", "."],
                 ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                  "commit", "-qm", "init"]):
        sp.run(argv, cwd=src, check=True, capture_output=True)

    manifest = f"""
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {{name: repocell}}
spec:
  containers:
    - name: main
      command: ["sh", "-c",
                "cat /work/hello.txt; cat /run/kukeon/setup-status.json; sleep 20"]
      repos:
        - {{url: "file://{src}", path: /work}}
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest)
    time.sleep(2)
    rec = json.loads(d.kuke("--json", "get", "cells", "repocell").stdout)
    setup = rec["status"].get("setup") or []
    assert setup and setup[0]["state"] == "ready", setup
    assert setup[0]["path"] == "/work"

    log = d.kuke("log", "repocell").stdout
    assert "from-the-repo" in log
    assert '"state": "ready"' in log   # in-cell setup-status report
    d.kuke("delete", "cell", "repocell", "--force")


def test_repo_clone_failure_reported_not_fatal(daemon):
    """A bad repo URL must surface as setup state=failed while the cell
    still starts (report-don't-block, like the reference's stages)."""
    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: badrepo}
spec:
  containers:
    - name: main
      command: ["sh", "-c", "echo alive; sleep 15"]
      repos:
        - {url: "file:///nonexistent/nowhere.git", path: /work}
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest)
    time.sleep(2)
    rec = json.loads(d.kuke("--json", "get", "cells", "badrepo").stdout)
    setup = rec["status"].get("setup") or []
    assert setup and setup[0]["state"] == "failed", setup
    assert setup[0].get("error")
    assert rec["status"]["containers"][0]["state"] == "running"
    d.kuke("delete", "cell", "badrepo", "--force")


def test_instance_pinning_refuses_reconfigured_run_path(daemon):
    """VERDICT r3 item 9: a daemon must refuse a run path bootstrapped under
    different settings (reference: internal/instance/instance.go:21-28)."""
    d = daemon
    # The fixture's daemon pinned the default subnet pool at bootstrap.
    assert os.path.exists(os.path.join(d.run_path, "instance.json"))
    d.stop_daemon_only()
    with pytest.raises(RuntimeError, match="bootstrapped under different"):
        Daemon(run_path=d.run_path,
               env_overrides={"KUKEON_POD_SUBNET_CIDR": "10.200.0.0/16"})


def test_doctor_lists_enforcement_layers(daemon):
    out = daemon.kuke("doctor").stdout
    for tool in ("kukepause", "kukeshim", "kuketty", "kukecell", "kukenet"):
        assert f"native/{tool}" in out and "MISSING" not in out.split(f"native/{tool}")[1].split("\n")[0]
    assert "isolation" in out
    assert "net-enforce" in out
    assert "instance" in out


def test_init_provisions_kukeon_group():
    """kuke init (root) provisions the `kukeon` group and the daemon socket
    carries its gid (reference: internal/sysuser + SocketGID)."""
    import grp
    import stat as _stat

    if os.geteuid() != 0:
        pytest.skip("group provisioning needs root")
    sys.path.insert(0, REPO)
    from kukeon_tpu.runtime import sysuser

    gid = sysuser.ensure_group()
    assert gid is not None
    assert grp.getgrnam("kukeon").gr_gid == gid
    # A daemon started after provisioning hands the socket to the group.
    d = Daemon()
    try:
        st = os.stat(d.socket_path)
        assert st.st_gid == gid
        assert _stat.S_IMODE(st.st_mode) == 0o660
    finally:
        d.stop()


def test_attach_through_real_pty(daemon):
    """VERDICT r3 item 10 (carried since r1): drive the ACTUAL `kuke attach`
    client under a real PTY — raw mode, keystrokes, Ctrl-] Ctrl-] detach,
    workload survival, and re-attach continuity (reference:
    e2e/e2e_pty_test.go:33-45 drives kuke attach with creack/pty)."""
    import errno
    import pty as _pty
    import select as _select

    d = daemon
    d.kuke("apply", "-f", "-", stdin_data=ATTACH_MANIFEST)

    def spawn_attach():
        pid, fd = _pty.fork()
        if pid == 0:  # child: exec the real CLI under the PTY
            os.execvpe(
                sys.executable,
                CLI + ["--socket", d.socket_path, "--run-path", d.run_path,
                       "attach", "term"],
                d.env,
            )
        return pid, fd

    def read_until(fd, needle: bytes, timeout: float = 30.0) -> bytes:
        buf = b""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            r, _, _ = _select.select([fd], [], [], 0.5)
            if not r:
                continue
            try:
                chunk = os.read(fd, 4096)
            except OSError as e:
                if e.errno == errno.EIO:   # PTY closed
                    break
                raise
            if not chunk:
                break
            buf += chunk
            if needle in buf:
                return buf
        raise AssertionError(f"never saw {needle!r} in PTY output:\n{buf!r}")

    # --- session 1: banner, command echo, detach --------------------------
    pid, fd = spawn_attach()
    try:
        read_until(fd, b"(attached")
        os.write(fd, b"echo pty-marker-$((40+2))\n")
        read_until(fd, b"pty-marker-42")
        os.write(fd, b"\x1d\x1d")          # Ctrl-] twice = detach
        _, status = os.waitpid(pid, 0)
        assert os.waitstatus_to_exitcode(status) == 0, "detach must exit 0"
    finally:
        try:
            os.close(fd)
        except OSError:
            pass

    # The workload survives the detach.
    rec = json.loads(d.kuke("--json", "get", "cells", "term").stdout)
    st = rec["status"]["containers"][0]
    assert st["state"] == "running"
    os.kill(st["pid"], 0)

    # --- session 2: re-attach sees terminal continuity ---------------------
    pid, fd = spawn_attach()
    try:
        read_until(fd, b"(attached")
        os.write(fd, b"echo second-session-$((41+1))\n")
        read_until(fd, b"second-session-42")
        os.write(fd, b"\x1d\x1d")
        os.waitpid(pid, 0)
    finally:
        try:
            os.close(fd)
        except OSError:
            pass

    # The capture transcript records both sessions (continuity evidence).
    cap = d.kuke("log", "term").stdout
    assert "pty-marker-42" in cap
    assert "second-session-42" in cap
    d.kuke("delete", "cell", "term", "--force")


def test_doctor_tpu_runtime_probe(monkeypatch):
    """probe_tpu_runtime distinguishes a live runtime from a wedged one
    (device nodes visible, first transfer hangs)."""
    from kukeon_tpu.runtime.devices import probe_tpu_runtime

    # Pin the child to CPU: the probe must exercise a REAL backend, and the
    # CPU platform is the one this CI host can always answer on.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("KUKEON_TPU_CHIPS", "")   # no chips claimed
    state, detail = probe_tpu_runtime(timeout_s=120.0)
    assert state == "ok", detail
    assert "backend=cpu" in detail

    # Chips visible but the backend fell back to CPU (TPU init failed
    # non-fatally): must NOT read as ok.
    monkeypatch.setenv("KUKEON_TPU_CHIPS", "0,1")
    state, detail = probe_tpu_runtime(timeout_s=120.0)
    assert state == "unavailable"
    assert "chips visible but backend=cpu" in detail

    # A wedged runtime = the child never returns: simulated with a child
    # that blocks forever (what a hung libtpu transfer looks like).
    import subprocess as _sp

    real_run = _sp.run

    def hang(cmd, **kw):
        return real_run([cmd[0], "-c", "import time; time.sleep(60)"],
                        **{**kw, "timeout": kw.get("timeout")})

    monkeypatch.setattr(_sp, "run", hang)
    state, detail = probe_tpu_runtime(timeout_s=0.5)
    assert state == "wedged"
    assert "did not finish" in detail


def test_moe_model_cell_e2e(daemon):
    """A mixtral (MoE) model cell boots through the same manifest path and
    answers /v1/generate — the model registry + pluggable engine running
    under the real daemon."""
    import urllib.request

    d = daemon
    manifest = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: moe}
spec:
  model: {model: mixtral-tiny, chips: 1, port: 9478, numSlots: 2,
          maxSeqLen: 128, hostNetwork: true}
"""
    d.kuke("apply", "-f", "-", stdin_data=manifest)
    deadline = time.monotonic() + 120.0
    healthy = False
    while time.monotonic() < deadline:
        try:
            r = urllib.request.urlopen("http://127.0.0.1:9478/v1/health", timeout=1)
            healthy = json.loads(r.read())["status"] == "ok"
            break
        except OSError:
            rec = json.loads(d.kuke("--json", "get", "cells", "moe").stdout)
            st = rec["status"]["containers"][0]
            if st["state"] == "exited":
                log = d.kuke("log", "moe", "--container", "model-server",
                             check=False).stdout
                raise AssertionError(
                    f"moe server exited ({st['exitCode']}):\n{log}")
            time.sleep(1.0)
    assert healthy, "moe model server did not become healthy in 120s"

    body = json.dumps({"prompt": "hello", "maxNewTokens": 3}).encode()
    r = urllib.request.urlopen(
        urllib.request.Request("http://127.0.0.1:9478/v1/generate", data=body,
                               headers={"Content-Type": "application/json"}),
        timeout=60,
    )
    assert json.loads(r.read())["numTokens"] == 3
    d.kuke("delete", "cell", "moe", "--force")
