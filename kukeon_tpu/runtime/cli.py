"""kuke: the CLI (reference: cmd/kuke, 23 verbs).

Verbs: init, daemon (serve/start/stop/kill/restart/status/logs/metrics),
apply,
create, delete, get, run, start, stop, kill, attach, log, purge, refresh,
rollout, status, top, trace, query, alerts, doctor, image, build, team,
uninstall, version, autocomplete.

Workload verbs route to the daemon; read/maintenance verbs "promote" to an
in-process controller when --no-daemon / KUKEON_NO_DAEMON is set (reference
process model: docs/site/architecture/process-model.md). Every knob resolves
flag > env > configuration document > default through the config registry
(kukeon_tpu/runtime/config.py).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import yaml

from kukeon_tpu import __version__
from kukeon_tpu.runtime import consts
from kukeon_tpu.runtime.client import LocalClient, UnixClient
from kukeon_tpu.runtime.errors import KukeonError


def _parse_kv_args(pairs, flag: str) -> dict[str, str]:
    """KEY=VALUE arg list -> dict, with a usage error (not a traceback) on a
    malformed pair."""
    out = {}
    for kv in pairs or []:
        k, sep, v = kv.partition("=")
        if not sep or not k:
            raise KukeonError(f"{flag} wants KEY=VALUE, got {kv!r}")
        out[k] = v
    return out


def _client_settings():
    """Client-side knob resolution: flag > env > ClientConfiguration doc >
    default (reference: cmd/config precedence; internal/clientconfig)."""
    from kukeon_tpu.runtime import config

    try:
        return config.client_settings()
    except KukeonError as e:
        print(f"warning: {e}", file=sys.stderr)
        return config.Settings()


def _run_path(args) -> str:
    return _client_settings().get("KUKEON_RUN_PATH", args.run_path)


def _client(args):
    s = _client_settings()
    if getattr(args, "no_daemon", False) or s.get("KUKEON_NO_DAEMON"):
        return LocalClient(_run_path(args))
    sock = s.get("KUKEOND_SOCKET", args.socket) or consts.socket_path(_run_path(args))
    return UnixClient(sock)


def _scope(args) -> dict:
    return {
        "realm": getattr(args, "realm", None) or consts.DEFAULT_REALM,
        "space": getattr(args, "space", None) or consts.DEFAULT_SPACE,
        "stack": getattr(args, "stack", None) or consts.DEFAULT_STACK,
    }


def _print(obj, as_json=False):
    if as_json:
        print(json.dumps(obj, indent=2))
    else:
        print(yaml.safe_dump(obj, sort_keys=False, default_flow_style=False).rstrip())


# --- verb implementations ----------------------------------------------------

def cmd_image(args):
    c = _client(args)
    sub = args.image_cmd
    if sub in ("get", "delete", "save", "load") and not args.ref:
        print(f"error: image {sub} needs an image ref", file=sys.stderr)
        return 2
    if sub == "load" and not args.input:
        print("error: image load needs -i/--input <tarball>", file=sys.stderr)
        return 2
    if sub == "save" and not args.output:
        print("error: image save needs -o/--output <tarball>", file=sys.stderr)
        return 2
    if sub == "list":
        rows = c.call("ListImages")
        if args.json:
            _print(rows, True)
        else:
            print(f"{'REF':40} {'PARENT':30} CREATED")
            for m in rows:
                created = time.strftime("%Y-%m-%d %H:%M",
                                        time.localtime(m["createdAt"]))
                print(f"{m['name'] + ':' + m['tag']:40} "
                      f"{m['parent'] or '-':30} {created}")
    elif sub == "get":
        _print(c.call("GetImage", ref=args.ref), args.json)
    elif sub == "delete":
        c.call("DeleteImage", ref=args.ref)
        print(f"image/{args.ref}: deleted")
    elif sub == "prune":
        removed = c.call("PruneImages")
        for r in removed:
            print(f"image/{r}: pruned")
        print(f"{len(removed)} image(s) pruned")
    elif sub == "load":
        m = c.call("LoadImage", tarPath=os.path.abspath(args.input), ref=args.ref)
        print(f"image/{m['name']}:{m['tag']}: loaded")
    elif sub == "pull":
        if not args.ref:
            print("error: image pull needs a registry/repo[:tag] ref", file=sys.stderr)
            return 2
        m = c.call("PullImage", ref=args.ref,
                   insecure=True if args.insecure else None)
        print(f"image/{m['name']}:{m['tag']}: pulled")
    elif sub == "push":
        if not args.ref:
            print("error: image push needs a local image ref", file=sys.stderr)
            return 2
        pushed = c.call("PushImage", ref=args.ref, dest=args.to,
                        insecure=True if args.insecure else None)
        print(f"image/{args.ref}: pushed to {pushed}")
    elif sub == "save":
        c.call("SaveImage", ref=args.ref, tarPath=os.path.abspath(args.output))
        print(f"image/{args.ref}: saved to {args.output}")
    else:
        print(f"unknown image subcommand {sub!r}", file=sys.stderr)
        return 2
    return 0


def cmd_build(args):
    # Standalone like the reference's kukebuild: writes straight into the
    # store, no daemon required.
    from kukeon_tpu.runtime.images import ImageBuilder, ImageStore

    context = os.path.abspath(args.context)
    kukefile = args.file or os.path.join(context, "Kukefile")
    build_args = _parse_kv_args(args.build_arg, "--build-arg")
    builder = ImageBuilder(ImageStore(_run_path(args)))
    m = builder.build(kukefile, context_dir=context, tag=args.tag,
                      build_args=build_args)
    print(f"image/{m.ref}: built")
    return 0


def cmd_team(args):
    from kukeon_tpu.runtime.teams import TeamHost, team_init

    if args.team_cmd != "init":
        print(f"unknown team subcommand {args.team_cmd!r}", file=sys.stderr)
        return 2
    c = None if args.dry_run else _client(args)

    def apply_fn(blob, team, prune):
        return c.call("ApplyDocuments", yaml=blob, team=team, prune=prune)

    builder = None
    pusher = None
    if args.build:
        try:
            from kukeon_tpu.runtime.images import ImageBuilder, ImageStore
        except ImportError:
            print("error: the image builder is not available in this build; "
                  "run team init without --build", file=sys.stderr)
            return 1
        builder = ImageBuilder(ImageStore(_run_path(args)))
    if getattr(args, "push", False):
        from kukeon_tpu.runtime import registry as regmod
        from kukeon_tpu.runtime.images import ImageStore, split_ref

        def pusher(tag, reg):
            _, repo, t = regmod.parse_image_ref(tag)
            return regmod.push(ImageStore(_run_path(args)), tag,
                               dest=f"{reg}/{repo}:{t}")
    res = team_init(
        None if args.dry_run else apply_fn,
        args.file,
        host=TeamHost(),
        dry_run=args.dry_run,
        build=args.build,
        builder=builder,
        pusher=pusher,
    )
    print(f"team {res.project}: source at {res.checkout}")
    if res.built_images:
        for img in res.built_images:
            print(f"  built {img}")
    for img in res.pushed_images:
        print(f"  pushed {img}")
    if res.secret_names:
        print(f"  secrets: {', '.join(res.secret_names)}")
    if args.dry_run and res.rendered:
        from kukeon_tpu.runtime.apply.parser import dump_documents

        print(dump_documents(res.rendered.blueprints + res.rendered.configs))
        return 0
    for r in res.applied:
        print(f"  {r['kind'].lower()}/{r['name']} ({r['scope']}): {r['action']}")
    return 0


def cmd_version(args):
    del args
    print(f"kuke {__version__} (kukeon-tpu)")
    return 0


def cmd_init(args):
    """Host bootstrap: run path, hierarchy, daemon start (reference:
    cmd/kuke/init, init.go:484)."""
    run_path = _run_path(args)
    os.makedirs(run_path, exist_ok=True)
    local = LocalClient(run_path)     # bootstrap happens in the constructor
    del local
    # System group so non-root clients can dial the 0660 socket
    # (reference: internal/sysuser — kuke init provisions `kukeon`).
    from kukeon_tpu.runtime import sysuser

    gid = sysuser.ensure_group()
    if gid is not None:
        sysuser.chown_tree(run_path, gid)
        print(f"Group: {sysuser.GROUP} (gid {gid})")
    print(f"Run path: {run_path}")
    print(f"Realm: {consts.DEFAULT_REALM}")
    print(f"System realm: {consts.SYSTEM_REALM}")
    if not args.no_daemon_start:
        rc = _daemon_start(run_path, args.socket)
        if rc != 0:
            return rc
        print(f"kukeond is ready (unix://{args.socket or consts.socket_path(run_path)})")
    return 0


def _daemon_start(run_path: str, socket_path: str | None) -> int:
    sock = socket_path or consts.socket_path(run_path)
    if os.path.exists(sock):
        try:
            UnixClient(sock).call("Ping")
            print("daemon already running")
            return 0
        except KukeonError:
            pass
    log_path = os.path.join(run_path, "kukeond.log")
    with open(log_path, "a") as log:
        subprocess.Popen(
            [sys.executable, "-m", "kukeon_tpu.runtime.cli", "daemon", "serve",
             "--run-path", run_path, "--socket", sock],
            stdout=log, stderr=log, stdin=subprocess.DEVNULL,
            start_new_session=True,
        )
    deadline = time.monotonic() + 10.0   # reference: e2e daemon budget <=10s
    while time.monotonic() < deadline:
        try:
            UnixClient(sock).call("Ping")
            return 0
        except KukeonError:
            time.sleep(0.1)
    print(f"error: daemon did not come up within 10s (see {log_path})", file=sys.stderr)
    return 1


def cmd_daemon(args):
    run_path = _run_path(args)
    sock = args.socket or consts.socket_path(run_path)
    if args.daemon_cmd == "serve":
        from kukeon_tpu.runtime.daemon import DaemonServer

        # Socket + interval resolution (flag > env > ServerConfiguration
        # doc > default) happens inside DaemonServer via the config registry.
        DaemonServer(run_path, args.socket).serve()
        return 0
    if args.daemon_cmd == "start":
        return _daemon_start(run_path, args.socket)
    if args.daemon_cmd in ("stop", "kill"):
        pid_file = os.path.join(run_path, "kukeond.pid")
        try:
            pid = int(open(pid_file).read().strip())
        except (OSError, ValueError):
            print("daemon not running (no pid file)")
            return 0
        sig = signal.SIGTERM if args.daemon_cmd == "stop" else signal.SIGKILL
        try:
            os.kill(pid, sig)
        except ProcessLookupError:
            print("daemon not running (stale pid)")
            return 0
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            try:
                os.kill(pid, 0)
                time.sleep(0.1)
            except ProcessLookupError:
                break
        else:
            os.kill(pid, signal.SIGKILL)
        print("daemon stopped")
        return 0
    if args.daemon_cmd == "status":
        try:
            _print(UnixClient(sock).call("Status"), args.json)
            return 0
        except KukeonError as e:
            print(f"daemon unreachable: {e}", file=sys.stderr)
            return 1
    if args.daemon_cmd == "metrics":
        # Prometheus text straight from the daemon's registry: cell
        # lifecycle (starts/restarts/exit codes/backoff/uptime), reconcile
        # loop, RPC traffic, fault-injection fire counts.
        try:
            out = UnixClient(sock).call("Metrics")
        except KukeonError as e:
            print(f"daemon unreachable: {e}", file=sys.stderr)
            return 1
        print(out["text"], end="")
        return 0
    if args.daemon_cmd == "logs":
        log_path = os.path.join(run_path, "kukeond.log")
        return _tail(log_path, follow=args.follow)
    if args.daemon_cmd == "restart":
        args.daemon_cmd = "stop"
        cmd_daemon(args)
        return _daemon_start(run_path, args.socket)
    print(f"unknown daemon subcommand {args.daemon_cmd!r}", file=sys.stderr)
    return 2


def cmd_apply(args):
    blob = sys.stdin.read() if args.file == "-" else open(args.file).read()
    c = _client(args)
    results = c.call("ApplyDocuments", yaml=blob, team=args.team, prune=args.prune)
    for r in results:
        print(f"{r['kind'].lower()}/{r['name']} ({r['scope']}): {r['action']}")
    return 0


def cmd_delete(args):
    c = _client(args)
    if args.file:
        blob = sys.stdin.read() if args.file == "-" else open(args.file).read()
        for r in c.call("DeleteDocuments", yaml=blob):
            print(f"{r['kind'].lower()}/{r['name']} ({r['scope']}): {r['action']}")
        return 0
    kind, name = args.kind, args.name
    s = _scope(args)
    if kind in ("cell", "cells"):
        c.call("DeleteCell", **s, name=name, force=args.force)
    elif kind in ("realm", "realms"):
        c.call("DeleteRealm", name=name, purge=args.force)
    elif kind in ("space", "spaces"):
        c.call("DeleteSpace", realm=s["realm"], name=name, purge=args.force)
    elif kind in ("stack", "stacks"):
        c.call("DeleteStack", realm=s["realm"], space=s["space"], name=name, purge=args.force)
    elif kind in ("secret", "secrets"):
        c.call("DeleteSecret", realm=s["realm"], space=args.space, stack=args.stack, name=name)
    elif kind in ("blueprint", "blueprints", "cellblueprint"):
        c.call("DeleteBlueprint", realm=s["realm"], space=args.space, stack=args.stack, name=name)
    elif kind in ("config", "configs", "cellconfig"):
        c.call("DeleteConfig", realm=s["realm"], space=args.space, stack=args.stack, name=name)
    elif kind in ("volume", "volumes"):
        c.call("DeleteVolume", realm=s["realm"], space=args.space, stack=args.stack, name=name)
    else:
        print(f"unknown kind {kind!r}", file=sys.stderr)
        return 2
    print(f"{kind}/{name}: deleted")
    return 0


def cmd_create(args):
    """Imperative create (reference: cmd/kuke/create — realm, space, stack,
    cell, secret, volume by name or any kind via -f)."""
    c = _client(args)
    s = _scope(args)
    if args.file:
        blob = sys.stdin.read() if args.file == "-" else open(args.file).read()
        results = c.call("ApplyDocuments", yaml=blob)
        for r in results:
            print(f"{r['kind'].lower()}/{r['name']} ({r['scope']}): {r['action']}")
        return 0
    kind, name = args.kind, args.name
    if not kind or not name:
        print("error: kuke create wants -f FILE or KIND NAME", file=sys.stderr)
        return 2
    if kind in ("realm", "realms"):
        c.call("CreateRealm", name=name)
    elif kind in ("space", "spaces"):
        c.call("CreateSpace", realm=s["realm"], name=name)
    elif kind in ("stack", "stacks"):
        c.call("CreateStack", realm=s["realm"], space=s["space"], name=name)
    elif kind in ("cell", "cells"):
        main = {"name": "main"}
        if args.image:
            main["image"] = args.image
        if args.command:
            main["command"] = args.command
        doc = {
            "apiVersion": "kukeon.io/v1beta1", "kind": "Cell",
            "metadata": {"name": name, **{k: v for k, v in s.items() if v}},
            "spec": {"containers": [main]},
        }
        rec = c.call("CreateCell", doc=doc, start=not args.no_start)
        print(f"cell/{name}: {rec['status']['phase']}")
        return 0
    elif kind in ("secret", "secrets"):
        data = _parse_kv_args(args.data, "--data")
        if not data:
            print("error: kuke create secret wants --data KEY=VALUE", file=sys.stderr)
            return 2
        blob = yaml.safe_dump({
            "apiVersion": "kukeon.io/v1beta1", "kind": "Secret",
            "metadata": {"name": name, "realm": s["realm"]},
            "spec": {"data": data},
        })
        c.call("ApplyDocuments", yaml=blob)
    elif kind in ("volume", "volumes"):
        blob = yaml.safe_dump({
            "apiVersion": "kukeon.io/v1beta1", "kind": "Volume",
            "metadata": {"name": name, "realm": s["realm"]},
            "spec": {"reclaimPolicy": args.reclaim_policy},
        })
        c.call("ApplyDocuments", yaml=blob)
    else:
        print(f"unknown kind {kind!r}", file=sys.stderr)
        return 2
    print(f"{kind}/{name}: created")
    return 0


def cmd_get(args):
    c = _client(args)
    s = _scope(args)
    kind = args.kind
    if kind in ("realms", "realm"):
        if args.name:
            _print(c.call("GetRealm", name=args.name), args.json)
        else:
            for r in c.call("ListRealms"):
                print(r)
    elif kind in ("spaces", "space"):
        if args.name:
            _print(c.call("GetSpace", realm=s["realm"], name=args.name), args.json)
        else:
            for x in c.call("ListSpaces", realm=s["realm"]):
                print(x)
    elif kind in ("stacks", "stack"):
        if args.name:
            _print(c.call("GetStack", realm=s["realm"], space=s["space"], name=args.name), args.json)
        else:
            for x in c.call("ListStacks", realm=s["realm"], space=s["space"]):
                print(x)
    elif kind in ("cells", "cell"):
        if args.name:
            _print(c.call("GetCell", **s, name=args.name), args.json)
        else:
            rows = c.call("ListCells", realm=s["realm"],
                          space=getattr(args, "space", None),
                          stack=getattr(args, "stack", None))
            if args.json:
                _print(rows, True)
            else:
                fmt = "{:<24} {:<10} {:<28} {:<9} {:<10} {}"
                print(fmt.format("NAME", "PHASE", "SCOPE", "CHIPS", "SYNC", "CONTAINERS"))
                for r in rows:
                    scope = f"{r['realm']}/{r['space']}/{r['stack']}"
                    chips = ",".join(map(str, r["status"].get("tpuChips", []))) or "-"
                    st = r["status"]
                    # SYNC column mirrors the reference's three-way verdict:
                    # config-lineage cells show Synced/OutOfSync/Error, others "-".
                    if st.get("outOfSyncError"):
                        sync = "Error"
                    elif st.get("outOfSync"):
                        sync = "OutOfSync"
                    elif (r.get("provenance") or {}).get("config"):
                        sync = "Synced"
                    else:
                        sync = "-"
                    conts = ",".join(
                        f"{cs['name']}:{cs['state']}"
                        + (f"(x{cs['restarts']})" if cs.get("restarts") else "")
                        for cs in st["containers"]
                    )
                    print(fmt.format(r["name"], st["phase"], scope, chips, sync, conts))
    elif kind in ("secrets", "secret"):
        for x in c.call("ListSecrets", realm=s["realm"], space=args.space, stack=args.stack):
            print(x)
    elif kind in ("blueprints", "blueprint", "cellblueprints"):
        for x in c.call("ListBlueprints", realm=s["realm"], space=args.space, stack=args.stack):
            print(x)
    elif kind in ("configs", "config", "cellconfigs"):
        for x in c.call("ListConfigs", realm=s["realm"], space=args.space, stack=args.stack):
            print(x)
    elif kind in ("volumes", "volume"):
        for x in c.call("ListVolumes", realm=s["realm"], space=args.space, stack=args.stack):
            print(x)
    else:
        print(f"unknown kind {kind!r}", file=sys.stderr)
        return 2
    return 0


def cmd_lifecycle(args):
    c = _client(args)
    s = _scope(args)
    out = c.call(args.verb.capitalize() + "Cell", **s, name=args.name)
    print(f"cell/{args.name}: {out['status']['phase']}")
    return 0


def cmd_run(args):
    """Create-or-attach state machine (reference: cmd/kuke/run)."""
    c = _client(args)
    s = _scope(args)
    name = args.name

    if args.from_blueprint:
        values = _parse_kv_args(args.param, "--param")
        rec = c.call("RunBlueprint", realm=s["realm"], space=s["space"], stack=s["stack"],
                     blueprint=args.from_blueprint, values=values)
        name = rec["name"]
    elif args.from_config:
        rec = c.call("MaterializeConfig", realm=s["realm"], space=s["space"],
                     stack=s["stack"], name=args.from_config)
        name = rec["name"]
    elif args.file:
        blob = sys.stdin.read() if args.file == "-" else open(args.file).read()
        docs = list(yaml.safe_load_all(blob))
        cells = [d for d in docs if d and d.get("kind") == "Cell"]
        if len(cells) != 1:
            print("error: kuke run -f needs exactly one Cell document", file=sys.stderr)
            return 2
        doc = cells[0]
        if args.rm:
            doc.setdefault("spec", {})["autoDelete"] = True
        name = doc.get("metadata", {}).get("name")
        md = doc.get("metadata", {})
        s = {"realm": md.get("realm") or s["realm"], "space": md.get("space") or s["space"],
             "stack": md.get("stack") or s["stack"]}
        try:
            existing = c.call("GetCell", **s, name=name)
        except KukeonError:
            existing = None
        if existing is None:
            rec = c.call("CreateCell", doc=doc)
        elif existing["status"]["phase"] in ("stopped", "failed"):
            rec = c.call("StartCell", **s, name=name)
        else:
            rec = existing
    elif name:
        try:
            rec = c.call("GetCell", **s, name=name)
            if rec["status"]["phase"] in ("stopped", "failed"):
                rec = c.call("StartCell", **s, name=name)
        except KukeonError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
    else:
        print("error: kuke run needs a cell name, -f, -b, or -c", file=sys.stderr)
        return 2

    print(f"cell/{name}: {rec['status']['phase']}")
    if args.detach:
        return 0
    return _attach(c, s, name, args.container)


def cmd_attach(args):
    c = _client(args)
    s = _scope(args)
    return _attach(c, s, args.name, args.container)


def _attach(c, s, name, container) -> int:
    from kukeon_tpu.runtime.attach import run_attach

    info = c.call("AttachContainer", realm=s["realm"], space=s["space"],
                  stack=s["stack"], cell=name, container=container)
    return run_attach(info["socketPath"])


def cmd_log(args):
    c = _client(args)
    s = _scope(args)
    info = c.call("Log", realm=s["realm"], space=s["space"], stack=s["stack"],
                  cell=args.name, container=args.container)
    return _tail(info["path"], follow=args.follow)


def _tail(path: str, follow: bool = False) -> int:
    if not os.path.exists(path):
        print(f"(no log yet at {path})", file=sys.stderr)
        if not follow:
            return 1
    pos = 0
    try:
        while True:
            if os.path.exists(path):
                with open(path, "rb") as f:
                    f.seek(pos)
                    chunk = f.read()
                    pos = f.tell()
                if chunk:
                    sys.stdout.buffer.write(chunk)
                    sys.stdout.flush()
            if not follow:
                return 0
            time.sleep(1.0)   # reference: 1s poll (log.go:63-84)
    except KeyboardInterrupt:
        return 0


def cmd_status(args):
    try:
        c = _client(args)
        t0 = time.monotonic()
        ping = c.call("Ping")
        rtt_ms = (time.monotonic() - t0) * 1000
        status = c.call("Status")
        status["daemon"] = {"pid": ping["pid"], "rttMs": round(rtt_ms, 2),
                            "uptimeSeconds": round(ping["uptimeSeconds"], 1)}
        _print(status, args.json)
        return 0
    except KukeonError as e:
        print(f"daemon: unreachable ({e})", file=sys.stderr)
        return 1


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    for unit in ("B", "K", "M", "G", "T"):
        if abs(n) < 1024 or unit == "T":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}T"


def _fmt_ms(s) -> str:
    return "-" if s is None else f"{s * 1000:.0f}ms"


def render_top(rows, sparks=None) -> str:
    """The `kuke top` table as a string (pure so tests and the --watch
    repaint share it). ``sparks`` is {cell: {qps/p95/queue: [values]}}
    from the TSDB's range queries; when present each cell row grows a
    history line of sparklines drawn from the daemon's own scrape
    history rather than a single instantaneous scrape."""
    from kukeon_tpu.obs.tsdb import sparkline

    if not rows:
        return "no running model cells"
    # Staleness dimming: a row whose last GOOD scrape (ScrapeCells'
    # scrapeAgeS, from the daemon's kukeon_cell_scrape_age_seconds
    # bookkeeping) is older than 2 scrape intervals renders ANSI-dim —
    # its numbers are last-known-good, not current. Env name mirrors
    # daemon.SCRAPE_INTERVAL_ENV (not imported: the daemon module drags
    # in the whole controller stack).
    stale_after_s = 2 * float(
        os.environ.get("KUKEON_SCRAPE_INTERVAL_S", "") or 10.0)
    lines = []
    fmt = "{:<32} {:<8} {:<6} {:>7} {:>8} {:>8} {:>6} {:>14} {:>9}"
    lines.append(fmt.format("CELL", "MODEL", "READY", "QPS", "P50TTFT",
                            "P95TTFT", "QUEUE", "HBM", "RESTARTS"))
    for r in rows:
        if (r.get("scrapeAgeS") or 0.0) > stale_after_s:
            add = lambda ln: lines.append(f"\x1b[2m{ln}\x1b[0m")  # noqa: E731
        else:
            add = lines.append
        if not r.get("ok"):
            add(fmt.format(r["cell"], "-", "down", "-", "-", "-",
                           "-", "-", r.get("restarts", 0))
                + f"  ({r.get('error', 'scrape failed')})")
            continue
        if r.get("kind") == "gateway":
            # Gateway row: the replicated cell's front door. READY is the
            # replica census, QPS the aggregate over replicas; latency/HBM
            # live on the per-replica rows beneath it.
            ready = (f"{r.get('readyReplicas', 0)}/{r.get('replicas', '?')}")
            extra = f"  (gateway, retries={r.get('retries', 0)}"
            if r.get("scale"):
                # Autoscaled cell: the FleetScaler's current target and
                # the declared bounds.
                sc = r["scale"]
                extra += (f", scale={sc.get('desired', '?')}"
                          f"[{sc.get('min', 1)}..{sc.get('max', '?')}]")
            if r.get("handoffs"):
                # Disaggregated fleet: how many prefill->decode KV
                # handoffs this gateway drove, at what median cost.
                extra += (f", handoffs={r['handoffs']}"
                          + (f" p50={r['handoffMsP50']}ms"
                             if r.get("handoffMsP50") is not None else "")
                          + (f" fallbacks={r['handoffFallbacks']}"
                             if r.get("handoffFallbacks") else ""))
            add(fmt.format(
                r["cell"], r.get("model") or "-", ready,
                f"{r['qps']:.1f}" if r.get("qps") is not None else "-",
                "-", "-", "-", "-", r.get("restarts", 0))
                + extra + ")")
            continue
        hbm = "-"
        if r.get("hbmInUseBytes") is not None:
            hbm = (f"{_fmt_bytes(r['hbmInUseBytes'])}"
                   f"/{_fmt_bytes(r.get('hbmLimitBytes'))}")
        # The TTFT histogram's top-bucket exemplar: the p95 row links
        # directly to a reconstructable trace (`kuke trace <id>`).
        exemplar = (f"  (p95 trace={r['ttftP95TraceId']})"
                    if r.get("ttftP95TraceId") else "")
        add(fmt.format(
            r["cell"], r.get("model") or "-",
            "yes" if r.get("ready") else "no",
            f"{r['qps']:.1f}" if r.get("qps") is not None else "-",
            _fmt_ms(r.get("ttftP50S")), _fmt_ms(r.get("ttftP95S")),
            r.get("queueDepth", "-"), hbm, r.get("restarts", 0))
            + exemplar)
        if r.get("meshChips", 1) > 1 and r.get("hbmPerDevice"):
            # Sharded cell: one line per chip of the serving mesh. The
            # aggregate HBM cell above hides shard skew — a single chip
            # near its limit OOMs the whole mesh, so show each one with
            # its high-water mark.
            for dev, h in r["hbmPerDevice"].items():
                add(
                    f"  chip {dev}: hbm {_fmt_bytes(h.get('inUse'))}"
                    f"/{_fmt_bytes(h.get('limit'))}"
                    f" peak {_fmt_bytes(h.get('peak'))}")
        sp = (sparks or {}).get(r["cell"])
        if sp:
            add("  {:<30} qps {:<12} p95 {:<12} queue {:<12}".format(
                "history:", sparkline(sp.get("qps", ()), 10),
                sparkline(sp.get("p95", ()), 10),
                sparkline(sp.get("queue", ()), 10)).rstrip())
    return "\n".join(lines)


def _top_sparklines(c) -> dict:
    """Three range queries against the daemon's TSDB -> per-cell value
    lists for the --watch history columns (QPS summed over outcome
    series). A daemon without history yet (or an old one without the
    Query RPC) simply yields no sparklines."""
    out: dict[str, dict[str, list]] = {}
    specs = (("qps", "kukeon_engine_requests_total", "rate"),
             ("p95", "kukeon_engine_ttft_seconds", "p95"),
             ("queue", "kukeon_engine_queue_depth", "avg"))
    for col, family, agg in specs:
        try:
            res = c.call("Query", expr=family, windowS="5m", agg=agg,
                         stepS="30s")
        except KukeonError:
            continue
        for row in res.get("range", []):
            cell = row["labels"].get("cell")
            if not cell:
                continue
            vals = row["values"]
            slot = out.setdefault(cell, {})
            prev = slot.get(col)
            if prev is None:
                slot[col] = list(vals)
            else:
                # requests_total carries an outcome label: sum the
                # per-outcome rate series into one QPS line.
                slot[col] = [
                    None if (a is None and b is None)
                    else (a or 0) + (b or 0)
                    for a, b in zip(prev, vals)]
    return out


def cmd_top(args):
    """One-screen fleet view from a single federated scrape: the daemon
    pulls every running model cell's /metrics (ScrapeCells) and this
    renders the per-cell table — ready, QPS, TTFT p50/p95, queue depth,
    HBM, restarts. Unreachable cells show their scrape error instead of
    silently vanishing. ``--watch`` repaints in place and adds per-cell
    sparkline history (QPS, TTFT p95, queue depth) from the daemon's
    in-memory scrape history instead of a single scrape."""
    watch = getattr(args, "watch", False)
    interval = getattr(args, "interval", None) or 5.0
    c = _client(args)
    try:
        while True:
            try:
                out = c.call("ScrapeCells")
            except KukeonError as e:
                print(f"daemon unreachable: {e}", file=sys.stderr)
                return 1
            rows = out.get("cells", [])
            if args.json:
                _print(rows, True)
                return 0
            sparks = _top_sparklines(c) if (watch and rows) else None
            body = render_top(rows, sparks)
            if watch:
                sys.stdout.write("\x1b[H\x1b[2J")
                print(time.strftime("%H:%M:%S")
                      + f" — kuke top (every {interval:g}s, history = last"
                        " 5m; ctrl-c to exit)")
            print(body)
            if not watch:
                return 0
            sys.stdout.flush()
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


def _fmt_label_set(labels: dict) -> str:
    if not labels:
        return "(no labels)"
    return "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}"


def cmd_query(args):
    """Windowed query against the daemon's in-memory scrape history
    (obs/tsdb.py): `kuke query 'kukeon_engine_ttft_seconds{cell=...}'
    --window 5m --agg p95`. One row per matching series; --step adds a
    sparkline of per-step values over the window."""
    out = _client(args).call("Query", expr=args.expr, windowS=args.window,
                             agg=args.agg, stepS=args.step)
    if args.json:
        _print(out, True)
        return 0
    series = out.get("series", [])
    if not series:
        print(f"no data for {args.expr!r} over the last {args.window} "
              "(series outside retention, or the daemon has no history "
              "yet)")
        return 1
    from kukeon_tpu.obs.tsdb import sparkline
    rng = {json.dumps(r["labels"], sort_keys=True): r["values"]
           for r in out.get("range", [])}
    width = max(len(_fmt_label_set(s["labels"])) for s in series)
    width = max(width, len("SERIES"))
    print(f"{'SERIES':<{width}}  {args.agg.upper():>12}"
          + ("  TREND" if rng else ""))
    for s in sorted(series, key=lambda s: _fmt_label_set(s["labels"])):
        line = (f"{_fmt_label_set(s['labels']):<{width}}  "
                f"{s['value']:>12.6g}")
        vals = rng.get(json.dumps(s["labels"], sort_keys=True))
        if vals:
            line += "  " + sparkline(vals)
        print(line)
    return 0


def cmd_alerts(args):
    """The alert engine's live state (one row per rule, plus one per
    active labelset) and its recent firing/resolved transitions — the
    operator view of kukeon_alerts_firing. ``--check`` turns it into a
    health gate for CI and cron: exit 1 while any rule is firing, 2 when
    the user rule file is broken (rulesError), 0 on a quiet fleet."""
    out = _client(args).call("Alerts",
                             transitions=getattr(args, "transitions", 50))
    check = getattr(args, "check", False)
    if args.json:
        _print(out, True)
        if check:
            if any(r["state"] == "firing" for r in out.get("alerts", [])):
                return 1
            return 2 if out.get("rulesError") else 0
        return 0
    if out.get("rulesError"):
        print(f"warning: KUKEON_ALERT_RULES ignored: {out['rulesError']}",
              file=sys.stderr)
    fmt = "{:<24} {:<9} {:<8} {:>12} {:>8} {}"
    print(fmt.format("ALERT", "SEVERITY", "STATE", "VALUE", "FOR",
                     "LABELS"))
    now = time.time()
    for r in out.get("alerts", []):
        state = r["state"]
        value = f"{r['value']:.4g}" if r.get("value") is not None else "-"
        dur = (f"{max(0.0, now - r['since']):.0f}s"
               if state != "ok" and r.get("since") is not None else "-")
        labels = (_fmt_label_set(r["labels"]) if r.get("labels") else "-")
        print(fmt.format(r["alert"], r["severity"], state, value, dur,
                         labels))
    trs = out.get("transitions", [])
    if trs:
        print("\nrecent transitions:")
        for tr in trs[-10:]:
            ts = time.strftime("%H:%M:%S", time.localtime(tr["at"]))
            extra = f" cell={tr['cell']}" if tr.get("cell") else ""
            if tr.get("trace_id"):
                extra += f" trace={tr['trace_id']}"
            print(f"  {ts} {tr['alert']} -> {tr['state']} "
                  f"(value {tr['value']:.4g} vs {tr['threshold']:.4g})"
                  f"{extra}")
    if check:
        firing = [r["alert"] for r in out.get("alerts", [])
                  if r["state"] == "firing"]
        if firing:
            print(f"\ncheck: {len(firing)} rule(s) firing: "
                  + ", ".join(sorted(set(firing))), file=sys.stderr)
            return 1
        if out.get("rulesError"):
            # Nothing firing, but the operator's rule file is broken —
            # the gate cannot vouch for rules that never loaded.
            return 2
        print("\ncheck: fleet healthy (nothing firing)")
    return 0


def _span_detail(span: dict) -> str:
    """One span's human detail column: replica attempts and retry hops for
    gateway spans, token counts for engine spans, error text for failures."""
    bits: list[str] = []
    hops = [e for e in span.get("events", [])
            if e.get("event") in ("proxy_attempt", "proxy_retry")]
    if hops:
        parts = []
        for e in hops:
            a = e.get("attrs") or {}
            if e["event"] == "proxy_attempt":
                parts.append(a.get("replica", "?"))
            else:
                parts[-1:] = [f"{parts[-1] if parts else '?'}"
                              f"!{a.get('reason', 'retry')}"]
        bits.append("attempts " + " -> ".join(parts))
    for e in span.get("events", []):
        # The disaggregated KV handoff hop: which prefill cell fed which
        # decode cell, and what the transfer moved.
        if e.get("event") == "kv_handoff":
            a = e.get("attrs") or {}
            bits.append(f"handoff {a.get('prefill', '?')}->"
                        f"{a.get('decode', '?')} "
                        f"{a.get('pages', '?')}p/{a.get('bytes', '?')}B")
        elif e.get("event") == "handoff_fallback":
            a = e.get("attrs") or {}
            bits.append(f"handoff fallback (stage {a.get('stage', '?')})")
    if span.get("tokens"):
        bits.append(f"{span['tokens']} tokens")
    if span.get("attrs", {}).get("retries"):
        bits.append(f"retries={span['attrs']['retries']}")
    if span.get("error"):
        bits.append(span["error"])
    return "; ".join(bits)


def render_trace(trace_id: str, spans: list[dict]) -> str:
    """The reconstructed cross-component timeline for one trace: every
    span (gateway proxy, each replica attempt's engine span, boot spans)
    on one time axis, children indented under their parent span, with
    stage, cell, phase durations, retry hops, and outcome. Pure so tests
    drive it without a daemon."""
    if not spans:
        return f"trace {trace_id}: no spans found"
    base = min(s.get("startedAt") or 0.0 for s in spans)
    by_id = {s.get("spanId"): s for s in spans}

    def depth(s: dict) -> int:
        d, seen = 0, set()
        while s.get("parentSpanId") in by_id and s["spanId"] not in seen:
            seen.add(s["spanId"])
            s = by_id[s["parentSpanId"]]
            d += 1
        return d

    lines = [f"trace {trace_id} — {len(spans)} span(s)"]
    for s in sorted(spans, key=lambda x: (x.get("startedAt") or 0.0)):
        indent = "  " * (1 + depth(s))
        offset = (s.get("startedAt") or base) - base
        phases = " | ".join(
            f"{k} {v * 1000:.1f}ms" for k, v in (s.get("phasesS") or
                                                 {}).items() if v)
        detail = _span_detail(s)
        lines.append(
            f"{indent}+{offset:7.3f}s {s.get('component', '?'):<8}"
            f" {s.get('cell', '-'):<28}"
            f" {s.get('outcome') or '?':<9}"
            f" e2e {(s.get('e2eS') or 0) * 1000:8.1f}ms"
            + (f"  [{phases}]" if phases else "")
            + (f"  {detail}" if detail else ""))
    return "\n".join(lines)


def cmd_trace(args):
    """Render one distributed trace end to end: the daemon unions every
    model cell's /v1/trace ring (gateway + all replicas) for this trace id
    and this prints the reconstructed timeline — which replica(s) a
    request hit, every retry hop, and how the engine phases partition the
    request's wall time."""
    try:
        out = _client(args).call("Traces", traceId=args.trace_id)
    except KukeonError as e:
        print(f"daemon unreachable: {e}", file=sys.stderr)
        return 1
    spans = out.get("spans", [])
    if args.json:
        _print(spans, True)
        return 0
    print(render_trace(args.trace_id, spans))
    return 0 if spans else 1


def render_timeline(steps: list[dict]) -> str:
    """The engine-step flight recorder as a table: one line per recorded
    engine-loop step — wall time, batch occupancy, decode chunk size,
    tokens emitted, host transfers, preemptions, the per-program wall
    split, and the trace ids seated that step (each resolvable via
    `kuke trace <id>`). Pure so tests drive it without a daemon."""
    if not steps:
        return ("no recorded engine steps "
                "(cell idle, or no flight recorder)")
    base = min(s.get("t") or 0.0 for s in steps)
    fmt = "{:>9} {:>5} {:>9} {:>5} {:>5} {:>6} {:>5} {:>4} {:>5}"
    lines = [fmt.format("+T", "SEQ", "WALL", "OCC", "CHUNK", "TOKENS",
                        "XFER", "PRE", "QUEUE") + "  DETAIL"]
    for s in sorted(steps, key=lambda x: (x.get("t") or 0.0,
                                          x.get("seq") or 0)):
        occ = (f"{s.get('occupancy', 0)}/{s['slots']}" if s.get("slots")
               else str(s.get("occupancy", 0)))
        xfer = (s.get("fetches") or 0) + (s.get("uploads") or 0)
        progs = " ".join(
            f"{k} {v * 1000:.1f}ms"
            for k, v in sorted((s.get("programs") or {}).items()))
        traces = ",".join(s.get("traces") or ())
        detail = "  ".join(b for b in (
            progs,
            f"traces={traces}" if traces else "",
            f"[{s['cell']}]" if s.get("cell") else "") if b)
        lines.append(fmt.format(
            f"+{(s.get('t') or base) - base:.3f}s",
            s.get("seq", "-"),
            f"{(s.get('wall_s') or 0) * 1000:.1f}ms",
            occ, s.get("chunk_k", "-"), s.get("tokens", 0),
            xfer, s.get("preemptions", 0), s.get("queue_depth", "-"))
            + (f"  {detail}" if detail else ""))
    return "\n".join(lines)


def cmd_timeline(args):
    """The flight-recorder view: the daemon unions the matching cells'
    /v1/timeline rings (Timeline RPC) and this renders the last N
    engine-loop steps — what the batch looked like, where the step's
    wall time went per program, and which traces were seated, so a
    latency spike localizes to a step before `kuke trace` zooms in."""
    try:
        out = _client(args).call("Timeline", cell=args.cell, n=args.n)
    except KukeonError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    steps = out.get("steps", [])
    if args.json:
        _print(steps, True)
        return 0
    print(render_timeline(steps))
    return 0 if steps else 1


def cmd_scale(args):
    """The autoscaler's status verb: one row per autoscaled model cell —
    active target vs declared bounds, the latest queue-pressure and SLO
    burn signals, each decision rule's debounce state — plus the recent
    scale events (up/down/aborted with reasons). Read-only: the scaler
    itself decides; this is how the operator watches it decide."""
    out = _client(args).call("ScaleStatus")
    cells = out.get("cells", [])
    if getattr(args, "name", None):
        cells = [c for c in cells if c["cell"].endswith("/" + args.name)
                 or c["cell"] == args.name]
    if args.json:
        _print({"cells": cells, "events": out.get("events", [])}, True)
        return 0
    if not cells:
        print("no autoscaled model cells (set model.minReplicas/"
              "maxReplicas, and give the daemon a telemetry tick)")
        return 1
    fmt = "{:<32} {:>8} {:>7} {:>11} {:>8} {}"
    print(fmt.format("CELL", "REPLICAS", "BOUNDS", "QUEUE-RATIO", "BURN",
                     "RULES"))
    for c in sorted(cells, key=lambda c: c["cell"]):
        rules = c.get("rules") or {}
        lit = [f"{k}={v}" for k, v in sorted(rules.items()) if v != "ok"]
        print(fmt.format(
            c["cell"], c.get("active", "?"),
            f"{c.get('min', 1)}..{c.get('max', '?')}",
            f"{c.get('queueRatio', 0):.3f}", f"{c.get('burnRate', 0):.2f}",
            " ".join(lit) if lit else "quiet"))
    events = out.get("events", [])
    if events:
        print("\nrecent scale events:")
        for ev in events[-10:]:
            ts = time.strftime("%H:%M:%S", time.localtime(ev["at"]))
            arrow = {"up": "+1", "down": "-1"}.get(ev["direction"], "?")
            print(f"  {ts} {ev['cell']} {arrow} -> {ev.get('to', '?')} "
                  f"[{ev['result']}] {ev.get('reason', '')}")
    return 0


def cmd_rollout(args):
    """Rolling restart of a replicated model cell (drain -> restart ->
    ready, one replica at a time; the daemon drives it, the gateway keeps
    traffic flowing). Zero failed requests is the contract."""
    c = _client(args)
    s = _scope(args)
    out = c.call("RolloutCell", **s, name=args.name,
                 drainTimeoutS=args.drain_timeout,
                 readyTimeoutS=args.ready_timeout,
                 standby=getattr(args, "standby", True))
    if args.json:
        _print(out, True)
        return 1 if out.get("aborted") else 0
    sb = next((r["standby"] for r in out["replicas"]
               if isinstance(r.get("standby"), dict)), None)
    if sb is not None:
        print(f"  standby {sb['replica']}: ready in {sb['readyS']}s "
              "(census held at N throughout)")
    for r in out["replicas"]:
        if r.get("standby") is True:
            # The standby itself failed before any replica drained.
            print(f"  standby {r['replica']}: FAILED: {r.get('error')}")
            continue
        drained = "drained" if r["drained"] else "drain timeout (restarted anyway)"
        if r.get("error"):
            print(f"  {r['replica']}: {drained}, FAILED: {r['error']}")
        else:
            print(f"  {r['replica']}: {drained}, ready again in {r['readyS']}s")
    if out.get("aborted"):
        # The per-step records above say exactly which replicas finished;
        # re-running `kuke rollout` after fixing the stalled one is safe
        # (a healthy replica just drains and restarts again).
        done = sum(1 for r in out["replicas"] if not r.get("error"))
        print(f"cell/{args.name}: rollout ABORTED after {done} replica(s): "
              f"{out.get('error')}", file=sys.stderr)
        return 1
    print(f"cell/{args.name}: rollout complete "
          f"({len(out['replicas'])} replicas)")
    return 0


def cmd_doctor(args):
    """Host pre-flight checks (reference: kuke doctor / internal/cgroupcheck:
    controller availability + delegation detail; all five native tools; the
    isolation and egress-enforcement layers the security story depends on)."""
    from kukeon_tpu.runtime import instance, sysuser
    from kukeon_tpu.runtime.cgroups import CgroupManager
    from kukeon_tpu.runtime.devices import discover_chips

    checks = []
    cg = CgroupManager()
    if cg.available():
        try:
            with open(os.path.join(cg.root, "cgroup.controllers")) as f:
                avail = set(f.read().split())
            with open(os.path.join(cg.root, cg.base, "cgroup.subtree_control")) as f:
                delegated = set(f.read().split())
        except OSError:
            avail, delegated = set(), set()
        want = {"cpu", "memory", "pids"}
        missing = want - delegated
        detail = f"controllers={sorted(avail & want)} delegated={sorted(delegated & want)}"
        if missing & avail:
            detail += f" (NOT delegated: {sorted(missing & avail)})"
        checks.append(("cgroup-v2", f"ok — {detail}"))
    else:
        checks.append(("cgroup-v2", "unavailable (limits degrade)"))
    chips = discover_chips()
    checks.append(("tpu-chips", f"{len(chips)} visible ({chips})" if chips else "none visible"))
    from kukeon_tpu.runtime.devices import probe_tpu_runtime

    state, detail = probe_tpu_runtime(
        timeout_s=float(os.environ.get("KUKEON_DOCTOR_PROBE_TIMEOUT", "20"))
    )
    checks.append(("tpu-runtime",
                   f"{state} — {detail}" if state != "ok" else f"ok — {detail}"))
    bin_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bin")
    for b in ("kukepause", "kukeshim", "kuketty", "kukecell", "kukenet"):
        ok = os.access(os.path.join(bin_dir, b), os.X_OK)
        checks.append((f"native/{b}", "ok" if ok else "MISSING (run `make -C native`)"))
    # The two enforcement layers:
    from kukeon_tpu.runtime.cells import namespace as nsb

    checks.append(("isolation", "namespace sandbox (kukecell)" if nsb.available()
                   else "process backend (no sandboxing — need root + kukecell)"))
    # Same predicate the daemon uses — the preflight must never claim
    # enforcement the runtime would run without.
    from kukeon_tpu.runtime.net.kukenet import kukenet_usable
    from kukeon_tpu.runtime.net.manager import _enforcement_enabled
    from kukeon_tpu.runtime.net.runners import ShellRunner

    r = ShellRunner()
    if not _enforcement_enabled(r):
        checks.append(("net-enforce", "OFF (need root + ip + iptables/kukenet, "
                       "or KUKEON_NET_ENFORCE=1)"))
    elif r.available("iptables"):
        checks.append(("net-enforce", "on (iptables CLI)"))
    elif kukenet_usable():
        checks.append(("net-enforce", "on (kukenet, native xtables)"))
    else:
        checks.append(("net-enforce", "forced on (KUKEON_NET_ENFORCE=1) but no "
                       "enforcer binary — policies will fail"))
    gid = sysuser.group_gid()
    checks.append(("group-kukeon", f"gid {gid}" if gid is not None
                   else "absent (kuke init as root provisions it)"))
    run_path = _run_path(args)
    checks.append(("run-path", run_path + (" (exists)" if os.path.isdir(run_path) else " (not initialized — run `kuke init`)")))
    pinned = instance.read(run_path)
    if pinned:
        checks.append(("instance", ", ".join(f"{k}={v}" for k, v in sorted(pinned.items()))))
    for name, result in checks:
        print(f"{name:<18} {result}")
    return 0


def cmd_purge(args):
    c = _client(args)
    s = _scope(args)
    if args.kind in ("realm", "realms"):
        c.call("DeleteRealm", name=args.name, purge=True)
    elif args.kind in ("space", "spaces"):
        c.call("DeleteSpace", realm=s["realm"], name=args.name, purge=True)
    elif args.kind in ("stack", "stacks"):
        c.call("DeleteStack", realm=s["realm"], space=s["space"], name=args.name, purge=True)
    else:
        print(f"purge supports realm|space|stack, not {args.kind!r}", file=sys.stderr)
        return 2
    print(f"{args.kind}/{args.name}: purged")
    return 0


def cmd_refresh(args):
    c = _client(args)
    counts = c.call("ReconcileNow")
    _print(counts, args.json)
    return 0


_BASH_COMPLETION = """\
# kuke bash completion — source this file (kuke autocomplete bash).
_kuke_complete() {
    local cur="${COMP_WORDS[COMP_CWORD]}" prev="${COMP_WORDS[COMP_CWORD-1]}"
    local verbs="init apply create build daemon get delete doctor start status \
stop team kill purge refresh rollout run attach log top trace query alerts \
scale autocomplete image uninstall version"
    if [ "$COMP_CWORD" -eq 1 ]; then
        COMPREPLY=($(compgen -W "$verbs" -- "$cur")); return
    fi
    case "$prev" in
        start|stop|kill|attach|log|run|rollout|scale)
            COMPREPLY=($(compgen -W "$(kuke autocomplete cells 2>/dev/null)" -- "$cur"));;
        get|delete|purge|create)
            COMPREPLY=($(compgen -W "realm space stack cell secret blueprint \
config volume" -- "$cur"));;
    esac
}
complete -F _kuke_complete kuke
"""


def cmd_autocomplete(args):
    """Shell completion: `bash` emits the completion script; resource kinds
    emit live names for dynamic completion (reference: cmd/config
    autocomplete.go — daemon-backed completions)."""
    what = args.what
    if what == "bash":
        print(_BASH_COMPLETION, end="")
        return 0
    try:
        c = _client(args)
        realm = getattr(args, "realm", None) or consts.DEFAULT_REALM
        if what == "realms":
            names = c.call("ListRealms")
        elif what == "spaces":
            names = c.call("ListSpaces", realm=realm)
        elif what == "stacks":
            names = c.call("ListStacks", realm=realm,
                           space=getattr(args, "space", None) or consts.DEFAULT_SPACE)
        elif what == "cells":
            names = [r["name"] for r in c.call("ListCells", realm=realm,
                                               space=None, stack=None)]
        elif what == "blueprints":
            names = c.call("ListBlueprints", realm=realm, space=None, stack=None)
        elif what == "configs":
            names = c.call("ListConfigs", realm=realm, space=None, stack=None)
        else:
            return 2
        for n in names:
            print(n)
        return 0
    except KukeonError:
        return 0   # completion must never error loudly


def cmd_uninstall(args):
    run_path = _run_path(args)
    if not args.yes:
        print(f"would remove {run_path}; pass --yes to confirm", file=sys.stderr)
        return 2
    try:
        args.daemon_cmd = "stop"
        args.socket = None
        cmd_daemon(args)
    except Exception:  # noqa: BLE001
        pass
    import shutil

    shutil.rmtree(run_path, ignore_errors=True)
    print(f"removed {run_path}")
    return 0


# --- parser ------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kuke", description="kukeon-tpu: TPU-native agent runtime")
    p.add_argument("--run-path", default=None, help="metadata root (env KUKEON_RUN_PATH)")
    p.add_argument("--socket", default=None, help="daemon socket (env KUKEOND_SOCKET)")
    p.add_argument("--no-daemon", action="store_true", help="run the controller in-process")
    p.add_argument("--json", action="store_true", help="JSON output")

    # Global flags are accepted after the verb too (SUPPRESS keeps a
    # flag-after-verb from clobbering a flag-before-verb with its default).
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--run-path", default=argparse.SUPPRESS)
    common.add_argument("--socket", default=argparse.SUPPRESS)
    common.add_argument("--no-daemon", action="store_true", default=argparse.SUPPRESS)
    common.add_argument("--json", action="store_true", default=argparse.SUPPRESS)

    sub = p.add_subparsers(dest="cmd", required=True)

    def sub_add(name, **kw):
        return sub.add_parser(name, parents=[common], **kw)

    sub_add("version")
    sp = sub_add("init")
    sp.add_argument("--no-daemon-start", action="store_true")

    sp = sub_add("daemon")
    sp.add_argument("daemon_cmd", choices=["serve", "start", "stop", "kill",
                                           "restart", "status", "logs",
                                           "metrics"])
    sp.add_argument("-f", "--follow", action="store_true")

    sp = sub_add("apply")
    sp.add_argument("-f", "--file", required=True)
    sp.add_argument("--team", default=None)
    sp.add_argument("--prune", action="store_true")

    sp = sub_add("delete")
    sp.add_argument("kind", nargs="?", default=None)
    sp.add_argument("name", nargs="?", default=None)
    sp.add_argument("-f", "--file", default=None)
    sp.add_argument("--force", action="store_true")
    _scope_args(sp)

    sp = sub_add("get")
    sp.add_argument("kind")
    sp.add_argument("name", nargs="?", default=None)
    _scope_args(sp)

    sp = sub_add("create")
    sp.add_argument("kind", nargs="?", default=None)
    sp.add_argument("name", nargs="?", default=None)
    sp.add_argument("-f", "--file", default=None)
    sp.add_argument("--image", default=None, help="cell: image for the main container")
    sp.add_argument("--command", nargs=argparse.REMAINDER, default=None,
                    help="cell: command for the main container; consumes ALL "
                         "remaining argv, so it must be the last flag")
    sp.add_argument("--no-start", action="store_true",
                    help="cell: create without starting")
    sp.add_argument("--data", action="append", help="secret: KEY=VALUE")
    sp.add_argument("--reclaim-policy", default="delete",
                    choices=["delete", "retain"], help="volume reclaim policy")
    _scope_args(sp)

    for verb in ("start", "stop", "kill"):
        sp = sub_add(verb)
        sp.add_argument("name")
        sp.set_defaults(verb=verb)
        _scope_args(sp)

    sp = sub_add("run")
    sp.add_argument("name", nargs="?", default=None)
    sp.add_argument("-f", "--file", default=None)
    sp.add_argument("-b", "--from-blueprint", default=None)
    sp.add_argument("-c", "--from-config", default=None)
    sp.add_argument("-p", "--param", action="append", help="KEY=VALUE blueprint params")
    sp.add_argument("--rm", action="store_true", help="autoDelete on exit")
    sp.add_argument("-d", "--detach", action="store_true")
    sp.add_argument("--container", default=None)
    _scope_args(sp)

    sp = sub_add("attach")
    sp.add_argument("name")
    sp.add_argument("--container", default=None)
    _scope_args(sp)

    sp = sub_add("log")
    sp.add_argument("name")
    sp.add_argument("--container", default=None)
    sp.add_argument("-f", "--follow", action="store_true")
    _scope_args(sp)

    sub_add("status")
    sp = sub_add("top")
    sp.add_argument("-w", "--watch", action="store_true",
                    help="repaint in place with sparkline history columns "
                         "(QPS, TTFT p95, queue) from the daemon's scrape "
                         "history")
    sp.add_argument("--interval", type=float, default=5.0,
                    help="--watch repaint interval in seconds")
    sub_add("doctor")
    sub_add("refresh")

    sp = sub_add("query")
    sp.add_argument("expr",
                    help="family{label=value,...} with an optional "
                         "'/ family{...}' ratio, e.g. "
                         "'kukeon_engine_ttft_seconds{cell=default/"
                         "default/default/llm}'")
    sp.add_argument("--window", default="5m",
                    help="trailing window (30s, 5m, 1h; default 5m)")
    sp.add_argument("--agg", default="avg",
                    choices=["rate", "delta", "avg", "max", "min",
                             "latest", "p50", "p95", "p99"],
                    help="aggregation over the window (p* need a "
                         "histogram family)")
    sp.add_argument("--step", default=None,
                    help="also print a per-step sparkline (e.g. 30s)")

    sp = sub_add("alerts")
    sp.add_argument("-n", "--transitions", type=int, default=50,
                    help="recent transitions to fetch")
    sp.add_argument("--check", action="store_true",
                    help="health gate: exit 1 while any rule is firing, "
                         "2 on a broken KUKEON_ALERT_RULES file")

    sp = sub_add("scale")
    sp.add_argument("name", nargs="?", default=None,
                    help="optional cell name filter")
    _scope_args(sp)

    sp = sub_add("trace")
    sp.add_argument("trace_id",
                    help="32-hex trace id (from logs, /v1/trace, or the "
                         "TTFT exemplar in `kuke top`)")

    sp = sub_add("timeline")
    sp.add_argument("cell", nargs="?", default=None,
                    help="cell key substring (realm/space/stack/name); "
                         "omit for the whole fleet")
    sp.add_argument("-n", type=int, default=50, dest="n",
                    help="newest engine steps to fetch per cell")

    sp = sub_add("rollout")
    sp.add_argument("name")
    sp.add_argument("--drain-timeout", type=float, default=60.0,
                    help="seconds to wait for each replica's drain")
    sp.add_argument("--ready-timeout", type=float, default=300.0,
                    help="seconds to wait for each restarted replica's readyz")
    sp.add_argument("--standby", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pre-warm a parked replica to /readyz before the "
                         "first drain so the ready census holds at N "
                         "(skipped when the cell has no parked capacity)")
    _scope_args(sp)

    sp = sub_add("image")
    sp.add_argument("image_cmd",
                    choices=["list", "get", "delete", "prune", "load", "save",
                             "pull", "push"])
    sp.add_argument("ref", nargs="?", default=None)
    sp.add_argument("-i", "--input", default=None, help="tarball to load")
    sp.add_argument("-o", "--output", default=None, help="tarball to save to")
    sp.add_argument("--to", default=None,
                    help="push target registry/repo[:tag] (default: the "
                         "image's own ref)")
    sp.add_argument("--insecure", action="store_true",
                    help="pull/push over plain HTTP (implied for localhost)")

    sp = sub_add("build")
    sp.add_argument("context", nargs="?", default=".")
    sp.add_argument("-t", "--tag", required=True)
    sp.add_argument("-f", "--file", default=None, help="Kukefile path")
    sp.add_argument("--build-arg", action="append", help="KEY=VALUE")

    sp = sub_add("team")
    sp.add_argument("team_cmd", choices=["init"])
    sp.add_argument("-f", "--file", required=True, help="ProjectTeam manifest")
    sp.add_argument("--dry-run", action="store_true")
    sp.add_argument("--build", action="store_true",
                    help="build catalog images before rendering")
    sp.add_argument("--push", action="store_true",
                    help="push built images to the teams-config registry "
                         "(requires --build)")

    sp = sub_add("purge")
    sp.add_argument("kind")
    sp.add_argument("name")
    _scope_args(sp)

    sp = sub_add("uninstall")
    sp.add_argument("--yes", action="store_true")

    sp = sub_add("autocomplete")
    sp.add_argument("what", choices=["bash", "realms", "spaces", "stacks",
                                     "cells", "blueprints", "configs"])
    _scope_args(sp)
    return p


def _scope_args(sp):
    sp.add_argument("--realm", default=None)
    sp.add_argument("--space", default=None)
    sp.add_argument("--stack", default=None)


HANDLERS = {
    "version": cmd_version,
    "init": cmd_init,
    "daemon": cmd_daemon,
    "apply": cmd_apply,
    "delete": cmd_delete,
    "create": cmd_create,
    "get": cmd_get,
    "start": cmd_lifecycle,
    "stop": cmd_lifecycle,
    "kill": cmd_lifecycle,
    "run": cmd_run,
    "attach": cmd_attach,
    "log": cmd_log,
    "status": cmd_status,
    "top": cmd_top,
    "query": cmd_query,
    "alerts": cmd_alerts,
    "scale": cmd_scale,
    "trace": cmd_trace,
    "timeline": cmd_timeline,
    "rollout": cmd_rollout,
    "doctor": cmd_doctor,
    "refresh": cmd_refresh,
    "purge": cmd_purge,
    "image": cmd_image,
    "build": cmd_build,
    "team": cmd_team,
    "uninstall": cmd_uninstall,
    "autocomplete": cmd_autocomplete,
}


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from kukeon_tpu.runtime import logging_setup

    logging_setup.setup(os.environ.get("KUKEOND_LOG_LEVEL", "info"))
    try:
        return HANDLERS[args.cmd](args)
    except KukeonError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        # `kuke ... | head` closed the pipe: normal unix behavior, not an
        # error. Point stdout at devnull so interpreter teardown doesn't
        # raise again while flushing.
        devnull = os.open(os.devnull, os.O_WRONLY)
        os.dup2(devnull, sys.stdout.fileno())
        return 0


if __name__ == "__main__":
    sys.exit(main())
