#!/usr/bin/env python
"""Launch a distributed training job (reference tools/launch.py:29-80 CLI,
dmlc_tracker local launcher semantics).

TPU-native redesign: instead of the ps-lite scheduler + DMLC_* rendezvous,
the local launcher
  * spawns ``-s`` parameter-server processes (kvstore/ps_server.py) when
    servers are requested (dist_async / PS-mode dist_sync), and
  * spawns ``-n`` worker processes with the coordination env that
    ``jax.distributed.initialize`` + DistKVStore consume:
    MXT_COORDINATOR, MXT_NUM_WORKERS, MXT_WORKER_ID (DMLC_* aliases are
    exported too so reference-era scripts keep working).

Examples
--------
  # 2 workers, pure-collective dist_sync (jax.distributed over DCN/ICI)
  python tools/launch.py -n 2 --launcher local python train.py

  # 2 workers + 1 async parameter server
  python tools/launch.py -n 2 -s 1 --kv-mode async --launcher local \
      python train.py
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _await_servers(addrs, timeout=120.0):
    """Block until every parameter server accepts connections.  A worker
    connects the moment it starts, and a server that is still importing
    refuses it (the workers' own retry budget is under a second)."""
    deadline = time.monotonic() + timeout
    for addr in addrs:
        host, _, port = addr.rpartition(":")
        while True:
            try:
                socket.create_connection((host, int(port)), 1.0).close()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise SystemExit(
                        f"launch.py: parameter server {addr} did not "
                        f"listen within {timeout:.0f}s")
                time.sleep(0.1)


def _server_code(port, kv_mode, num_workers):
    """Bootstrap string for one PS server process.  Servers are CPU
    processes by role (reference: the server role never owns a GPU);
    the cpu backend is forced BEFORE anything imports jax — the
    server-side optimizer path uses jnp and must not take a chip from
    the workers."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return (f"import sys; sys.path.insert(0, {repo_root!r}); "
            f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"from incubator_mxnet_tpu.kvstore.ps_server import "
            f"serve_forever; "
            f"serve_forever({port}, {kv_mode!r}, {num_workers})")


def _refuse_workers_sharing_tpu_host(env, num_workers):
    """Several local workers on a TPU host would each claim every chip
    (this launcher gives a worker no chip of its own): the second one
    fails or hangs at its first JAX call.  Say so instead."""
    if num_workers < 2:
        return
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from incubator_mxnet_tpu.context import child_tpu_chips
    chips = child_tpu_chips(env)
    if chips is not None:
        raise SystemExit(
            f"launch.py --launcher local: {num_workers} workers on one "
            f"TPU host ({chips} chip(s)) would each claim every chip, and "
            "a chip belongs to one process at a time.  Run one worker per "
            "host (--launcher ssh), drive all local chips from one process "
            "(make_fused_train_step(..., mesh=...)), or set "
            "JAX_PLATFORMS=cpu for a CPU rehearsal.")


def launch_local(args, extra_env=None):
    """Spawn servers + workers on this host; returns worker exit codes."""
    procs = []
    env_base = dict(os.environ)
    env_base.update(extra_env or {})

    server_ports = []
    for i in range(args.num_servers):
        port = _free_port()
        server_ports.append(port)
        env = dict(env_base)
        env["DMLC_ROLE"] = "server"
        env["JAX_PLATFORMS"] = "cpu"
        code = _server_code(port, args.kv_mode, args.num_workers)
        procs.append(("server", subprocess.Popen(
            [sys.executable, "-c", code], env=env)))

    coordinator = f"127.0.0.1:{_free_port()}"
    server_addrs = [f"127.0.0.1:{p}" for p in server_ports]
    worker_envs = []
    for i in range(args.num_workers):
        env = dict(env_base)
        env.update(_worker_env(args, i, coordinator, server_addrs))
        worker_envs.append(env)
    _refuse_workers_sharing_tpu_host(worker_envs[0], args.num_workers)
    _await_servers(server_addrs)
    workers = []
    for env in worker_envs:
        p = subprocess.Popen(args.command, env=env)
        workers.append(p)
        procs.append(("worker", p))

    codes = [p.wait() for p in workers]
    for role, p in procs:
        if role == "server" and p.poll() is None:
            p.send_signal(signal.SIGTERM)
    return codes


def read_hostfile(path):
    """Reference dmlc hostfile format: one ``host`` (optionally
    ``host:slots`` or ``host slots=N``) per line; # comments."""
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            slots = 1
            if " slots=" in line:
                host, _, s = line.partition(" slots=")
                slots = int(s)
            elif ":" in line:
                host, _, s = line.partition(":")
                slots = int(s)
            else:
                host = line
            hosts.append((host.strip(), slots))
    if not hosts:
        raise ValueError(f"hostfile {path} is empty")
    return hosts


def _worker_env(args, i, coordinator, server_addrs):
    env = {
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_WORKER_ID": str(i),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "MXT_COORDINATOR": coordinator,
        "MXT_NUM_WORKERS": str(args.num_workers),
        "MXT_WORKER_ID": str(i),
        "MXT_SERVERS": ",".join(server_addrs),
        "MXT_KV_MODE": args.kv_mode,
    }
    for kv in args.env_worker + args.env:
        k, _, v = kv.partition(":")
        env[k] = v
    return env


def _assign_hosts(hosts, n):
    """Round-robin n workers over (host, slots) respecting slots first."""
    flat = [h for h, slots in hosts for _ in range(slots)]
    if len(flat) < n:  # oversubscribe round-robin like dmlc ssh tracker
        flat = flat + [hosts[i % len(hosts)][0]
                       for i in range(n - len(flat))]
    return flat[:n]


def _sh_quote(s):
    import shlex
    return shlex.quote(s)


def launch_ssh(args, extra_env=None):
    """Reference dmlc_tracker/ssh.py semantics, TPU-native rendezvous:
    one ssh per worker carrying the coordination env inline (``env K=V
    ... cd DIR && exec CMD``); jax.distributed.initialize on each host
    joins the coordinator on the first host.  PS servers (if any) run on
    the first host.  ``--ssh-cmd`` injects the transport — tests use a
    shim that runs the remote shell locally; production uses real ssh
    with agent/keys (StrictHostKeyChecking left to the user's config).
    """
    hosts = read_hostfile(args.hostfile)
    assignment = _assign_hosts(hosts, args.num_workers)
    head = assignment[0]
    # Ports are probed on the LAUNCHER (a heuristic: free here says
    # nothing certain about the head host).  A remote bind failure is
    # loud — serve_forever raises, ssh exits nonzero, and workers error
    # out connecting — and --port pins the coordinator deterministically
    # for schedulers that pre-allocate ports.
    port = args.port or _free_port()
    coordinator = f"{head}:{port}"
    ssh_cmd = args.ssh_cmd.split()
    workdir = args.sync_dst_dir or os.getcwd()

    procs = []
    server_addrs = []
    for i in range(args.num_servers):
        sport = _free_port()
        server_addrs.append(f"{head}:{sport}")
        code = _server_code(sport, args.kv_mode, args.num_workers)
        # Lifecycle: the server runs in the remote shell's background
        # while `cat` holds the ssh channel open; when the launcher
        # closes the server's stdin pipe (or dies), cat sees EOF and the
        # shell kills the server — SIGTERM on the local ssh client alone
        # would leak the remote process.
        server_sh = (f"env DMLC_ROLE=server JAX_PLATFORMS=cpu "
                     f"{_sh_quote(sys.executable)} -c {_sh_quote(code)} "
                     f"& SRV=$!; cat > /dev/null; kill $SRV 2>/dev/null")
        remote = f"cd {_sh_quote(workdir)} && {{ {server_sh}; }}"
        procs.append(("server", subprocess.Popen(
            ssh_cmd + [head, remote], stdin=subprocess.PIPE)))
    _await_servers(server_addrs)

    workers = []
    for i, host in enumerate(assignment):
        env = _worker_env(args, i, coordinator, server_addrs)
        env_str = " ".join(f"{k}={_sh_quote(v)}" for k, v in env.items())
        cmd = " ".join(_sh_quote(c) for c in args.command)
        remote = f"cd {_sh_quote(workdir)} && env {env_str} {cmd}"
        p = subprocess.Popen(ssh_cmd + [host, remote])
        workers.append(p)
        procs.append(("worker", p))

    codes = [p.wait() for p in workers]
    for role, p in procs:
        if role == "server":
            if p.stdin:
                p.stdin.close()     # EOF -> remote shell kills the server
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.send_signal(signal.SIGTERM)
    return codes


def launch_mpi(args, extra_env=None):
    """Reference dmlc_tracker/mpi.py role: delegate process placement to
    mpirun.  Rank-dependent vars can't ride ``-x`` (same value
    everywhere), so MXT_WORKER_ID is derived per-rank from the MPI env
    (OMPI_COMM_WORLD_RANK / PMI_RANK / SLURM_PROCID) at package import —
    the launcher exports MXT_WORKER_ID_FROM_MPI=1 to request that."""
    if args.num_servers:
        raise NotImplementedError(
            "--launcher mpi runs collective mode only (mpirun places "
            "workers; there is no MPMD server placement here) — use "
            "--launcher ssh or local for parameter-server mode")
    hosts = read_hostfile(args.hostfile) if args.hostfile else None
    head = hosts[0][0] if hosts else "127.0.0.1"
    port = args.port or _free_port()
    env = {
        "MXT_COORDINATOR": f"{head}:{port}",
        "MXT_NUM_WORKERS": str(args.num_workers),
        "MXT_WORKER_ID_FROM_MPI": "1",
        "MXT_KV_MODE": args.kv_mode,
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    }
    for kv in args.env_worker + args.env:
        k, _, v = kv.partition(":")
        env[k] = v
    cmd = args.mpirun_cmd.split() + ["-np", str(args.num_workers)]
    if args.hostfile:
        cmd += ["--hostfile", args.hostfile]
    for k, v in env.items():
        cmd += ["-x", f"{k}={v}"]
    cmd += args.command
    os_env = dict(os.environ)
    os_env.update(env)
    os_env.update(extra_env or {})
    return [subprocess.call(cmd, env=os_env)]


def launch_sge(args, extra_env=None):
    """Reference dmlc_tracker/sge.py role: workers ride a qsub array
    job (``-t 1-N``, ``-sync y`` so the launcher blocks on completion);
    each task derives MXT_WORKER_ID from $SGE_TASK_ID.  The coordinator
    address points at the submitting host (the reference runs its
    tracker on the submit node the same way) and any parameter servers
    run here as local processes.  ``--qsub-cmd`` injects the transport —
    tests use a shim that executes the array tasks locally."""
    import tempfile

    port = args.port or _free_port()
    head = args.sge_head or socket.gethostname()

    procs = []
    server_addrs = []
    for i in range(args.num_servers):
        sport = _free_port()
        server_addrs.append(f"{head}:{sport}")  # PS genuinely run here
        env = dict(os.environ)
        env.update(extra_env or {})
        env["DMLC_ROLE"] = "server"
        env["JAX_PLATFORMS"] = "cpu"
        code = _server_code(sport, args.kv_mode, args.num_workers)
        procs.append(subprocess.Popen([sys.executable, "-c", code], env=env))
    _await_servers(server_addrs)

    # The jax.distributed coordinator is HOSTED BY WORKER 0 on whatever
    # exec node SGE places task 1 — unknowable at submit time.  Task 1
    # publishes its host through the shared working directory (#$ -cwd;
    # SGE clusters share it over NFS — the same assumption the reference
    # dmlc_tracker/sge.py makes) and the other tasks poll for it.
    coord_file = f".mxt_sge_coord.{os.getpid()}.{port}"
    # template env from the shared helper; worker id and coordinator
    # host are substituted by the array task itself
    env = _worker_env(args, 0, coordinator="__SGE__", server_addrs=server_addrs)
    env.pop("MXT_WORKER_ID"), env.pop("DMLC_WORKER_ID")
    env.pop("MXT_COORDINATOR")
    env.update(extra_env or {})
    lines = ["#!/bin/bash", f"#$ -t 1-{args.num_workers}", "#$ -cwd",
             'export MXT_WORKER_ID=$((SGE_TASK_ID-1))',
             'export DMLC_WORKER_ID=$MXT_WORKER_ID',
             f'if [ "$SGE_TASK_ID" = "1" ]; then',
             f'  echo "$(hostname):{port}" > {coord_file}.tmp'
             f' && mv {coord_file}.tmp {coord_file}',
             'else',
             f'  for i in $(seq 1 120); do'
             f' [ -f {coord_file} ] && break; sleep 1; done',
             f'  [ -f {coord_file} ] || {{ echo "coordinator file never'
             f' appeared" >&2; exit 1; }}',
             'fi',
             f'export MXT_COORDINATOR="$(cat {coord_file})"']
    for k, v in env.items():
        lines.append(f"export {k}={_sh_quote(v)}")
    lines.append("exec " + " ".join(_sh_quote(c) for c in args.command))
    with tempfile.NamedTemporaryFile("w", suffix=".sh", delete=False) as f:
        f.write("\n".join(lines) + "\n")
        script = f.name
    os.chmod(script, 0o755)
    try:
        rc = subprocess.call(args.qsub_cmd.split()
                             + ["-sync", "y", "-t",
                                f"1-{args.num_workers}", script])
    finally:
        os.unlink(script)
        for leftover in (coord_file, coord_file + ".tmp"):
            try:
                os.unlink(leftover)
            except OSError:
                pass
        for p in procs:            # PS lifetime = the job's lifetime
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return [rc]


def _rendezvous_server():
    """Tracker-analog service on the submit node (the reference runs its
    dmlc tracker there the same way): atomically assigns worker ids and
    publishes worker 0's coordinator address — container placement under
    YARN is unknowable at submit time and there is no shared cwd to
    rendezvous through (unlike SGE)."""
    import socketserver
    import threading

    state = {"coord": None, "next_id": 0}
    lock = threading.Lock()

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            line = self.rfile.readline().decode("utf-8", "replace").strip()
            if line == "ID":
                with lock:
                    wid = state["next_id"]
                    state["next_id"] += 1
                self.wfile.write(f"{wid}\n".encode())
            elif line.startswith("PUT "):
                with lock:
                    state["coord"] = line[4:].strip()
                self.wfile.write(b"OK\n")
            elif line == "GET":
                with lock:
                    coord = state["coord"] or ""
                self.wfile.write((coord + "\n").encode())

    srv = socketserver.ThreadingTCPServer(("0.0.0.0", 0), Handler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, srv.server_address[1]


def launch_yarn(args, extra_env=None):
    """Reference dmlc_tracker/yarn.py role, minimally: submit the
    workers as a YARN distributed-shell application (the reference
    ships a Java ApplicationMaster; this build rides Hadoop's stock
    distributedshell AM instead — ``--yarn-jar`` points at it, e.g.
    $HADOOP_HOME/share/hadoop/yarn/hadoop-yarn-applications-
    distributedshell-*.jar).  The tracker analog (worker-id assignment
    + coordinator discovery) and any parameter servers run on the
    submit node, exactly where the reference runs its tracker; each
    container executes a self-contained bootstrap that dials back.
    ``--yarn-cmd`` injects the transport — tests use a shim that runs
    the containers locally."""
    import tempfile

    if not args.yarn_jar:
        raise SystemExit("--launcher yarn requires --yarn-jar (the "
                         "hadoop distributedshell jar)")
    port = args.port or _free_port()
    head = args.yarn_head or socket.gethostname()

    procs = []
    server_addrs = []
    for _ in range(args.num_servers):
        sport = _free_port()
        server_addrs.append(f"{head}:{sport}")  # PS run on the submit node
        env = dict(os.environ)
        env.update(extra_env or {})
        env["DMLC_ROLE"] = "server"
        env["JAX_PLATFORMS"] = "cpu"
        code = _server_code(sport, args.kv_mode, args.num_workers)
        procs.append(subprocess.Popen([sys.executable, "-c", code], env=env))
    _await_servers(server_addrs)

    srv, rport = _rendezvous_server()
    env = _worker_env(args, 0, coordinator="__YARN__",
                      server_addrs=server_addrs)
    env.pop("MXT_WORKER_ID"), env.pop("DMLC_WORKER_ID")
    env.pop("MXT_COORDINATOR")
    env.update(extra_env or {})

    rdv = (f"import socket;s=socket.create_connection(({head!r},{rport}),"
           "timeout=30);f=s.makefile()")
    lines = [
        "#!/bin/bash",
        f"wid=$(python3 -c \"{rdv};s.sendall(b'ID\\n');"
        "print(f.readline().strip())\")",
        'export MXT_WORKER_ID=$wid',
        'export DMLC_WORKER_ID=$wid',
        'if [ "$wid" = "0" ]; then',
        f"  python3 -c \"{rdv};"
        f"s.sendall(('PUT '+socket.gethostname()+':{port}\\n')"
        ".encode());f.readline()\"",
        f'  export MXT_COORDINATOR="$(hostname):{port}"',
        'else',
        '  for i in $(seq 1 120); do',
        f"    c=$(python3 -c \"{rdv};s.sendall(b'GET\\n');"
        "print(f.readline().strip())\")",
        '    [ -n "$c" ] && break; sleep 1',
        '  done',
        '  [ -n "$c" ] || { echo "coordinator never appeared" >&2;'
        ' exit 1; }',
        '  export MXT_COORDINATOR="$c"',
        'fi',
    ]
    for k, v in env.items():
        lines.append(f"export {k}={_sh_quote(v)}")
    lines.append("exec " + " ".join(_sh_quote(c) for c in args.command))
    with tempfile.NamedTemporaryFile("w", suffix=".sh", delete=False) as f:
        f.write("\n".join(lines) + "\n")
        script = f.name
    os.chmod(script, 0o755)
    try:
        # the distributedshell client blocks until the app completes
        rc = subprocess.call(
            args.yarn_cmd.split()
            + ["jar", args.yarn_jar, "-jar", args.yarn_jar,
               "-shell_script", script,
               "-num_containers", str(args.num_workers)])
    finally:
        os.unlink(script)
        srv.shutdown()
        srv.server_close()
        for p in procs:            # PS lifetime = the job's lifetime
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return [rc]


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed job (reference launch.py CLI)")
    parser.add_argument("-n", "--num-workers", required=True, type=int)
    parser.add_argument("-s", "--num-servers", type=int, default=0)
    parser.add_argument("-H", "--hostfile", type=str,
                        help="ssh/mpi launcher host file")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh", "mpi", "sge", "yarn"])
    parser.add_argument("--kv-mode", type=str, default="sync",
                        choices=["sync", "async"],
                        help="parameter-server mode when -s > 0")
    parser.add_argument("--sync-dst-dir", type=str,
                        help="remote working dir for ssh launcher")
    parser.add_argument("--port", type=int, default=0,
                        help="coordinator port (0 = pick a free one)")
    parser.add_argument("--ssh-cmd", type=str, default="ssh",
                        help="ssh transport (tests inject a local shim)")
    parser.add_argument("--mpirun-cmd", type=str, default="mpirun")
    parser.add_argument("--qsub-cmd", type=str, default="qsub",
                        help="sge submit command (tests inject a shim)")
    parser.add_argument("--sge-head", type=str, default=None,
                        help="coordinator host workers dial back to "
                             "(default: this host's name)")
    parser.add_argument("--yarn-cmd", type=str, default="yarn",
                        help="yarn CLI (tests inject a shim)")
    parser.add_argument("--yarn-jar", type=str, default=None,
                        help="hadoop distributedshell jar path")
    parser.add_argument("--yarn-head", type=str, default=None,
                        help="submit-node host workers dial back to "
                             "(default: this host's name)")
    parser.add_argument("--env-server", action="append", default=[])
    parser.add_argument("--env-worker", action="append", default=[])
    parser.add_argument("--env", action="append", default=[])
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.launcher == "local":
        codes = launch_local(args)
    elif args.launcher == "ssh":
        if not args.hostfile:
            parser.error("--launcher ssh requires -H hostfile")
        codes = launch_ssh(args)
    elif args.launcher == "mpi":
        codes = launch_mpi(args)
    elif args.launcher == "sge":
        codes = launch_sge(args)
    else:
        codes = launch_yarn(args)
    bad = [c for c in codes if c != 0]
    sys.exit(bad[0] if bad else 0)


if __name__ == "__main__":
    main()
