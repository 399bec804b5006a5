#!/usr/bin/env python
"""launch.py — spawn a distributed training job.

Reference parity: tools/launch.py:21-120 (dmlc-tracker). The reference
launches W worker + S server + 1 scheduler processes and lets ps-lite
wire them up. Here:

* ``dist_sync`` needs NO servers — workers form a collective world via
  jax.distributed (kvstore_dist.py); ``launch.py -n W`` spawns exactly
  W workers.
* ``dist_async`` needs real parameter servers (immediate Hogwild
  applies, kvstore_async.py): ``launch.py -n W -s S`` additionally
  spawns S server processes (DMLC_ROLE=server → kvstore_server.py
  serve loop) on DMLC_PS_ROOT_PORT..+S-1; keys shard across them.
  There is still no scheduler — the launcher itself owns the topology.

Launchers:

* ``local``  — all W workers on this host (the mode the reference's
  distributed tests use).
* ``ssh``    — one worker per host from ``-H/--hostfile`` (reference
  dmlc-tracker ssh mode): rank i runs on hostfile line i via
  ``ssh -o StrictHostKeyChecking=no host 'env ... cmd'``, the
  coordinator address is host 0. Hosts must share the working
  directory (NFS) or have the code deployed, like the reference.
  On TPU pods one process per TPU-VM host is exactly the
  jax.distributed topology.
* mpi/sge/yarn are not implemented: their schedulers are obsolete for
  TPU fleets — GKE/xmanager launch one process per host with the same
  env contract below.

Env passed to each worker (reference DMLC names kept for parity):
  DMLC_ROLE=worker  DMLC_NUM_WORKER=W  MXTPU_WORKER_RANK=i
  DMLC_PS_ROOT_URI=<coordinator host>  DMLC_PS_ROOT_PORT=<port>

Usage:
  python tools/launch.py -n 4 python train.py --kv-store dist_sync
  python tools/launch.py -n 2 --launcher ssh -H hosts.txt \
      python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _free_port_range(n):
    """A base port with n consecutive free ports (servers bind
    base..base+n-1; verifying only base would let rank>0 servers die on
    EADDRINUSE)."""
    for _ in range(50):
        base = _free_port()
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise SystemExit("launch.py: no free port range of %d found" % n)


def _worker_env(rank, num_workers, root_uri, root_port, extra):
    env = {
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_PS_ROOT_URI": root_uri,
        "DMLC_PS_ROOT_PORT": str(root_port),
        "MXTPU_WORKER_RANK": str(rank),
    }
    for kv in extra:
        name, _, value = kv.partition("=")
        env[name] = value
    return env


def _wait_all(procs, daemons=()):
    """Kill the job on first failure (one dead worker leaves the rest
    blocked in collectives — dmlc-tracker does the same). ``daemons``
    (server processes) must outlive the workers: one EXITING early, with
    any code, is a failure. On Ctrl-C / SIGINT, SIGTERM everything
    before propagating."""
    try:
        return _wait_all_inner(procs, daemons)
    except KeyboardInterrupt:
        for p in list(procs) + list(daemons):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        raise


def _wait_all_inner(procs, daemons=()):
    rc = None
    while rc is None:
        time.sleep(0.2)
        codes = [p.poll() for p in procs]
        dead_daemon = any(p.poll() is not None for p in daemons)
        if any(c not in (None, 0) for c in codes) or dead_daemon:
            rc = next((c for c in codes if c not in (None, 0)), None)
            if rc is None:
                rc = 1
                print("launch.py: a server process died while workers "
                      "were running — failing the job", file=sys.stderr)
            for p in procs:
                if p.poll() is None:
                    p.terminate()
        elif all(c == 0 for c in codes):
            rc = 0
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    return rc


def launch_local(args):
    port = _free_port()
    server_port = (_free_port_range(args.num_servers)
                   if args.num_servers else port)
    # one wire-auth secret per job: every frame on the parameter-server
    # wire is HMAC-signed with it (kvstore_async.py), so a stray process
    # that can reach the port cannot feed the server pickles
    if args.num_servers and "MXTPU_PS_SECRET" not in os.environ:
        import secrets as _secrets
        os.environ["MXTPU_PS_SECRET"] = _secrets.token_hex(16)
    procs = []
    server_procs = []
    for srank in range(args.num_servers):
        # parameter-server processes for dist_async (kvstore_server.py
        # enters the serve loop at import; reference: ps-lite RunServer)
        env = dict(os.environ)
        env.update({
            "DMLC_ROLE": "server",
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_NUM_SERVER": str(args.num_servers),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(server_port),
            "MXTPU_SERVER_RANK": str(srank),
            "JAX_PLATFORMS": "cpu",
        })
        for kv in args.env:
            name, _, value = kv.partition("=")
            env[name] = value
        server_procs.append(subprocess.Popen(
            [sys.executable, "-c", "import mxnet_tpu"], env=env))
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update(_worker_env(rank, args.num_workers, "127.0.0.1",
                               server_port, args.env))
        if args.num_servers:
            # the collective coordinator must not collide with server 0's
            # listen port; workers reach servers via DMLC_PS_ROOT_PORT
            env["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % port
            env["DMLC_NUM_SERVER"] = str(args.num_servers)
        # worker collectives run on CPU devices locally
        env.setdefault("JAX_PLATFORMS", "cpu")
        procs.append(subprocess.Popen(args.command, env=env))
    rc = _wait_all(procs, daemons=server_procs)
    for p in server_procs:      # servers are job-scoped daemons
        if p.poll() is None:
            p.terminate()
    for p in server_procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    return rc


def launch_ssh(args):
    if not args.hostfile:
        raise SystemExit("--launcher ssh requires -H/--hostfile")
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()
                 and not h.lstrip().startswith("#")]
    if len(hosts) < args.num_workers:
        raise SystemExit("hostfile has %d hosts < -n %d"
                         % (len(hosts), args.num_workers))
    root_uri = hosts[0]
    port = args.port or _free_port()
    server_port = port + 1000 if args.num_servers else port
    cwd = os.getcwd()
    # per-job wire-auth secret (HMAC on every parameter-server frame;
    # kvstore_async.py). Passed in the remote env line: visible to other
    # users of the remote hosts via `ps` — acceptable on the same
    # trusted-cluster assumption as the reference's ps-lite, while still
    # shutting out off-host peers that can merely reach the open port.
    ps_secret = os.environ.get("MXTPU_PS_SECRET")
    if args.num_servers and not ps_secret:
        import secrets as _secrets
        ps_secret = _secrets.token_hex(16)

    def _ssh(host, env, command, stdin=None):
        envstr = " ".join("%s=%s" % (k, shlex.quote(v))
                          for k, v in env.items())
        remote = "cd %s && env %s %s" % (
            shlex.quote(cwd), envstr,
            " ".join(shlex.quote(c) for c in command))
        return subprocess.Popen(["ssh", "-o", "StrictHostKeyChecking=no",
                                 "-o", "BatchMode=yes", host, remote],
                                stdin=stdin)

    server_procs = []
    for srank in range(args.num_servers):
        # dist_async servers run on host 0 (srank -> port server_port+srank;
        # no remote availability probe — pick a known-free range with -p).
        # Cross-host workers must reach them: bind wide, trusted-network
        # assumption like the reference's ps-lite.
        env = {"DMLC_ROLE": "server",
               "DMLC_NUM_WORKER": str(args.num_workers),
               "DMLC_NUM_SERVER": str(args.num_servers),
               "DMLC_PS_ROOT_URI": root_uri,
               "DMLC_PS_ROOT_PORT": str(server_port),
               "DMLC_PS_BIND": "0.0.0.0",
               "MXTPU_SERVER_RANK": str(srank)}
        if ps_secret:
            env["MXTPU_PS_SECRET"] = ps_secret
        for kv in args.env:
            name, _, value = kv.partition("=")
            env[name] = value
        # stdin-watchdog: when this ssh client dies (job end, Ctrl-C,
        # terminate()), `cat` sees EOF and the remote server is killed —
        # otherwise the non-daemon serve thread would orphan and poison
        # the port for the next run
        # watchdog: stdin-EOF (job over / launcher killed) kills the
        # server, while `wait $c` keeps the ssh client's exit tied to the
        # SERVER's (a crashed server must still fail _wait_all fast)
        # the watcher subshell closes its own stdout/stderr (it would
        # otherwise hold the ssh channel open after the server dies,
        # hiding the crash from _wait_all's daemon poll)
        server_procs.append(_ssh(
            hosts[0], env,
            ["sh", "-c",
             "%s -c 'import mxnet_tpu' & c=$!; "
             "(cat; kill $c 2>/dev/null) >/dev/null 2>&1 & wait $c"
             % shlex.quote(sys.executable)],
            stdin=subprocess.PIPE))   # held open: EOF == job over
    procs = []
    for rank in range(args.num_workers):
        env = _worker_env(rank, args.num_workers, root_uri, server_port,
                          args.env)
        if args.num_servers:
            env["MXTPU_COORDINATOR"] = "%s:%d" % (root_uri, port)
            env["DMLC_NUM_SERVER"] = str(args.num_servers)
            if ps_secret:
                env["MXTPU_PS_SECRET"] = ps_secret
        procs.append(_ssh(hosts[rank], env, args.command))
    rc = _wait_all(procs, daemons=server_procs)
    for p in server_procs:
        if p.poll() is None:
            p.terminate()
    return rc


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed job (reference tools/launch.py)")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter-server processes (needed by "
                             "dist_async; dist_sync uses collectives and "
                             "needs none)")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"],
                        help="'local' (one host) or 'ssh' (one worker per "
                             "hostfile line)")
    parser.add_argument("-H", "--hostfile", type=str, default=None,
                        help="ssh mode: file with one hostname per line "
                             "(rank i -> line i; host 0 is the coordinator)")
    parser.add_argument("-p", "--port", type=int, default=0,
                        help="ssh mode: coordinator port (default: random; "
                             "pick a fixed one reachable on host 0)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra NAME=VALUE env for workers")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the worker command")
    args = parser.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    if args.num_servers and args.launcher == "ssh":
        print("launch.py: ssh mode runs servers only on host 0 "
              "(one per -s)", file=sys.stderr)

    try:
        if args.launcher == "ssh":
            sys.exit(launch_ssh(args))
        sys.exit(launch_local(args))
    except KeyboardInterrupt:
        sys.exit(1)


if __name__ == "__main__":
    main()
