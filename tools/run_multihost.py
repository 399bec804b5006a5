#!/usr/bin/env python
"""run_multihost.py — spawn N local processes as a kvstore='tpu' world.

The minimal launcher for tests and benchmarks of the collective
kvstore (docs/KVSTORE.md): each process gets the MXTPU_* env contract
(coordinator address, world size, rank) that ``mxnet_tpu``'s package
import feeds into ``jax.distributed.initialize`` BEFORE any XLA
backend touch. On a real pod the platform launcher (GKE/xmanager, one
process per TPU-VM host) sets the same three variables; this script is
the single-machine stand-in, defaulting every process to the CPU
backend so an N-process world runs anywhere.

Usage:
  python tools/run_multihost.py -n 2 python tests/tpu_kvstore_worker.py
  python tools/run_multihost.py -n 4 --env MXNET_KVSTORE_FUSED=1 \
      python train.py --kv-store tpu

Differences from tools/launch.py (the reference dmlc-tracker port):
no server processes (kvstore='tpu' has none), no ssh mode (pods get
real launchers), and the env contract is MXTPU_COORDINATOR /
MXTPU_NUM_PROCESSES / MXTPU_PROCESS_ID rather than the DMLC names.
``spawn()`` is importable for tests.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_env(rank, num_processes, coordinator, extra_env=None,
               platform="cpu"):
    """The per-process environment for one member of the world."""
    env = dict(os.environ)
    # a fresh world must not inherit the single-process test mesh flags
    # or a parent's rank/coordinator
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_COORDINATOR": coordinator,
        "MXTPU_NUM_PROCESSES": str(num_processes),
        "MXTPU_PROCESS_ID": str(rank),
    })
    if platform:
        env["JAX_PLATFORMS"] = platform
    for kv in (extra_env or []):
        name, _, value = kv.partition("=")
        env[name] = value
    return env


def spawn(num_processes, command, extra_env=None, platform="cpu",
          coordinator=None, stdout=None, stderr=None):
    """Start the world; returns the list of Popen handles in rank
    order. ``stdout``/``stderr`` pass through to Popen (PIPE for
    tests that assert on worker output)."""
    coordinator = coordinator or "127.0.0.1:%d" % _free_port()
    procs = []
    for rank in range(num_processes):
        procs.append(subprocess.Popen(
            command,
            env=worker_env(rank, num_processes, coordinator, extra_env,
                           platform),
            stdout=stdout, stderr=stderr))
    return procs


def wait_all(procs, timeout=None):
    """Wait for every process; on the FIRST failure terminate the rest
    (a dead member leaves survivors blocked in collectives). Returns
    the job's exit code."""
    import time
    deadline = None if timeout is None else time.monotonic() + timeout
    rc = None
    try:
        while rc is None:
            time.sleep(0.2)
            codes = [p.poll() for p in procs]
            if any(c not in (None, 0) for c in codes):
                rc = next(c for c in codes if c not in (None, 0))
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
            elif all(c == 0 for c in codes):
                rc = 0
            elif deadline is not None and time.monotonic() >= deadline:
                rc = 124
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        raise
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    return rc


def main():
    parser = argparse.ArgumentParser(
        description="Spawn N local processes as a kvstore='tpu' world")
    parser.add_argument("-n", "--num-processes", type=int, required=True)
    parser.add_argument("--platform", type=str, default="cpu",
                        help="JAX_PLATFORMS for the workers (default "
                             "cpu; pass '' to inherit)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra NAME=VALUE env for every process")
    parser.add_argument("--timeout", type=float, default=None,
                        help="kill the job after this many seconds")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    procs = spawn(args.num_processes, args.command, args.env,
                  args.platform or None)
    sys.exit(wait_all(procs, timeout=args.timeout))


if __name__ == "__main__":
    main()
