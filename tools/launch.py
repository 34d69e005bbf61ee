#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py + dmlc tracker).

The reference spawned a ps-lite scheduler plus N server and W worker
processes over ssh/mpirun/yarn, wiring roles with DMLC_* env vars. In the
multi-controller JAX model there is no scheduler or server process — every
worker runs the same program and rendezvouses at a coordinator address
(``incubator_mxnet_tpu.parallel.dist.initialize`` maps the same DMLC_* vars
onto ``jax.distributed.initialize``). This launcher therefore spawns just the
N identical workers:

    python tools/launch.py -n 4 python train.py --kv-store dist_sync
    python tools/launch.py -n 8 -H hostfile --launcher ssh python train.py

One process per HOST, not per chip: on a TPU host one process drives every
local chip (a mesh over ``jax.local_devices()``), and a chip belongs to one
process at a time. ``--launcher local`` gives all n workers the same
environment, so on a TPU host each would try to take every chip and all
but one would fail or hang; it is for CPU rehearsals of the rendezvous
(``JAX_PLATFORMS=cpu``, as the tests and ``tools/multichip_smoke.py --dist``
run it) and for the ssh launcher's one-worker-per-host layout.

Env vars set per worker (reference-compatible names):
  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT  coordinator host:port
  DMLC_NUM_WORKER                       total workers
  DMLC_WORKER_ID                        this worker's rank
  DMLC_ROLE=worker                      (compat; every process is a worker)
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys
from typing import List, Optional


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(base: dict, uri: str, port: int, n: int, rank: int) -> dict:
    env = dict(base)
    env.update({
        "DMLC_PS_ROOT_URI": uri,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
    })
    return env


def launch_local(n: int, command: List[str], port: Optional[int] = None,
                 env: Optional[dict] = None) -> int:
    """Spawn n workers on localhost; returns the first nonzero exit code.
    All share one environment: pin ``JAX_PLATFORMS=cpu`` in ``env`` on a
    host with chips (see the module docstring)."""
    port = port or _free_port()
    base = dict(os.environ if env is None else env)
    procs = [subprocess.Popen(
        command, env=_worker_env(base, "localhost", port, n, rank))
        for rank in range(n)]
    rc = 0
    for p in procs:
        code = p.wait()
        if code and not rc:
            rc = code
    return rc


def launch_ssh(n: int, hosts: List[str], command: List[str],
               port: Optional[int] = None) -> int:
    """One worker per host entry (cycled if fewer hosts than workers); the
    coordinator is the first host. Assumes passwordless ssh and an identical
    checkout/venv path on every host — same contract as the dmlc ssh
    tracker."""
    port = port or 9000
    uri = hosts[0]
    cmd_str = " ".join(shlex.quote(c) for c in command)
    procs = []
    for rank in range(n):
        host = hosts[rank % len(hosts)]
        exports = " ".join(
            f"{k}={shlex.quote(str(v))}"
            for k, v in _worker_env({}, uri, port, n, rank).items())
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             f"cd {shlex.quote(os.getcwd())} && env {exports} {cmd_str}"]))
    rc = 0
    for p in procs:
        code = p.wait()
        if code and not rc:
            rc = code
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="total worker processes")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="file with one host per line (ssh launcher)")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-p", "--port", type=int, default=None,
                    help="coordinator port (default: auto for local, 9000 ssh)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="training command to run on every worker")
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    command = args.command[1:] if args.command[0] == "--" else args.command
    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh needs -H hostfile")
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        return launch_ssh(args.num_workers, hosts, command, args.port)
    return launch_local(args.num_workers, command, args.port)


if __name__ == "__main__":
    sys.exit(main())
