#!/usr/bin/env python
"""Cluster launcher. reference: tools/launch.py +
3rdparty/dmlc-core/tracker/dmlc_tracker/{local.py,ssh.py}.

Spawns N worker processes with the DMLC_* rendezvous env protocol the
reference uses; under the TPU build the coordinator is JAX's multi-controller
service instead of a ps-lite scheduler, so there are no server/scheduler
processes — `-s` is accepted and ignored with a note (SPMD has no servers).

Launchers:
  local  — all workers as subprocesses of this host (the reference's
           `--launcher local`, used by tests/nightly dist tests). Every
           worker gets the same environment, and a chip belongs to one
           process, so on a host with chips the first worker takes them
           all: this launcher is for CPU workers. The supported way to use
           the four chips of one host is one process and a mesh
           (`gluon.FusedTrainStep(mesh=create_mesh(data=4))`).
  ssh    — one worker per host from --hostfile via ssh (reference ssh.py).
  tpu    — emit the per-host env and command for TPU pods (one process per
           host; the operator's pod runner executes it on each host).

Usage:
  python tools/launch.py -n 4 --launcher local python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def build_env(rank, args):
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": args.root_uri,
        "DMLC_PS_ROOT_PORT": str(args.root_port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "DMLC_WORKER_ID": str(rank),
    })
    return env


def launch_local(args, command):
    import time
    procs = []
    try:
        for rank in range(args.num_workers):
            procs.append(subprocess.Popen(command,
                                          env=build_env(rank, args)))
        # poll the whole group: first nonzero exit kills the rest — a dead
        # worker leaves peers blocked in a collective forever (reference:
        # dmlc_tracker local.py behavior)
        while True:
            codes = [p.poll() for p in procs]
            if any(c not in (None, 0) for c in codes):
                return next(c for c in codes if c not in (None, 0))
            if all(c == 0 for c in codes):
                return 0
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


def launch_ssh(args, command):
    if not args.hostfile:
        raise SystemExit("--launcher ssh requires --hostfile")
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < args.num_workers:
        raise SystemExit("hostfile has %d hosts; need %d"
                         % (len(hosts), args.num_workers))
    procs = []
    try:
        for rank in range(args.num_workers):
            env = build_env(rank, args)
            exports = " ".join("export %s=%s;" % (k, v)
                               for k, v in env.items()
                               if k.startswith("DMLC_"))
            remote = "%s cd %s; %s" % (exports, os.getcwd(),
                                       " ".join(command))
            procs.append(subprocess.Popen(["ssh", "-o",
                                           "StrictHostKeyChecking=no",
                                           hosts[rank], remote]))
        code = 0
        for p in procs:
            code = p.wait() or code
        return code
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


def launch_tpu(args, command):
    """Print the per-host launch spec (TPU pod runners execute a single
    command on every host; rendezvous envs differ only in worker id)."""
    for rank in range(args.num_workers):
        env = {k: v for k, v in build_env(rank, args).items()
               if k.startswith("DMLC_")}
        exports = " ".join("%s=%s" % (k, v) for k, v in sorted(env.items()))
        print("host%d: %s %s" % (rank, exports, " ".join(command)))
    return 0


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="accepted for reference CLI parity; SPMD has "
                             "no server processes")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh", "tpu"])
    parser.add_argument("--hostfile", "-H", default=None)
    parser.add_argument("--root-uri", default="127.0.0.1")
    parser.add_argument("--root-port", type=int, default=9091)
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.num_servers:
        print("note: -s ignored — SPMD collectives replace parameter "
              "servers (see SURVEY.md §5.8)", file=sys.stderr)
    fn = {"local": launch_local, "ssh": launch_ssh, "tpu": launch_tpu}
    sys.exit(fn[args.launcher](args, args.command))


if __name__ == "__main__":
    main()
