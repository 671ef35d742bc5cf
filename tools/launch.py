#!/usr/bin/env python
"""Multi-host job launcher (reference: tools/launch.py — dmlc tracker
spawning scheduler/servers/workers over ssh/mpi/local).

TPU-native: there is no parameter-server tier; every process is a worker in
one SPMD job coordinated by the JAX distributed runtime over DCN
(SURVEY.md §5.8). The launcher assigns each process
MXTPU_COORDINATOR / MXTPU_NUM_PROCS / MXTPU_PROC_ID (consumed by
mxnet_tpu.kvstore.create('dist_sync') → jax.distributed.initialize) and
spawns them locally or over ssh."""
import argparse
import os
import subprocess
import sys


def worker_env(args, rank):
    env = dict(os.environ)
    env["MXTPU_COORDINATOR"] = args.coordinator
    env["MXTPU_NUM_PROCS"] = str(args.num_workers)
    env["MXTPU_PROC_ID"] = str(rank)
    if args.num_servers:
        # server tier size for dist_* kvstores (reference: launch.py -s);
        # rank 0 hosts the servers on consecutive ports from the
        # coordinator's (kvstore_dist.py)
        env["MXTPU_NUM_SERVERS"] = str(args.num_servers)
    # reference env names kept for script compat (tools/launch.py DMLC_*):
    # the dmlc tracker contract also publishes the scheduler address, which
    # reference-contract scripts read via DMLC_PS_ROOT_URI/PORT
    env["DMLC_NUM_WORKER"] = str(args.num_workers)
    env["DMLC_NUM_SERVER"] = str(args.num_servers or 1)
    env["DMLC_ROLE"] = "worker"
    host, sep, port = args.coordinator.rpartition(":")
    if not sep or not host or not port.isdigit():
        raise SystemExit(
            f"--coordinator must be host:port, got {args.coordinator!r}")
    env["DMLC_PS_ROOT_URI"] = host
    env["DMLC_PS_ROOT_PORT"] = port
    return env


def launch_local(args, command):
    procs = []
    for rank in range(args.num_workers):
        env = worker_env(args, rank)
        if args.num_workers > 1 and "JAX_PLATFORMS" not in env:
            # a chip belongs to ONE process: several workers on this host
            # cannot each hold it, so a local multi-worker launch is a CPU
            # rehearsal of the job's wiring (docs/multichip.md).  Export
            # JAX_PLATFORMS yourself to place workers differently.
            env["JAX_PLATFORMS"] = "cpu"
            if rank == 0:
                print(f"launch.py: {args.num_workers} local workers -> "
                      "JAX_PLATFORMS=cpu (CPU rehearsal; one process per "
                      "chip)", file=sys.stderr)
        p = subprocess.Popen(command, shell=True, env=env)
        procs.append(p)
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def launch_ssh(args, command):
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert hosts, "empty hostfile"
    # Popen is non-blocking: a plain loop launches all ranks concurrently
    # (the old thread-per-rank scaffolding added unsynchronized appends for
    # zero gain)
    procs = []
    for rank in range(args.num_workers):
        host = hosts[rank % len(hosts)]
        env_fwd = " ".join(
            f"{k}={v}" for k, v in worker_env(args, rank).items()
            if k.startswith(("MXTPU_", "DMLC_")))
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             f"cd {os.getcwd()} && env {env_fwd} {command}"]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def main():
    parser = argparse.ArgumentParser(
        description="launch a distributed mxnet_tpu job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter-server tier size (reference -s); "
                             "0 = one in-process server on rank 0")
    parser.add_argument("--launcher", choices=("local", "ssh"),
                        default="local")
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--coordinator", type=str, default="127.0.0.1:9027",
                        help="host:port of process 0 for DCN bootstrap")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    cmd_parts = args.command
    if cmd_parts and cmd_parts[0] == "--":
        # argparse.REMAINDER keeps the conventional separator; passing the
        # literal '--' to sh fails with 'Illegal option --'
        cmd_parts = cmd_parts[1:]
    command = " ".join(cmd_parts)
    assert command, "no command given"
    if args.launcher == "ssh":
        assert args.hostfile, "--hostfile required for ssh launcher"
        sys.exit(launch_ssh(args, command))
    sys.exit(launch_local(args, command))


if __name__ == "__main__":
    main()
