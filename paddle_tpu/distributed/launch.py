"""python -m paddle_tpu.distributed.launch — multi-host training launcher.

Reference: python/paddle/distributed/launch.py:193 — spawns one process
per GPU and builds the PADDLE_TRAINER_ENDPOINTS env cluster.  TPU-native:
one process per HOST (JAX owns all local chips in one process), with the
coordination service address passed via env; on a single host with N
chips no spawning is needed at all (the SPMD mesh covers them), so this
launcher only forks for multi-host simulation/testing or real multi-host
when given --hosts.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys


def _parse_args():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes on this host (TPU: keep 1; chips are "
                        "covered by the in-process mesh)")
    p.add_argument("--num_hosts", type=int, default=1)
    p.add_argument("--host_id", type=int, default=0)
    p.add_argument("--coordinator", type=str, default="127.0.0.1:8476")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


def launch():
    args = _parse_args()
    nproc = args.nproc_per_node
    total = nproc * args.num_hosts
    if nproc > 1:
        from ..framework.place import host_tpu_chips

        if host_tpu_chips():
            # a chip belongs to ONE process: the first child would take
            # every local chip and the rest fail or hang at start-up
            sys.exit("paddle_tpu.distributed.launch: --nproc_per_node="
                     f"{nproc} on a TPU host — use 1; the in-process "
                     "mesh covers all local chips")

    if total <= 1:
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": "0",
            "PADDLE_TRAINERS_NUM": "1",
        })
        os.execvpe(sys.executable,
                   [sys.executable, args.training_script] + args.training_script_args,
                   env)
        return

    procs = []
    for local_rank in range(nproc):
        rank = args.host_id * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(total),
            "PADDLE_COORDINATOR_ADDRESS": args.coordinator,
            "PADDLE_NUM_PROCESSES": str(total),
            "PADDLE_PROCESS_ID": str(rank),
        })
        log = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            log = open(os.path.join(args.log_dir, f"worker.{rank}.log"), "w")
        procs.append((subprocess.Popen(
            [sys.executable, args.training_script] + args.training_script_args,
            env=env, stdout=log, stderr=subprocess.STDOUT if log else None,
        ), log))

    code = 0
    for proc, log in procs:
        proc.wait()
        code = code or proc.returncode
        if log:
            log.close()
    sys.exit(code)


if __name__ == "__main__":
    launch()
