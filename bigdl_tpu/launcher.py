"""``bigdl-tpu-run``: the multi-host pod launch helper.

Reference: ``scripts/spark-submit-with-bigdl.sh:38-44`` — the reference's
launch story is "spark-submit with the BigDL jars + conf wired in"; the
TPU-native analog wires ``jax.distributed`` env instead of Spark conf:

- on a real TPU pod slice each host runs the same command and jax discovers
  its neighbors from the TPU metadata — ``bigdl-tpu-run train.py`` is then
  just env + exec;
- ``--num-processes N`` spawns N local CPU processes with a shared
  coordinator — the "multi-node without a cluster" mode the reference gets
  from ``local[N]`` masters, used by the multi-host tests. It is a CPU
  simulation only: a chip belongs to one process at a time and one process
  drives every chip of a host, so N children on ``--platform tpu`` would
  each claim all of them — that combination is refused;
- ``--coordinator``/``--process-id`` pass through to
  ``jax.distributed.initialize`` for manual clusters (the yarn/mesos/k8s
  master-string parsing of ``Engine.parseExecutorAndCore:445`` collapses to
  these three knobs).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def build_parser():
    ap = argparse.ArgumentParser(
        prog="bigdl-tpu-run",
        description="Launch a bigdl_tpu training script (single host, "
                    "TPU pod member, or N simulated local processes)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="spawn N local CPU processes with a shared "
                         "coordinator (CPU simulation only; refuses "
                         "--platform tpu)")
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port for manual clusters")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this host's process id for manual clusters")
    ap.add_argument("--num-hosts", type=int, default=None,
                    help="total process count for manual clusters")
    ap.add_argument("--platform", default=None,
                    help="force JAX_PLATFORMS (tpu/cpu)")
    ap.add_argument("--devices-per-process", type=int, default=None,
                    help="virtual CPU device count per process "
                         "(xla_force_host_platform_device_count)")
    ap.add_argument("script", help="python script to run")
    ap.add_argument("args", nargs=argparse.REMAINDER,
                    help="arguments passed to the script")
    return ap


def _child_env(base, platform=None, devices=None, coordinator=None,
               process_id=None, num_hosts=None):
    env = dict(base)
    if platform:
        env["JAX_PLATFORMS"] = platform
        env["BIGDL_TPU_PLATFORM"] = platform  # Engine.init forces it via
        # jax.config as well
    if devices:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices}"
        ).strip()
    if coordinator:
        env["BIGDL_TPU_COORDINATOR"] = coordinator
    if process_id is not None:
        env["BIGDL_TPU_PROCESS_ID"] = str(process_id)
    if num_hosts is not None:
        env["BIGDL_TPU_NUM_PROCESSES"] = str(num_hosts)
    return env


def main(argv=None):
    args = build_parser().parse_args(argv)
    cmd = [sys.executable, args.script] + args.args

    if args.num_processes:
        if args.platform not in (None, "cpu"):
            raise SystemExit(
                f"--num-processes is a CPU simulation; it cannot run on "
                f"platform {args.platform!r} (one process drives every chip "
                f"of a host: run the script once without --num-processes)")
        # local simulation: N processes, localhost coordinator
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        coordinator = f"127.0.0.1:{port}"
        procs = []
        for pid in range(args.num_processes):
            env = _child_env(os.environ, platform="cpu",
                             devices=args.devices_per_process,
                             coordinator=coordinator, process_id=pid,
                             num_hosts=args.num_processes)
            procs.append(subprocess.Popen(cmd, env=env))
        rcs = [p.wait() for p in procs]
        return max(rcs)

    env = _child_env(os.environ, platform=args.platform,
                     devices=args.devices_per_process,
                     coordinator=args.coordinator,
                     process_id=args.process_id, num_hosts=args.num_hosts)
    return subprocess.call(cmd, env=env)


if __name__ == "__main__":
    sys.exit(main())
