#!/usr/bin/env python
"""Launch multi-host training (reference tools/launch.py → dmlc tracker).

The reference spawns worker/server/scheduler processes over ssh/mpi/yarn and
rendezvouses via env vars (DMLC_ROLE etc.). On TPU the launch model is one
process per host, all running the SAME SPMD program, rendezvousing through
the jax distributed runtime — there are no parameter servers to start.

  python tools/launch.py -n 4 -H hostfile python train_imagenet.py ...
  → runs the command on every host with MXNET_COORDINATOR/MXNET_NUM_PROCS/
    MXNET_PROC_ID set; mxnet_tpu initialises jax.distributed from those.

--launcher local spawns the processes locally (the reference's local tracker
used by the nightly dist tests). On a TPU host each of N>1 local ranks is
given one chip of its own (``_local_chip_env``); a chip belongs to one process
at a time.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def _alloc_ps_port(coordinator):
    """Pick the dist_async parameter-server port for this job.

    When the coordinator host is local the port is allocated by binding
    with SO_REUSEPORT and HOLDING the socket for the launcher's lifetime,
    so the ephemeral port cannot be handed to another process before (or
    while) rank 0's server binds it with its own SO_REUSEPORT socket (the
    launcher's bound-but-not-listening socket never receives connections).
    For remote coordinators fall back to the deterministic
    coordinator-port+512 convention. Either way the chosen port is
    exported as MXNET_PS_PORT so workers and server agree by construction.

    Returns (port, holder_socket_or_None); the caller keeps the holder
    referenced for the job's duration."""
    import socket

    host, port = coordinator.rsplit(":", 1)
    if host in ("127.0.0.1", "localhost", "0.0.0.0") and \
            hasattr(socket, "SO_REUSEPORT"):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        s.bind((host, 0))
        return s.getsockname()[1], s
    return int(port) + 512, None


def _job_security_env():
    """A per-job random HMAC key for the dist_async wire protocol, unless
    the operator already provided one."""
    if os.environ.get("MXNET_PS_KEY"):
        return {}
    import secrets

    return {"MXNET_PS_KEY": secrets.token_hex(32)}


# libtpu process grid (x,y,z) for N local ranks of one chip each: the entry
# that was run on real hardware (the four-chip v5e host, a 2x2 slice)
_TPU_PROCESS_BOUNDS = {4: "2,2,1"}


def _local_chip_env(rank, num_workers, coordinator, one_world):
    """Accelerator env for local rank ``rank`` of ``num_workers``.

    A TPU chip belongs to one process at a time. Left alone, N local ranks
    each try to open every chip: on a four-chip v5e host three of four
    ranks died at backend start-up on libtpu's multi-process lockfile and
    the job never ended. So each local rank is handed exactly one chip —
    visible chip ``rank`` — through libtpu's visible-device variables.
    ``one_world`` (the jax.distributed jobs): the ranks also form ONE slice
    — ``TPU_PROCESS_BOUNDS``/``TPU_PROCESS_ADDRESSES`` tell every libtpu
    instance about its peers, so cross-rank collectives are XLA
    collectives over ICI (4 ranks: every rank sees 4 global devices, one
    local). ``jax.process_index()`` — and with it ``kv.rank`` of a
    ``dist_sync`` store — then follows the chip's position in the slice, a
    permutation of the launcher's ranks, not their identity. Elastic jobs
    (``one_world=False``) keep every rank a single-chip world of its own;
    their collectives ride the kvstore's TCP transport. Both were run on
    the four-chip host (CHANGES.md, PR 21). A one-world job whose worker
    count has no known process grid runs on the host backend
    (``JAX_PLATFORMS=cpu``, unless the operator set it) rather than race
    for the chips. On a host with no TPU
    the variables are inert. A single worker keeps the whole host: one
    process driving every chip through a dp mesh is the supported
    multi-chip path (``context=[mx.tpu(i) ...]``).
    """
    if num_workers == 1:
        return {}
    if one_world and num_workers not in _TPU_PROCESS_BOUNDS:
        return {} if os.environ.get("JAX_PLATFORMS") else \
            {"JAX_PLATFORMS": "cpu"}
    env = {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # N libtpu loads on one host are intended here
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    if one_world:
        port0 = int(coordinator.rsplit(":", 1)[1]) + 1000
        env.update({
            "TPU_PROCESS_BOUNDS": _TPU_PROCESS_BOUNDS[num_workers],
            "TPU_PROCESS_ADDRESSES": ",".join(
                f"localhost:{port0 + r}" for r in range(num_workers)),
            "TPU_PROCESS_PORT": str(port0 + rank),
            "CLOUD_TPU_TASK_ID": str(rank),
        })
    return env


def _worker_env(rank, num_workers, coordinator, num_restarts=0,
                job_env=None):
    env = dict(os.environ)
    env.update({
        "MXNET_COORDINATOR": coordinator,
        "MXNET_NUM_PROCS": str(num_workers),
        "MXNET_PROC_ID": str(rank),
        # how many times the supervisor has restarted the job — surfaced
        # to workers so kvstore.num_dead_node can report reality
        "MXNET_NUM_RESTARTS": str(num_restarts),
        # reference-compatible names some scripts read:
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_WORKER_ID": str(rank),
    })
    # supervised jobs should never hang silently in a dead-peer collective:
    # the kvstore watchdog turns a stalled barrier into a clean exit the
    # supervisor restarts (and, with MXNET_CHECKPOINT_DIR, a mid-training
    # resume). Operators can override or disable (0) explicitly.
    env.setdefault("MXNET_KV_TIMEOUT", "600")
    env.update(job_env or {})
    return env


def _supervise_local(command, num_workers, coordinator, max_restarts):
    """Run + monitor local workers; restart the JOB on any rank failure
    (the launcher-level failure detection the reference gets from the
    ps-lite scheduler's liveness tracking + is_recovery restart path,
    kvstore_dist.h:177-195).

    Restarts are whole-job: the jax distributed runtime cannot re-admit a
    single restarted rank while the surviving ranks sit stalled in a
    collective (and if rank 0 dies, the coordination service dies with it),
    so a per-rank restart would deadlock until timeout. Instead any
    non-zero exit terminates every rank and relaunches all of them, up to
    ``max_restarts`` times; mid-training progress survives via the scripts'
    own checkpoint/resume (--load-epoch pattern). Each attempt advances the
    coordinator port (stale-socket avoidance) and exports
    MXNET_NUM_RESTARTS so workers can report the recovery count.
    """
    import time

    host, port0 = coordinator.rsplit(":", 1)
    attempt = 0
    job_env = _job_security_env()
    holders = []  # keep allocated PS ports reserved for the job's lifetime
    while True:
        coord = f"{host}:{int(port0) + attempt}"
        ps_port, holder = _alloc_ps_port(coord)
        holders.append(holder)
        job_env["MXNET_PS_PORT"] = str(ps_port)
        procs = {
            rank: subprocess.Popen(
                command,
                env={**_worker_env(rank, num_workers, coord, attempt,
                                   job_env),
                     **_local_chip_env(rank, num_workers, coord, True)},
            )
            for rank in range(num_workers)
        }
        failed_rank = None
        while procs:
            time.sleep(0.2)
            for rank, p in list(procs.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del procs[rank]
                if rc == 0:
                    continue
                failed_rank = (rank, rc)
                for q in procs.values():
                    q.terminate()
                for q in procs.values():
                    try:
                        q.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        q.kill()
                        q.wait()
                procs.clear()
                break
        if failed_rank is None:
            return 0
        rank, rc = failed_rank
        if attempt >= max_restarts:
            sys.stderr.write(
                f"launch.py: rank {rank} died (rc={rc}), restart budget "
                f"spent ({max_restarts}) — job failed\n"
            )
            return 1
        attempt += 1
        sys.stderr.write(
            f"launch.py: rank {rank} died (rc={rc}); whole-job restart "
            f"{attempt}/{max_restarts}\n"
        )


def _supervise_elastic(command, num_workers, coordinator, max_restarts):
    """Per-rank restart supervision for the elastic membership plane
    (``--elastic``): exports ``MXNET_KV_TRANSPORT=tcp`` so the job runs on
    the live-membership kvstore, under which a single dead rank is NOT a
    job death — survivors reshard to dp−1 and keep training, so only the
    dead rank is relaunched, with its OLD rank id (it re-joins as the same
    member), its per-rank ``MXNET_NUM_RESTARTS`` bumped, and the
    coordinator/PS-port env preserved (the launcher's port-holder socket
    keeps the address reserved across the restart).

    Contrast with :func:`_supervise_local`: there the jax runtime pins the
    world, so any death forces a whole-job relaunch on a fresh port; here
    the membership table absorbs the churn and the job never loses the
    survivors' progress.
    """
    import time

    job_env = _job_security_env()
    job_env["MXNET_KV_TRANSPORT"] = "tcp"
    ps_port, _holder = _alloc_ps_port(coordinator)
    job_env["MXNET_PS_PORT"] = str(ps_port)
    restarts = {rank: 0 for rank in range(num_workers)}
    spent = 0

    def _spawn(rank):
        return subprocess.Popen(
            command,
            env={**_worker_env(rank, num_workers, coordinator,
                               restarts[rank], job_env),
                 **_local_chip_env(rank, num_workers, coordinator, False)},
        )

    procs = {rank: _spawn(rank) for rank in range(num_workers)}
    while procs:
        time.sleep(0.2)
        for rank, p in list(procs.items()):
            rc = p.poll()
            if rc is None:
                continue
            del procs[rank]
            if rc == 0:
                continue
            if spent >= max_restarts:
                sys.stderr.write(
                    f"launch.py: rank {rank} died (rc={rc}), restart "
                    f"budget spent ({max_restarts}) — job failed\n")
                for q in procs.values():
                    q.terminate()
                for q in procs.values():
                    try:
                        q.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        q.kill()
                        q.wait()
                return 1
            spent += 1
            restarts[rank] += 1
            sys.stderr.write(
                f"launch.py: rank {rank} died (rc={rc}); per-rank "
                f"restart (attempt {restarts[rank]}, budget "
                f"{spent}/{max_restarts})\n")
            procs[rank] = _spawn(rank)
    return 0


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"])
    parser.add_argument("--port", type=int, default=9127)
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="whole-job restarts after any rank failure "
                             "(local launcher); with --elastic, total "
                             "per-rank restarts")
    parser.add_argument("--elastic", action="store_true",
                        help="run on the elastic membership plane "
                             "(MXNET_KV_TRANSPORT=tcp): a dead rank is "
                             "relaunched alone with its old rank id while "
                             "survivors keep training (local launcher)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")

    hosts = ["127.0.0.1"] * args.num_workers
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [l.strip() for l in f if l.strip()]
        assert len(hosts) >= args.num_workers

    coordinator = f"{hosts[0]}:{args.port}"
    if args.launcher == "local":
        if args.elastic:
            sys.exit(_supervise_elastic(
                args.command, args.num_workers, coordinator,
                args.max_restarts
            ))
        sys.exit(_supervise_local(
            args.command, args.num_workers, coordinator, args.max_restarts
        ))

    job_env = _job_security_env()
    ps_port, _ps_holder = _alloc_ps_port(coordinator)
    job_env["MXNET_PS_PORT"] = str(ps_port)
    procs = []
    for rank in range(args.num_workers):
        env = _worker_env(rank, args.num_workers, coordinator,
                          job_env=job_env)
        remote_env = " ".join(
            f"{k}={v}" for k, v in env.items()
            if k.startswith(("MXNET_", "DMLC_")) and k != "MXNET_PS_KEY"
        )
        # the HMAC secret must never ride the command line (argv is world-
        # readable via ps on both ends); feed it through ssh stdin instead
        key = env.get("MXNET_PS_KEY", "")
        key_prefix = "IFS= read -r MXNET_PS_KEY; export MXNET_PS_KEY; " \
            if key else ""
        cmd = ["ssh", hosts[rank],
               f"{key_prefix}cd {os.getcwd()} && {remote_env} "
               f"{' '.join(args.command)}"]
        p = subprocess.Popen(cmd, stdin=subprocess.PIPE if key else None)
        if key:
            p.stdin.write((key + "\n").encode())
            p.stdin.close()
        procs.append(p)

    code = 0
    for p in procs:
        p.wait()
        code = code or p.returncode
    sys.exit(code)


if __name__ == "__main__":
    main()
