#!/usr/bin/env python
"""Supervised-relaunch driver: the live-world recovery loop end to end.

One file, two roles:

- **Supervisor** (default): builds a world of ``--procs`` worker
  processes (each a ``--worker`` invocation of this same file), arms the
  recovery plane (crash-record sideband + collective deadlines +
  ``resume=auto`` checkpointing) and supervises them under the restart
  budget — classify, relaunch, shrink — via
  ``utils/supervisor.Supervisor``.  Prints ``SUPERVISOR <json>`` (the
  machine-readable run summary) and each final worker ``RESULT`` line;
  exits nonzero when the budget ran out.

- **Worker** (``--worker RANK WORLD COORD``): one rank of the world —
  joins the jax.distributed rendezvous (world > 1), streams its shard of
  a deterministic K-Means dataset with checkpointing armed, and prints
  ``RESULT <json>`` (cost, bit-exact centers, checkpoint decision,
  resilience ladder).  Drill hooks via env:

  - ``SUPERVISE_KILL_RANK`` / ``SUPERVISE_KILL_WALK`` — that rank
    SIGKILLs itself mid-read of the given source walk (a preemption);
    by default only on attempt 0 (``SUPERVISE_KILL_SCOPE=first``), or on
    every multi-process attempt (``=multi`` — forces the supervisor to
    shrink past it).
  - ``OAP_MLLIB_TPU_CHAOS`` — the seeded chaos schedule (the supervisor
    re-seeds it per attempt).

Examples::

    # 2-process world, kill rank 1 mid-fit once, watch it resume
    python dev/supervise.py --procs 2 --checkpoint-dir /tmp/ck \\
        --crash-dir /tmp/crash --kill-rank 1

    # chaos drill: seeded random kills, supervised to completion
    python dev/supervise.py --procs 2 --checkpoint-dir /tmp/ck \\
        --crash-dir /tmp/crash --chaos 7:0.01:kill:1

CI drives both through dev/chaos_gate.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS, D, K, MAX_ITER, CHUNK = 3000, 8, 4, 6, 500
DATA_SEED = 321  # matches the elastic-worlds drill dataset


def _worker(rank: int, world: int, coord: str) -> int:
    """One rank: streamed K-Means over this rank's shard, checkpoint
    armed, recovery plane live.  Exit codes: 0 = RESULT printed, 17 =
    recovery-plane abort (crash record written), 3 = unexpected error."""
    local_dev = 1
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={local_dev}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", local_dev)

    import numpy as np

    if world > 1:
        from oap_mllib_tpu.parallel import bootstrap

        if not bootstrap.initialize_distributed(coord, world, rank):
            print("failed to join world", flush=True)
            return 3

    from oap_mllib_tpu.data.stream import ChunkSource
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.utils import recovery

    # deterministic GLOBAL dataset; each rank streams a contiguous shard
    # (world-independent data, so a shrunken world resumes over the same
    # global rows — the resharded-restore parity contract)
    rng = np.random.default_rng(int(os.environ.get(
        "SUPERVISE_DATA_SEED", str(DATA_SEED))))
    x = rng.normal(size=(ROWS, D)).astype(np.float32)
    per = ROWS // world
    shard = x[rank * per: ROWS if rank == world - 1 else (rank + 1) * per]

    kill_rank = int(os.environ.get("SUPERVISE_KILL_RANK", "-1"))
    kill_walk = int(os.environ.get("SUPERVISE_KILL_WALK", "4"))
    kill_scope = os.environ.get("SUPERVISE_KILL_SCOPE", "first")
    attempt = int(os.environ.get("SUPERVISE_ATTEMPT", "0"))
    arm_kill = rank == kill_rank and (
        attempt == 0 if kill_scope == "first" else world > 1
    )
    walks = {"n": 0}

    def gen():
        walks["n"] += 1
        # walk 1 = the random-init reservoir pass; Lloyd passes are
        # walks 2+.  The victim dies mid-read of the kill walk — earlier
        # passes are durable on every rank, peers are left inside the
        # pass collective for the deadline plane to convert.
        if arm_kill and walks["n"] == kill_walk:
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        for lo in range(0, shard.shape[0], CHUNK):
            yield shard[lo: lo + CHUNK]

    src = ChunkSource(gen, D, CHUNK, n_rows=shard.shape[0])
    try:
        m = KMeans(k=K, seed=7, init_mode="random", max_iter=MAX_ITER,
                   tol=0.0).fit(src)
    except recovery.RecoveryError as e:
        # crash record already written by the plane; exit promptly so
        # the supervisor can classify and relaunch
        print(f"RECOVERY_ABORT rank={rank} {type(e).__name__}: {e}",
              flush=True)
        os._exit(17)
    except Exception as e:  # noqa: BLE001 — worker boundary
        print(f"WORKER_ERROR rank={rank} {type(e).__name__}: {e}",
              flush=True)
        os._exit(3)
    ck = getattr(m.summary, "checkpoint", {}) or {}
    print("RESULT " + json.dumps({
        "rank": rank,
        "world": world,
        "cost": float(m.summary.training_cost),
        "centers_hex": np.ascontiguousarray(
            m.cluster_centers_).tobytes().hex(),
        "decision": ck.get("decision"),
        "restored_step": ck.get("restored_step"),
        "ladder": m.summary.resilience["ladder"],
    }), flush=True)
    return 0


def supervise(procs: int, checkpoint_dir: str, crash_dir: str, *,
              chaos: str = "", budget: int = 3, backoff: float = 0.2,
              shrink_after: int = 2, collective_timeout: float = 15.0,
              kill_rank: int = -1, kill_walk: int = 4,
              kill_scope: str = "first", attempt_timeout: float = 300.0):
    """Supervise one K-Means world to completion; returns
    ``(summary, Supervisor)`` — the CLI prints the summary, and
    dev/chaos_gate.py inspects the Supervisor's per-attempt exits (env-
    incapability markers ride each rank's captured output)."""
    from oap_mllib_tpu.utils.supervisor import Supervisor

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["OAP_MLLIB_TPU_CHECKPOINT_DIR"] = checkpoint_dir
    if collective_timeout:
        env["OAP_MLLIB_TPU_COLLECTIVE_TIMEOUT"] = str(collective_timeout)
    if kill_rank >= 0:
        env["SUPERVISE_KILL_RANK"] = str(kill_rank)
        env["SUPERVISE_KILL_WALK"] = str(kill_walk)
        env["SUPERVISE_KILL_SCOPE"] = kill_scope

    def build_argv(rank, world, coord, attempt):
        return [sys.executable, os.path.abspath(__file__),
                "--worker", str(rank), str(world), coord]

    sup = Supervisor(
        build_argv, procs, crash_dir, env=env, chaos=chaos,
        restart_budget=budget, restart_backoff=backoff,
        shrink_after=shrink_after, attempt_timeout=attempt_timeout,
        grace_s=max(10.0, 2 * collective_timeout),
    )
    return sup.run(), sup


def _supervise(args) -> int:
    summary, _ = supervise(
        args.procs, args.checkpoint_dir, args.crash_dir, chaos=args.chaos,
        budget=args.budget, backoff=args.backoff,
        shrink_after=args.shrink_after,
        collective_timeout=args.collective_timeout,
        kill_rank=args.kill_rank, kill_walk=args.kill_walk,
        kill_scope=args.kill_scope, attempt_timeout=args.attempt_timeout,
    )
    for out in summary["outputs"]:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                print(line, flush=True)
    print("SUPERVISOR " + json.dumps(
        {k: v for k, v in summary.items() if k != "outputs"}), flush=True)
    return 0 if summary["ok"] else 1


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        rank, world, coord = (int(sys.argv[2]), int(sys.argv[3]),
                              sys.argv[4])
        return _worker(rank, world, coord)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--checkpoint-dir", required=True)
    ap.add_argument("--crash-dir", required=True)
    ap.add_argument("--chaos", default="",
                    help="base chaos spec (seed re-seeded +attempt)")
    ap.add_argument("--budget", type=int, default=3)
    ap.add_argument("--backoff", type=float, default=0.2)
    ap.add_argument("--shrink-after", type=int, default=2)
    ap.add_argument("--collective-timeout", type=float, default=15.0)
    ap.add_argument("--kill-rank", type=int, default=-1)
    ap.add_argument("--kill-walk", type=int, default=4)
    ap.add_argument("--kill-scope", choices=("first", "multi"),
                    default="first")
    ap.add_argument("--attempt-timeout", type=float, default=300.0)
    return _supervise(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
