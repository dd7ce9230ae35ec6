"""Live-world recovery pseudo-cluster worker (ISSUE 10).

One rank of a real ``jax.distributed`` world driving the recovery plane
(utils/recovery.py).  Modes (env ``RECOVERY_WORKER_MODE``):

- ``hang`` — rank 1 SIGKILLs itself mid-read of Lloyd pass 2 (a
  preemption, no cleanup); rank 0 finishes its local pass and blocks in
  the cross-process reduction.  With ``collective_timeout`` armed, rank
  0 must raise :class:`CollectiveTimeoutError` within the deadline —
  NOT hang until the parent's 120 s watchdog — print
  ``TIMEOUT_CAUGHT`` and exit 0 on its own, leaving its crash record in
  the sideband.
- ``abort`` — rank 1 writes a crash record for a fatal fault that never
  reaches a collective, then exits; rank 0, blocked inside its first
  pass reduction, must see the poison and raise
  :class:`PeerAbortError` promptly (print ``PEER_ABORT_CAUGHT``).

Invoked as:  python pseudo_cluster_worker_recovery.py RANK NPROC COORD LOCAL_DEV
(the standard worker argv — the shared _launch_world plumbing spawns it).
"""

import os
import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["RECOVERY_WORKER_MODE"]
crash_dir = os.environ["RECOVERY_CRASH_DIR"]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

from oap_mllib_tpu.parallel import bootstrap

ran = bootstrap.initialize_distributed(coord, nproc, rank)
assert ran, "initialize_distributed returned False"

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.utils import recovery

# the deadline is the mechanism under test: well under the parent's
# 120 s watchdog, well over a healthy pass
set_config(collective_timeout=10.0, crash_dir=crash_dir)

rng = np.random.default_rng(321)
x = rng.normal(size=(3000, 8)).astype(np.float32)
shard = x[rank * 1500: (rank + 1) * 1500]

if mode == "abort" and rank == 1:
    # a fatal fault that never reaches a common reduction: the sideband
    # is the only way peers can learn about it promptly
    recovery.write_crash_record(
        "drill.fault", "unclassified", "injected fatal fault (abort drill)"
    )
    print("ABORT_RECORDED rank=1", flush=True)
    os._exit(3)

walks = {"n": 0}


def gen():
    walks["n"] += 1
    # walk 1 = the random-init reservoir pass; the victim dies mid-read
    # of Lloyd pass 2 (walk 3) — rank 0 is left inside the pass
    # reduction for the deadline plane to convert into a diagnosis
    if mode == "hang" and rank == 1 and walks["n"] == 3:
        import signal

        os.kill(os.getpid(), signal.SIGKILL)
    for lo in range(0, shard.shape[0], 500):
        yield shard[lo: lo + 500]


src = ChunkSource(gen, shard.shape[1], 500, n_rows=shard.shape[0])
try:
    m = KMeans(k=4, seed=7, init_mode="random", max_iter=6, tol=0.0).fit(src)
except recovery.CollectiveTimeoutError as e:
    print(f"TIMEOUT_CAUGHT rank={rank} op={e.op} "
          f"elapsed={e.elapsed_s:.1f}", flush=True)
    os._exit(0)  # crash record written; skip jax shutdown (peer is gone)
except recovery.PeerAbortError as e:
    peer = e.record.get("rank")
    print(f"PEER_ABORT_CAUGHT rank={rank} peer={peer}", flush=True)
    os._exit(0)
except Exception as e:  # noqa: BLE001 — surface env-incapability markers
    print(f"WORKER_ERROR rank={rank} {type(e).__name__}: {e}", flush=True)
    os._exit(4)

print(f"RESULT_UNEXPECTED rank={rank} cost={m.summary.training_cost}",
      flush=True)
os._exit(5)  # both drill modes must end in a recovery-plane exit
