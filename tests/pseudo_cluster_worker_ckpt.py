"""Elastic-worlds pseudo-cluster worker (kill-and-resume leg, ISSUE 8).

One rank of a real ``jax.distributed`` world fitting streamed K-Means
with checkpointing armed.  Modes (env ``CKPT_WORKER_MODE``):

- ``full``    — uninterrupted checkpoint-armed fit; prints RESULT.
- ``victim``  — rank 1 hard-kills itself (``os._exit(9)``, no cleanup —
  a preemption) mid-read of Lloyd pass 3; passes 1–2 are durable on
  every rank (shards + manifest).  Rank 0 is left blocked in the pass
  collective; the parent kills it.
- ``resume``  — a RELAUNCHED world (fresh processes, same
  ``CKPT_CHECKPOINT_DIR``) resumes at the recorded pass and completes;
  prints RESULT.  The parent asserts RESULT equals the ``full`` run
  bit-for-bit (same world size ⇒ bit-identical continuation).
- ``resume1`` — a single-process relaunch path is exercised by the
  parent directly (world-size change), not via this worker.

Invoked as:  python pseudo_cluster_worker_ckpt.py RANK NPROC COORD LOCAL_DEV
"""

import os
import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["CKPT_WORKER_MODE"]
ckdir = os.environ["CKPT_CHECKPOINT_DIR"]

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

# deterministic global dataset, each rank streams its own half (matches
# tests/test_pseudo_cluster.py::TestElasticWorlds oracle)
rng = np.random.default_rng(321)
x = rng.normal(size=(3000, 8)).astype(np.float32)
shard = x[rank * 1500 : (rank + 1) * 1500]

walks = {"n": 0}


def gen():
    walks["n"] += 1
    # walk 1 = the random-init reservoir pass; Lloyd passes are walks
    # 2+.  The victim rank dies mid-read of Lloyd pass 3 (walk 4) —
    # passes 1 and 2 are checkpointed durably on every rank.
    if mode == "victim" and rank == 1 and walks["n"] == 4:
        os._exit(9)
    for lo in range(0, shard.shape[0], 500):
        yield shard[lo : lo + 500]


src = ChunkSource(gen, shard.shape[1], 500, n_rows=shard.shape[0])
set_config(checkpoint_dir=ckdir)
m = KMeans(k=4, seed=7, init_mode="random", max_iter=6, tol=0.0).fit(src)
ck = m.summary.checkpoint
import json

print(
    "RESULT "
    + json.dumps({
        "rank": rank,
        "cost": float(m.summary.training_cost),
        "centers_hex": np.ascontiguousarray(
            m.cluster_centers_
        ).tobytes().hex(),
        "decision": ck["decision"],
        "restored_step": ck["restored_step"],
        "ladder": m.summary.resilience["ladder"],
    }),
    flush=True,
)
