"""Capability-weighted sharding pseudo-cluster worker (ISSUE 15).

One rank of a real ``jax.distributed`` world driving the balance plane
(parallel/balance.py).  Every rank holds the SAME deterministic global
table and takes its shard through ``balance.local_sources`` — the
capability-weighted extent view.  Rank 1 is deliberately slowed: its
row slices sleep per chunk (a throttled host / cold-cache relaunch
stand-in).  Modes (env ``BALANCE_WORKER_MODE``):

- ``weighted`` — capabilities PINNED ``0:1.0,1:0.25`` → rank 1 gets a
  quarter-weight extent up front; the fit should beat the equal layout
  end-to-end (the parent compares walls).
- ``equal`` — ``capability_sharding=off`` → the equal-extent baseline
  over the identical slowed world (the parent's reference wall AND the
  parity oracle).
- ``rebalance`` — capabilities pinned EQUAL (1.0/1.0: same host, the
  probe would agree) so the initial plan is equal; the live straggler
  controller must detect the skew from the fleet rollups and re-plan
  extents mid-fit (the parent asserts a replan decision landed in
  ``summary.balance`` and rank 1's extent shrank).

Every rank prints RESULT with its fit wall, the rounded centers digest,
and the ``balance``/``fleet`` summary blocks.

Invoked as:  python pseudo_cluster_worker_balance.py RANK NPROC COORD LOCAL_DEV
"""

import json
import os
import sys
import time

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["BALANCE_WORKER_MODE"]
sleep_s = float(os.environ.get("BALANCE_CHUNK_SLEEP", "0.05"))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

from oap_mllib_tpu.parallel import bootstrap

ran = bootstrap.initialize_distributed(coord, nproc, rank)
assert ran, "initialize_distributed returned False"

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.parallel import balance

ROWS, D, CHUNK = 6000, 16, 250
rng = np.random.default_rng(1234)  # SAME table on every rank
x = rng.normal(size=(ROWS, D)).astype(np.float32)


class SlowRows:
    """Row-sliceable wrapper that sleeps per slice on THIS rank — the
    deliberately slowed host.  The balance view slices one chunk at a
    time, so each chunk pays one sleep."""

    def __init__(self, base, per_slice_s):
        self._base = base
        self._sleep = per_slice_s
        self.shape = base.shape
        self.ndim = base.ndim
        self.dtype = base.dtype

    def __getitem__(self, idx):
        if self._sleep > 0:
            time.sleep(self._sleep)
        return self._base[idx]


data = SlowRows(x, sleep_s if rank == 1 else 0.0)

if mode == "weighted":
    set_config(
        capability_sharding="auto",
        rank_capability="0:1.0,1:0.25",
    )
elif mode == "equal":
    set_config(capability_sharding="off")
elif mode == "rebalance":
    # equal pinned capabilities: the static plan is equal, so only the
    # LIVE controller (riding the fleet rollups) can fix the skew
    set_config(
        capability_sharding="auto",
        rank_capability="1.0",
        rebalance_threshold=1.3,
        rebalance_patience=2,
    )
else:
    print(f"WORKER_ERROR rank={rank} unknown mode {mode}", flush=True)
    os._exit(4)

# A start that does not depend on the layout: the streamed random init
# reservoirs each rank's OWN extent (ops/stream_ops.reservoir_sample), so
# the equal and the weighted layout of one table draw different rows —
# and the parity this worker reports is about the passes, not the draw.
from oap_mllib_tpu.ops import stream_ops  # noqa: E402

stream_ops.reservoir_sample = (
    lambda source, k, seed, timings=None: x[:: ROWS // k][:k].copy()
)

try:
    src = balance.local_sources(data, chunk_rows=CHUNK)
    t0 = time.monotonic()
    m = KMeans(
        k=4, seed=7, init_mode="random", max_iter=8, tol=0.0
    ).fit(src)
    wall = time.monotonic() - t0
except Exception as e:  # noqa: BLE001 — surface env markers
    import traceback

    traceback.print_exc()
    print(f"WORKER_ERROR rank={rank} {type(e).__name__}: {e}", flush=True)
    os._exit(4)

centers = np.asarray(m.cluster_centers_, np.float64)
digest = np.sort(centers.sum(axis=1)).round(6).tolist()
bal = getattr(m.summary, "balance", None)
flt = getattr(m.summary, "fleet", None)
print(
    "BALANCE rank=%d %s" % (rank, json.dumps(bal, sort_keys=True)),
    flush=True,
)
print(
    "FLEETROWS rank=%d %s" % (
        rank,
        json.dumps(
            {
                "per_rank_rows": (flt or {}).get("per_rank_rows"),
                "per_rank_capability": (flt or {}).get(
                    "per_rank_capability"),
            },
            sort_keys=True,
        ),
    ),
    flush=True,
)
print(
    "RESULT rank=%d %s" % (
        rank,
        json.dumps(
            {
                "ok": 1,
                "wall_s": round(wall, 4),
                "cost": float(m.summary.training_cost),
                "digest": digest,
                "centers": centers.round(10).tolist(),
            },
            sort_keys=True,
        ),
    ),
    flush=True,
)
