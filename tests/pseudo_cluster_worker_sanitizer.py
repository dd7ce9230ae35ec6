"""Sanitizer-plane worker: one rank of a real 2-process world, driving
the runtime sanitizers (utils/sanitizers.py) where they matter — across
an actual process boundary.

Modes (env ``SANITIZER_WORKER_MODE``, set by the parent test):

- ``diverge`` — rank 0 dispatches ``allreduce_sum`` while rank 1
  dispatches ``allgather_rows`` (the classic rank-divergent-collective
  shape that HANGS a world until the distributed timeout).  With the
  ``collective`` sanitizer armed, BOTH ranks must raise
  ``CollectiveDivergenceError`` promptly, each naming its own op and the
  first differing rank's op.  Exit 0 iff the divergence was caught.
- ``probe`` — (a) facade byte accounting: one ``allreduce_sum`` over a
  row-sharded table must book THIS PROCESS's shard bytes (half the
  global array in a 2-rank world), not the unsharded size (the ISSUE 7
  satellite regression); (b) a streamed K-Means fit with every
  sanitizer armed must succeed, with the collective fingerprint
  world-checked and identical across ranks.

Invoked as:  python pseudo_cluster_worker_sanitizer.py RANK NPROC COORD LOCAL_DEVICES
(the standard worker argv — the shared _launch_world plumbing spawns it).
"""

import json
import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])

import os

mode = os.environ.get("SANITIZER_WORKER_MODE", "probe")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

from oap_mllib_tpu.parallel import bootstrap

assert bootstrap.initialize_distributed(coord, nproc, rank)

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.data.table import DenseTable
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.parallel import collective
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.telemetry import metrics as tm
from oap_mllib_tpu.utils.sanitizers import CollectiveDivergenceError

rng = np.random.default_rng(123)
x = rng.normal(size=(4000, 12)).astype(np.float32)
half = x[rank * 2000 : (rank + 1) * 2000]

mesh = get_mesh()
table = DenseTable.from_process_local(half, mesh)

if mode == "diverge":
    set_config(sanitizers="collective")
    try:
        if rank == 0:
            collective.allreduce_sum(table.data, mesh)
        else:
            collective.allgather_rows(table.data, mesh)
    except CollectiveDivergenceError as e:
        msg = str(e)
        assert "allreduce_sum" in msg and "allgather_rows" in msg, msg
        print(f"DIVERGENCE_CAUGHT rank={rank}: {msg.splitlines()[0]}",
              flush=True)
        sys.exit(0)
    print(f"NO_DIVERGENCE rank={rank} — the divergent collective was "
          "dispatched without a diagnostic", flush=True)
    sys.exit(1)

# -- mode "probe" ------------------------------------------------------------

# (a) per-shard byte accounting through the facade


def _booked_bytes() -> float:
    series = tm.snapshot().get("oap_collective_bytes_total", {})
    return float(sum(series.values()))


before = _booked_bytes()
collective.allreduce_sum(table.data, mesh)
booked = _booked_bytes() - before

# (b) streamed fit with every sanitizer armed, across the real world
set_config(sanitizers="collective,transfer,retrace")
src = ChunkSource.from_array(half, chunk_rows=512)
m = KMeans(k=5, seed=7, init_mode="random", max_iter=5).fit(src)
san = m.summary.sanitizers

print("RESULT " + json.dumps({
    "rank": rank,
    "booked_bytes": booked,
    "global_bytes": int(table.data.nbytes),
    "streamed_cost": float(m.summary.training_cost),
    "san_ops": san["collective"]["ops"],
    "san_fingerprint": san["collective"]["fingerprint"],
    "san_world_checked": san["collective"]["world_checked"],
}), flush=True)
