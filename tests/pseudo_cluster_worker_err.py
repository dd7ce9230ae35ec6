"""Error-injection worker: one rank's source fails mid-pass.

Validates the round-4 _PassGuard contract in a REAL ``jax.distributed``
world (not the in-process mock): rank 1's ChunkSource yields a different
row count on the second pass; without the guard, rank 0 would block in
``process_allgather`` until the distributed timeout while rank 1 exits.
With it, BOTH ranks must raise promptly — rank 1 with the original
ValueError chained, rank 0 with the collective RuntimeError.

Invoked as:  python pseudo_cluster_worker_err.py RANK NPROC COORD LOCAL_DEVICES
(the standard worker argv, so the shared _launch_world plumbing spawns it).
Exit code 0 = the expected error was raised on this rank (the parent
asserts all ranks exit 0 quickly); any other outcome exits nonzero.
"""

import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

from oap_mllib_tpu.parallel import bootstrap

assert bootstrap.initialize_distributed(coord, nproc, rank)

from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.models.kmeans import KMeans

rng = np.random.default_rng(5)
x = rng.normal(size=(600, 8)).astype(np.float32)

if rank == 0:
    src = ChunkSource.from_array(x, chunk_rows=128)
else:
    # deterministic on pass 1, short by one row from pass 2 on —
    # ChunkSource's row-count check raises mid-pass on THIS rank only
    passes = {"n": 0}

    def gen():
        passes["n"] += 1
        rows = 600 if passes["n"] == 1 else 599
        yield x[:rows]

    src = ChunkSource(gen, n_features=8, chunk_rows=128)

try:
    # random init = 1 reservoir pass (consistent) + per-iteration passes;
    # rank 1's pass 2 errors, and the guard must carry it to the next
    # reduction so rank 0 fails the SAME fit call
    KMeans(k=4, seed=1, init_mode="random", max_iter=5).fit(src)
except (ValueError, RuntimeError) as e:
    cause = f" (cause: {e.__cause__})" if e.__cause__ is not None else ""
    print(
        f"EXPECTED_ERROR rank={rank} {type(e).__name__}: {e}{cause}",
        flush=True,
    )
    sys.exit(0)
print(f"NO_ERROR rank={rank} — fit succeeded but must not have", flush=True)
sys.exit(1)
