"""Worker for the 3-process pseudo-cluster variant.

The reference only ever tested 2 executors (its pseudo-YARN cluster,
dev/test-cluster/env.sh); this stresses a world size that is neither a
power of two nor the tested-everywhere 2: UNEVEN thirds through the
in-memory mesh path AND the streamed per-process-source path.

Invoked as:  python pseudo_cluster_worker3.py RANK NPROC COORD LOCAL_DEVICES
"""

import json
import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

from oap_mllib_tpu.parallel import bootstrap

assert bootstrap.initialize_distributed(coord, nproc, rank)
assert jax.process_count() == nproc

from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.models.pca import PCA

# same global dataset as the 2-process worker; uneven thirds
rng = np.random.default_rng(123)
proto = rng.normal(size=(5, 12)).astype(np.float32) * 3.0
x = (proto[rng.integers(5, size=4000)]
     + rng.normal(size=(4000, 12)).astype(np.float32) * 0.25)
cuts = [0, 1300, 2600, 4000]
shard = x[cuts[rank] : cuts[rank + 1]]

m = KMeans(k=5, seed=7, max_iter=30).fit(shard)
assert m.summary.accelerated

p = PCA(k=4).fit(shard)

ms = KMeans(k=5, seed=7, max_iter=30).fit(
    ChunkSource.from_array(shard, chunk_rows=300)
)
assert getattr(ms.summary, "streamed", False)
ps = PCA(k=4).fit(ChunkSource.from_array(shard, chunk_rows=300))
assert ps.summary["n_rows"] == 4000

# item-sharded ALS over a 3-rank world: a block count that is neither a
# power of two nor 2 exercises the item-block offsets/padding (last
# block short) through the second shuffle + all_gather exchange
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.als import ALS

rng_als = np.random.default_rng(77)
NU, NI, RANK_ = 60, 40, 3
au = rng_als.integers(NU, size=1200).astype(np.int64)
ai = rng_als.integers(NI, size=1200).astype(np.int64)
au[0], ai[0] = NU - 1, NI - 1
ar = rng_als.random(1200).astype(np.float32) * 4 + 1
acuts = [0, 400, 800, 1200]
asl = slice(acuts[rank], acuts[rank + 1])
set_config(als_item_layout="sharded")
m_sh = ALS(rank=RANK_, max_iter=3, reg_param=0.1, implicit_prefs=True,
           seed=3).fit(au[asl], ai[asl], ar[asl])
assert m_sh.summary["item_layout"] == "sharded"

# streamed-block 2-D composition over the SAME 3-rank world: each rank
# streams its local triples; the single-sweep double redistribution and
# the short last item block (kpb_i=14, 40 items over 3 blocks) cross
# the process boundary (ops/als_block_stream)
set_config(als_kernel="grouped")
trip3 = np.stack(
    [au[asl].astype(np.float64), ai[asl].astype(np.float64),
     ar[asl].astype(np.float64)], axis=1,
)
m_st3 = ALS(rank=RANK_, max_iter=3, reg_param=0.1, implicit_prefs=True,
            seed=3).fit(ChunkSource.from_array(trip3, chunk_rows=200))
assert m_st3.summary.get("streamed"), m_st3.summary
assert m_st3.summary["item_layout"] == "sharded", m_st3.summary
set_config(als_item_layout="auto", als_kernel="auto")

print(
    "RESULT "
    + json.dumps(
        {
            "rank": rank,
            "kmeans_cost": float(m.summary.training_cost),
            "pca_var": np.asarray(p.explained_variance_).tolist(),
            "streamed_cost": float(ms.summary.training_cost),
            "streamed_pca_var": np.asarray(ps.explained_variance_).tolist(),
            "als_sh_if": np.asarray(m_sh.item_factors_).tolist(),
            "als_st3_if": np.asarray(m_st3.item_factors_).tolist(),
        }
    ),
    flush=True,
)
