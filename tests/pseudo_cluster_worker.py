"""Worker process for the 2-process pseudo-cluster test.

Each worker is one rank of a real ``jax.distributed`` world over
127.0.0.1 — the analog of one Spark executor in the reference's only
multi-rank test, the 2-executor pseudo-YARN cluster
(reference dev/ci-test.sh:60-62, dev/test-cluster/setup-cluster.sh).

Invoked as:  python pseudo_cluster_worker.py RANK NPROC COORD LOCAL_DEVICES

Prints one JSON line of results for the parent test to compare against
the single-process oracle.
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

ran = bootstrap.initialize_distributed(coord, nproc, rank)
assert ran, "initialize_distributed returned False for a multi-process world"
assert jax.process_count() == nproc, jax.process_count()
assert len(jax.devices()) == nproc * local_dev, len(jax.devices())

from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.models.pca import PCA

# deterministic global dataset; each rank holds only its half (the
# "no host ever holding the full table" contract, data/table.py)
rng = np.random.default_rng(123)
proto = rng.normal(size=(5, 12)).astype(np.float32) * 3.0
x = (proto[rng.integers(5, size=4000)]
     + rng.normal(size=(4000, 12)).astype(np.float32) * 0.25)
half = x[rank * 2000 : (rank + 1) * 2000]

# default init = k-means||: the device-side rounds must run multi-host
# (round 1 crashed here — host indexing on a non-addressable array)
m = KMeans(k=5, seed=7, max_iter=30).fit(half)
assert m.summary.accelerated

# weighted fit exercises the collective sample_weight path
w_local = np.ones((2000,), np.float32)
w_local[:100] = 2.5
mw = KMeans(k=5, seed=7, init_mode="random", max_iter=10).fit(
    half, sample_weight=w_local
)

# UNEVEN shards: rank 0 holds 1999 valid rows (padded to 2000 mid-array),
# rank 1 holds 2000 — random init must never sample the padding row and
# must reach every valid row (valid->padded index mapping)
uneven = x[:1999] if rank == 0 else x[1999:3999]
mu = KMeans(k=5, seed=11, init_mode="random", max_iter=15).fit(uneven)

p = PCA(k=4).fit(half)

# model-axis fits: model_parallel=2 arranges the 4 global devices as a
# (data=2, model=2) mesh whose DATA axis crosses the process boundary —
# the feature-sharded K-Means Lloyd (kmeans_ops.lloyd_run_model_sharded)
# and the model-sharded PCA Gram run their psums/all_gathers across a
# real 2-process world, not just the single-host virtual mesh
from oap_mllib_tpu.config import set_config

set_config(model_parallel=2)
m_mp = KMeans(k=5, seed=7, init_mode="random", max_iter=15).fit(half)
assert m_mp.summary.accelerated
p_mp = PCA(k=4).fit(half)
assert p_mp.summary["mesh_shape"] == {"data": 2, "model": 2}
set_config(model_parallel=1)

# --- streamed (out-of-core) fits: each rank streams its OWN shard as a
# local ChunkSource; sums/Gram/init state reduce across processes
# (ops/stream_ops._psum_host / _allgather_host — the DCN analog of the
# mesh path's psums).  Every rank must produce IDENTICAL results.
from oap_mllib_tpu.data.stream import ChunkSource

ms = KMeans(k=5, seed=7, max_iter=30).fit(
    ChunkSource.from_array(half, chunk_rows=512)
)
assert getattr(ms.summary, "streamed", False)
ms_rand = KMeans(k=5, seed=11, init_mode="random", max_iter=15).fit(
    ChunkSource.from_array(half, chunk_rows=512)
)
ps = PCA(k=4).fit(ChunkSource.from_array(half, chunk_rows=512))
assert ps.summary["streamed"] and ps.summary["n_rows"] == 4000

# --- ALS: each rank contributes its LOCAL ratings shard (the per-rank
# partitions of the reference's shuffle, ALSDALImpl.scala:95-109).  This
# exercises the multi-process branches of exchange_ratings (allgathered
# bucket counts + make_array_from_process_local_data), the allgathered
# id-maxima resolution in ALS.fit, and the rank-local sharded factor path
# (no host materializes (n_users, rank); gather is on-demand collective).
from oap_mllib_tpu.models.als import ALS

rng_als = np.random.default_rng(77)
NU, NI, RANK = 60, 40, 3
xt = rng_als.normal(size=(NU, RANK)).astype(np.float32)
yt = rng_als.normal(size=(NI, RANK)).astype(np.float32)
au = rng_als.integers(NU, size=1200).astype(np.int64)
ai = rng_als.integers(NI, size=1200).astype(np.int64)
au[0], ai[0] = NU - 1, NI - 1  # pin the id maxima deterministically
ar = ((xt[au] * yt[ai]).sum(1)
      + rng_als.normal(size=1200).astype(np.float32) * 0.1).astype(np.float32)
# UNEVEN split: 590 vs 610 edges
cut = 590
sl = slice(0, cut) if rank == 0 else slice(cut, None)

als_out = {}
for implicit, tag in ((True, "imp"), (False, "exp")):
    m_als = ALS(rank=RANK, max_iter=3, reg_param=0.1, alpha=0.8,
                implicit_prefs=implicit, seed=3).fit(au[sl], ai[sl], ar[sl])
    assert m_als.summary["accelerated"]
    assert m_als.summary.get("sharded_factors"), "factors not kept sharded"
    als_out[f"als_{tag}_uf"] = np.asarray(m_als.user_factors_).tolist()
    als_out[f"als_{tag}_if"] = np.asarray(m_als.item_factors_).tolist()

# item-sharded 2-D layout across the real 2-process world: a second
# shuffle by item block, Y block-sharded over the global mesh, all_gather
# exchanges inside the scan, and the on-demand item-factor gather becomes
# a COLLECTIVE (every rank touches item_factors_ together)
set_config(als_item_layout="sharded")
m_sh = ALS(rank=RANK, max_iter=3, reg_param=0.1, alpha=0.8,
           implicit_prefs=True, seed=3).fit(au[sl], ai[sl], ar[sl])
assert m_sh.summary["item_layout"] == "sharded"
als_out["als_sh_uf"] = np.asarray(m_sh.user_factors_).tolist()
als_out["als_sh_if"] = np.asarray(m_sh.item_factors_).tolist()
set_config(als_item_layout="auto")

# --- streamed ALS composed with the REAL 2-process mesh: each rank
# streams its LOCAL triples through a ChunkSource; the prep
# redistributes edges by block over the process boundary (chunked
# fixed-shape allgather) and the fit walks host-resident grouped
# layouts through each device (ops/als_block_stream).  Forced grouped:
# the tiny test data would otherwise trip the COO blowup guard.
set_config(als_kernel="grouped")
trip = np.stack(
    [au[sl].astype(np.float64), ai[sl].astype(np.float64),
     ar[sl].astype(np.float64)], axis=1,
)
m_st = ALS(rank=RANK, max_iter=3, reg_param=0.1, alpha=0.8,
           implicit_prefs=True, seed=3).fit(
    ChunkSource.from_array(trip, chunk_rows=256)
)
assert m_st.summary.get("streamed"), m_st.summary
assert m_st.summary.get("block_parallel"), m_st.summary
als_out["als_st_uf"] = np.asarray(m_st.user_factors_).tolist()
als_out["als_st_if"] = np.asarray(m_st.item_factors_).tolist()

# the 2-D item-sharded streamed composition across the process boundary:
# the single-sweep double redistribution (user AND item keyed), the
# per-half-iteration replicate() of the other side's block factors, and
# the collective item-factor gather all cross processes here
set_config(als_item_layout="sharded")
m_st2 = ALS(rank=RANK, max_iter=3, reg_param=0.1, alpha=0.8,
            implicit_prefs=True, seed=3).fit(
    ChunkSource.from_array(trip, chunk_rows=256)
)
assert m_st2.summary.get("streamed"), m_st2.summary
assert m_st2.summary["item_layout"] == "sharded", m_st2.summary
als_out["als_st_sh_uf"] = np.asarray(m_st2.user_factors_).tolist()
als_out["als_st_sh_if"] = np.asarray(m_st2.item_factors_).tolist()
set_config(als_item_layout="auto", als_kernel="auto")

# --- PySpark-adapter distributed ingestion: a mocked partitioned
# DataFrame (the duck-typed rdd.mapPartitionsWithIndex surface) feeds
# each process ONLY its partitions (pid % world == rank), which the
# adapter passes as this process's local shard of the multi-host fit
# (compat/pyspark._collect_local_partitions — the executor-local
# conversion of the reference, OneDAL.scala:92-166).  No process ever
# collects the whole dataset.
from oap_mllib_tpu.compat import pyspark as compat_pyspark


class _PartDF:
    """Minimal partitioned-DataFrame mock: rows split into n_parts
    contiguous partitions; mapPartitionsWithIndex hands each (pid,
    iterator) to the filter like Spark would."""

    def __init__(self, cols, n_parts):
        self._cols, self._nparts = cols, n_parts

    @property
    def columns(self):
        return list(self._cols)

    def select(self, *names):
        return _PartDF({n: self._cols[n] for n in names}, self._nparts)

    def collect(self):
        names = list(self._cols)
        n = len(self._cols[names[0]])
        return [tuple(self._cols[c][j] for c in names) for j in range(n)]

    def count(self):
        # the adapter cross-checks allgathered kept-row counts against
        # this (compat/pyspark._collect_local_partitions)
        return len(self._cols[next(iter(self._cols))])

    @property
    def rdd(self):
        rows = self.collect()
        parts = np.array_split(np.arange(len(rows)), self._nparts)

        class _Res:
            def __init__(self, out):
                self._out = out

            def collect(self):
                return self._out

        class _RDD:
            def mapPartitionsWithIndex(self, f):
                out = []
                for pid, idx in enumerate(parts):
                    out.extend(f(pid, iter([rows[j] for j in idx])))
                return _Res(out)

        return _RDD()


pdf = _PartDF({"features": [list(row) for row in x]}, 8)
am = compat_pyspark.KMeans(k=5, seed=7, maxIter=30).fit(pdf)
assert am.summary.accelerated

rdf = _PartDF(
    {
        "user": [int(v) for v in au],
        "item": [int(v) for v in ai],
        "rating": [float(v) for v in ar],
    },
    6,
)
a_als = compat_pyspark.ALS(rank=RANK, maxIter=3, regParam=0.1, alpha=0.8,
                           implicitPrefs=True, seed=3, userCol="user",
                           itemCol="item", ratingCol="rating",
                           coldStartStrategy="drop").fit(rdf)
# the cold-start seen sets must be WORLD-consistent even though each
# rank ingested different partitions (compat/spark._global_unique)
seen_u = sorted(int(v) for v in a_als._inner._seenUsers)

print(
    "RESULT "
    + json.dumps(
        {
            "rank": rank,
            "kmeans_cost": float(m.summary.training_cost),
            "kmeans_iters": int(m.summary.num_iter),
            "weighted_cost": float(mw.summary.training_cost),
            "uneven_cost": float(mu.summary.training_cost),
            "pca_var": np.asarray(p.explained_variance_).tolist(),
            "pca_pc0_abs": np.abs(np.asarray(p.components_)[:, 0]).tolist(),
            "kmeans_mp_cost": float(m_mp.summary.training_cost),
            "kmeans_mp_iters": int(m_mp.summary.num_iter),
            "pca_mp_var": np.asarray(p_mp.explained_variance_).tolist(),
            "streamed_cost": float(ms.summary.training_cost),
            "streamed_iters": int(ms.summary.num_iter),
            "streamed_rand_cost": float(ms_rand.summary.training_cost),
            "streamed_pca_var": np.asarray(ps.explained_variance_).tolist(),
            "streamed_pca_pc0_abs": np.abs(
                np.asarray(ps.components_)[:, 0]
            ).tolist(),
            "adapter_mp_cost": float(am.summary.training_cost),
            "adapter_als_uf": np.asarray(a_als.userFactors).tolist(),
            "adapter_seen_users": seen_u,
            **als_out,
        }
    ),
    flush=True,
)
