"""Serving-plane replica-eviction pseudo-cluster worker (ISSUE 13).

One replica of a REAL ``jax.distributed`` serving fleet: both ranks pin
the same fitted K-Means model (replicated weights), answer identical
request legs, and heartbeat between legs over the deadline-watchdogged
host collective plane (serving/ha.py).  Modes (env
``SERVING_WORKER_MODE``):

- ``evict`` — rank 1 SIGKILLs itself before the heartbeat of leg 3 (a
  preempted replica); rank 0's next heartbeat must convert into a
  ``CollectiveTimeoutError`` which the :class:`ReplicaGuard` absorbs:
  the survivor EVICTS the fleet view, keeps answering the remaining
  legs in local-only mode, and its answers are bit-identical before
  and after the eviction (printed as per-leg digests the parent
  cross-checks).  Exit 0 with ``EVICTED`` + ``SERVE_OK`` markers.
- ``relaunched`` — the supervisor's replacement replica: a 1-process
  world (nproc=1) that LOADS the model the fleet saved beside the
  sideband (a fit inside the 2-process world is a collective over both
  ranks' rows, so refitting alone would give another model), serves
  the same request legs and prints the same digests, so the parent can
  assert the relaunch answers exactly what the survivor does.

Invoked as:  python pseudo_cluster_worker_serving.py RANK NPROC COORD LOCAL_DEV
(the standard worker argv — the shared _launch_world plumbing spawns it).
"""

import hashlib
import os
import sys

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["SERVING_WORKER_MODE"]
crash_dir = os.environ["SERVING_CRASH_DIR"]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

if nproc > 1:
    from oap_mllib_tpu.parallel import bootstrap

    ran = bootstrap.initialize_distributed(coord, nproc, rank)
    assert ran, "initialize_distributed returned False"

from oap_mllib_tpu import serving
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.kmeans import KMeans

# the deadline is the mechanism under test: well under the parent's
# 120 s watchdog, well over a healthy heartbeat
set_config(collective_timeout=10.0, crash_dir=crash_dir)

# every replica fits the same model from the same data (replicated
# weights — the serving fleet contract) and serves the same requests
rng = np.random.default_rng(77)
x = rng.normal(size=(600, 8)).astype(np.float32)
model_dir = os.path.join(crash_dir, "served-model")
if mode == "relaunched":
    from oap_mllib_tpu.models.kmeans import KMeansModel

    model = KMeansModel.load(model_dir)
else:
    model = KMeans(k=4, seed=5, init_mode="random", max_iter=4).fit(x)
    if rank == 0:
        model.save(model_dir)  # what a relaunched replica serves from
handle = serving.serve(model)
handle.warmup(128)

requests = [
    rng.normal(size=(int(s), 8)).astype(np.float32)
    for s in rng.integers(5, 128, size=6)
]

guard = serving.ReplicaGuard()
digests = []
announced = False
for leg, batch in enumerate(requests):
    if mode == "evict" and rank == 1 and nproc > 1 and leg == 3:
        import signal

        os.kill(os.getpid(), signal.SIGKILL)  # a preempted replica
    with guard.leg():
        ids = handle.predict(batch)
        digests.append(hashlib.sha256(ids.tobytes()).hexdigest()[:16])
        print(f"ANSWER rank={rank} leg={leg} digest={digests[-1]}",
              flush=True)
        if not guard.local_only and nproc > 1:
            view = serving.heartbeat(requests=handle.requests)
            if leg == 0:
                print(f"FLEET rank={rank} world={view['world']}",
                      flush=True)
    if guard.local_only and not announced:
        # first leg whose heartbeat the guard absorbed: announce the
        # eviction once — the survivor keeps answering locally
        announced = True
        err = type(guard.last_error).__name__
        print(f"EVICTED rank={rank} leg={leg} err={err}", flush=True)

print(f"SERVE_OK rank={rank} legs={len(digests)} "
      f"local_only={guard.local_only}", flush=True)
os._exit(0)
