"""Fleet observability pseudo-cluster worker (ISSUE 11).

One rank of a real ``jax.distributed`` world driving the fleet control
plane (telemetry/fleet.py + telemetry/flightrec.py).  Modes (env
``FLEET_WORKER_MODE``):

- ``skew`` — rank 1's chunk source sleeps per chunk (a deliberately
  slowed rank).  Every rank runs a streamed K-Means fit with per-pass
  fleet rollups armed (auto + 2-process world) and prints its fleet
  WINDOW (the gathered per-pass frames) and FLEETBLOCK (the summary's
  fleet block); rank 0 additionally scrapes its OWN live /metrics
  endpoint from a background thread WHILE the fit runs and prints
  SCRAPE_OK once ``oap_fleet_*`` families appear mid-fit.  The parent
  asserts the windows agree across ranks, the hand-fold matches, and
  the block names rank 1 with skew > 1.5.
- ``kill`` — flight recorder + collective deadline + crash sideband
  armed; rank 1 SIGKILLs itself mid-read of Lloyd pass 3.  Rank 0 must
  raise a recovery error within the deadline (at once where the
  transport reports the closed socket, so the ring holds only what the
  fit did until then — three walks of ten chunks are >= 32 events),
  leaving a v2 crash record whose ``flight_recorder`` tail carries
  >= 32 events.

Invoked as:  python pseudo_cluster_worker_fleet.py RANK NPROC COORD LOCAL_DEV
"""

import json
import os
import sys
import threading
import time

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["FLEET_WORKER_MODE"]

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
from oap_mllib_tpu.telemetry import fleet
from oap_mllib_tpu.utils import recovery

rng = np.random.default_rng(99)
rows, chunk = 3000, 300
x = rng.normal(size=(rows * nproc, 8)).astype(np.float32)
shard = x[rank * rows: (rank + 1) * rows]

walks = {"n": 0}


def gen():
    walks["n"] += 1
    for lo in range(0, rows, chunk):
        if mode == "skew" and rank == 1:
            time.sleep(0.03)  # the deliberately slowed rank
        if (mode == "kill" and rank == 1 and walks["n"] == 4
                and lo >= chunk * 4):
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        yield shard[lo: lo + chunk]


src = ChunkSource(gen, 8, chunk, n_rows=rows)

if mode == "kill":
    crash_dir = os.environ["FLEET_CRASH_DIR"]
    set_config(
        flight_recorder=256, collective_timeout=10.0, crash_dir=crash_dir,
    )
    try:
        KMeans(k=4, seed=7, init_mode="random", max_iter=6, tol=0.0).fit(src)
    except recovery.CollectiveTimeoutError as e:
        print(f"TIMEOUT_CAUGHT rank={rank} op={e.op}", flush=True)
        os._exit(0)  # crash record written; peer is gone
    except recovery.PeerAbortError:
        print(f"PEER_ABORT rank={rank}", flush=True)
        os._exit(0)
    except Exception as e:  # noqa: BLE001 — surface env markers
        print(f"WORKER_ERROR rank={rank} {type(e).__name__}: {e}",
              flush=True)
        os._exit(4)
    print(f"RESULT_UNEXPECTED rank={rank}", flush=True)
    os._exit(5)

# -- skew mode ----------------------------------------------------------------
port = int(os.environ.get("FLEET_METRICS_PORT", "0"))
set_config(flight_recorder=256, metrics_port=port)

scrape = {"ok": False}


def _scraper():
    import urllib.request

    url = f"http://127.0.0.1:{port + rank}/metrics"
    for _ in range(600):  # poll while the fit runs
        try:
            text = urllib.request.urlopen(url, timeout=2).read().decode()
            if "oap_fleet_pass_seconds" in text:
                scrape["ok"] = True
                return
        except OSError:
            pass
        time.sleep(0.1)


if rank == 0 and port:
    threading.Thread(target=_scraper, daemon=True).start()

window = {}
_orig_finalize = fleet.finalize_fit


def _capturing_finalize(summary, root):
    # the per-fit window resets at finalization — keep a copy for the
    # parent's cross-rank consistency assertions
    window["passes"] = fleet.last_window()
    _orig_finalize(summary, root)


fleet.finalize_fit = _capturing_finalize

try:
    m = KMeans(k=4, seed=7, init_mode="random", max_iter=4, tol=0.0).fit(src)
except Exception as e:  # noqa: BLE001 — surface env markers
    print(f"WORKER_ERROR rank={rank} {type(e).__name__}: {e}", flush=True)
    os._exit(4)

block = m.summary.fleet
print(f"FLEETBLOCK rank={rank} {json.dumps(block, sort_keys=True)}",
      flush=True)
print(
    "WINDOW rank=%d %s" % (
        rank,
        json.dumps(
            [
                {"phase": w["phase"], "frames": w["frames"],
                 "fields": w["fields"],
                 "slowest_rank": w["slowest_rank"],
                 "skew_ratio": w["skew_ratio"]}
                for w in window.get("passes", [])
            ],
            sort_keys=True,
        ),
    ),
    flush=True,
)
if rank == 0 and port:
    # give the scraper a beat in case the fit finished between polls
    for _ in range(20):
        if scrape["ok"]:
            break
        time.sleep(0.1)
    print(f"SCRAPE {'OK' if scrape['ok'] else 'MISSED'} rank=0", flush=True)
print(f"RESULT rank={rank} ok=1", flush=True)
