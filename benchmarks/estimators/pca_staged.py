"""PCA as ``estimators/pca.py`` drives it, for a configuration that says how
its table must go up (``expect_upload``): every fit's ``table_convert/upload``
span is held to ``piece_bytes_max``, as ``expect_kernel`` holds a fit to its
kernel.

A kernel other than the promised one is a failed fit, counted by ``run.py``.
An upload in larger pieces than the configuration allows is not a slower fit
of the same cell but another one: on a v5e host 8.6 GB handed over at once
go up at 0.5-1.3 GB/s, not 10, at a rate that swings by a seventh from run to
run (PERF.md section 6, PR 31), so no bound holds its ``fit_s``.  Such a
program cannot run the configuration, and the run says so at the first fit:
a line on stderr, no result line, exit code ``EXIT_CANNOT_STAGE``.
"""

import sys

import numpy as np

from estimators.pca import (  # noqa: F401  (what an adapter gives the harness)
    REFERENCE, eigh_work, fit_work, make_data, moments_work, phase_work,
    program_settings,
)

EXIT_CANNOT_STAGE = 4
UPLOAD_SPAN = "table_convert/upload"


def upload_breach(cfg, attrs):
    """Why an upload with these span attributes breaks the configuration's
    ``expect_upload``, or None.  A program that records no ``pieces`` sent
    each shard whole."""
    limit = cfg.get("expect_upload", {}).get("piece_bytes_max")
    if limit is None or "bytes" not in attrs:
        return None
    pieces = attrs.get("pieces", 1) * attrs.get("shards", 1)
    piece_bytes = -(-attrs["bytes"] // max(pieces, 1))
    if piece_bytes <= limit:
        return None
    return (f"{UPLOAD_SPAN} sent {attrs['bytes']} bytes in {pieces} piece(s) of "
            f"{piece_bytes}; the configuration allows {limit} a piece")


def fit(cfg, x, seed):
    """``estimators/pca.fit``, and the upload it made held to the
    configuration."""
    from oap_mllib_tpu import PCA

    model = PCA(k=cfg["k"]).fit(x)
    s = model.summary
    timings = s["timings"]
    phases = dict(timings.as_dict())
    attrs = timings.root.node(UPLOAD_SPAN).attrs if UPLOAD_SPAN in phases else {}
    breach = upload_breach(cfg, attrs)
    if breach:
        print(f"estimators/pca_staged.py: this program cannot run {cfg['name']}: "
              f"{breach}", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_CANNOT_STAGE)
    result = {
        "components": np.array(model.components_),
        "ratios": np.array(model.explained_variance_),
    }
    info = {
        "phases": phases,
        "kernel": s.get("kernel"),
        "accelerated": bool(s.get("accelerated", False)),
        "resilience": dict(s.get("resilience") or {}),
    }
    return result, info
