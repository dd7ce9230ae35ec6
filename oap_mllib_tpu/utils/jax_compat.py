"""The single import point for ``shard_map``.

Every sharded program in the package builds through this name, so the
SPMD dataflow rules of ``dev/oaplint`` have one spelling to resolve and
a future change of entry point touches one file.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with this package's keyword-only call shape."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
