"""Compile the kernels a default fit launches on one v5e chip — without
the chip.

The TPU compiler is installed wherever jax[tpu] is; it compiles for a
chip that is described and not attached.  Interpret mode cannot see what
it refuses: these kernels passed every interpret-mode test while Mosaic
rejected their ``(tile_rows, 1)`` column windows, a 111-row DMA window,
500-row ring segments, and Gram / centre blocks past the default
scoped-VMEM limit.  Each case compiles one raw kernel at the size
``chip_smoke.py`` (or ``bench.py --all``) runs it at, plus the edges the
dispatch rules admit.  Nothing runs, so nothing here says a result is
right — ``tests_tpu/`` and ``chip_smoke.py`` do that on the chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist
every worker imports this file.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

F32 = jnp.float32
N_KMEANS = 1 << 20  # chip_smoke's K-Means / PCA row count
TILE, DEPTH = 512, 2  # autotune.DEFAULTS geometry
MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to jax's persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles): keep the cache off around this file."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    """Lower + compile ``fn`` for the shapes' (described) devices; a
    refusal by Mosaic or XLA:TPU raises here."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    return compiled


def _s(shape, sharding):
    return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)


def _mosaic_bodies(compiled_text, kernel):
    """The Mosaic body of every ``kernel`` custom call in a compiled
    program, as MLIR text: it rides in the call's backend_config as
    bytecode, which parses without the chip."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    bodies = []
    for line in compiled_text.splitlines():
        if f"%{kernel}" not in line or "custom-call(" not in line:
            continue
        body = base64.b64decode(
            re.search(r'"body":"([A-Za-z0-9+/=]+)"', line).group(1)
        )
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        bodies.append(str(ir.Module.parse(body, ctx)))
    return bodies


def _assert_round_gathers_its_picks(text):
    """The compiled k-means|| round places its picks by a search of the
    prefix and a gather: no ``scatter`` walks the table.  It holds two
    loops, told apart by where they were traced: the search's (log2 of
    the rows steps, inside ``searchsorted``) and the fold's, whose trip
    count is the round's own pick count, a value read on the device, so
    the compiler knows none."""
    ops = [line.split(" = ", 1)[1] for line in text.splitlines()
           if " = " in line and "scatter" in line.split("metadata=")[0]]
    assert not ops, ops[:3]
    loops = [
        line for line in text.splitlines()
        if " while(" in line and "pll_round/" in line
        and "_uniform" not in line  # the random bits' own loop
    ]
    search = [line for line in loops if "searchsorted" in line]
    fold = [line for line in loops if "pll_round/while" in line]
    assert len(search) == 1 and len(fold) == 1 and len(loops) == 2
    assert "known_trip_count" not in fold[0]


def _assert_walks_to_a_bound_read_on_the_device(compiled_text, calls):
    """Every ``kmeans_accumulate_walk`` call of the program takes its trip
    count from SMEM: ONE loop whose upper bound is the loaded scalar (no
    constant trip count), and DMA starts before it only under a guard."""
    import re

    bodies = _mosaic_bodies(compiled_text, "kmeans_accumulate_walk")
    assert len(bodies) == calls
    for body in bodies:
        assert re.search(
            r'"stable_mosaic\.memref\.load"\(%arg0, [^)]*\) : '
            r"\(memref<1xi32, #tpu\.memory_space<smem>>", body
        ), "the walk reads no bound from SMEM"
        loops = re.findall(r'"stable_mosaic\.scf\.for"\(%\d+, (%\d+), ', body)
        assert len(loops) == 1
        # its upper bound is computed, not a constant trip count
        assert f'{loops[0]} = "stable_mosaic.arith.constant"' not in body
        head = body[:body.index('"stable_mosaic.scf.for"')]
        assert head.count("tpu.enqueue_dma") == 2  # depth 2: x and w of tile 0
        assert head.index("scf.if") < head.index("tpu.enqueue_dma")


def _mosaic_matmuls(compiled_text, kernel):
    """The MXU products of every ``kernel`` custom call in a compiled
    program, as ``(fp32 contract precision?, lhs type, result type)``."""
    import re

    calls = []
    for body in _mosaic_bodies(compiled_text, kernel):
        found = []
        for op in body.splitlines():
            if "tpu.matmul" not in op:
                continue
            lhs, out = re.search(
                r": \(vector<([^>]+)>.*\) -> vector<([^>]+)>", op
            ).groups()
            found.append(("contract_precision<fp32>" in op, lhs, out))
        calls.append(found)
    return calls


def _kmeans_shapes(sharding, n=N_KMEANS, k=1024, d=256):
    """(x, weight column, centres, live tiles) of one padded K-Means
    launch: the walk's bound is an argument, so what compiles is the
    dynamic trip count a fit runs (the kernel reads it from SMEM)."""
    return (
        _s((n, d), sharding), _s((n, 1), sharding), _s((k, d), sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
    )


class TestKMeansKernels:
    """k=1000 pads to 1024 lanes; d=256; the walk at the default
    geometry (depth 2) is the one Pallas form a fit launches."""

    @pytest.mark.parametrize("mode", ["highest", "high", "default"])
    def test_walk_loop_mode(self, one_chip, mode):
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk

        _compile(
            lambda x, w, c, live: kk._pallas_accumulate_dbuf(
                x, w, c, mode, False, False, TILE, DEPTH, live),
            *_kmeans_shapes(one_chip),
        )

    def test_walk_final_cost_pass(self, one_chip):
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk

        _compile(
            lambda x, w, c, live: kk._pallas_accumulate_dbuf(
                x, w, c, "highest", False, True, TILE, DEPTH, live),
            *_kmeans_shapes(one_chip),
        )

    @pytest.mark.parametrize("need_cost,cap", [
        (False, 9 * MiB), (True, 10.5 * MiB),
    ])
    def test_walk_scoped_vmem_at_the_cells_shape(
            self, one_chip, monkeypatch, need_cost, cap):
        """The ``highest`` walk at the cell's shape fits a scoped-VMEM cap
        well under one more ``(tile_rows, k)`` f32 sheet (2 MiB) above
        what it uses (7.6 MiB in the loop, 9.1 MiB with the cost, found
        by bisecting the cap; the six-pass sums read the same): a change
        that brings a sheet-sized temporary back fails here."""
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk

        monkeypatch.setattr(kk, "VMEM_LIMIT_BYTES", int(cap))
        _compile(
            lambda x, w, c, live: kk._pallas_accumulate_dbuf(
                x, w, c, "highest", False, need_cost, TILE, DEPTH, live),
            *_kmeans_shapes(one_chip, n=2097152),
        )

    def test_one_device_lloyd_program(self, one_chip, monkeypatch):
        """The one-chip cell's whole Lloyd program at its shapes
        (2,097,152 x 256, k=1000, 20 iterations): pad, loop and final
        cost pass in one jit, no collective."""
        from oap_mllib_tpu.ops import kmeans_ops

        # the walk's dispatch asks the backend: take its TPU branch
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fn = kmeans_ops._build_lloyd(
            None, "data", 1, 20, "highest", "f32", True, TILE, DEPTH,
            False, 1,
        )
        rows, d, k = 2097152, 256, 1000
        compiled = fn.lower(
            _s((rows, d), one_chip), _s((rows,), one_chip),
            _s((k, d), one_chip), _s((), one_chip),
        ).compile()
        text = compiled.as_text()
        assert "kmeans_accumulate_walk" in text
        assert "all-reduce" not in text
        # the loop's walk and the cost pass both end at the live tile
        _assert_walks_to_a_bound_read_on_the_device(text, 2)
        mem = compiled.memory_analysis()
        # the table alone: no padded copy of it (2.1 GB), no (rows, k)
        # sheet (8.4 GB), only the centres' and moments' blocks
        assert mem.argument_size_in_bytes < 1.01 * rows * d * 4
        assert mem.temp_size_in_bytes < 4 * MiB
        # the tier the configuration states: in the loop's walk and in the
        # cost pass ONE product at f32 contract precision, the cross term
        # x @ c.T (six bf16 passes: the assignment step_gap holds), and
        # three single bf16 passes for the sums
        walks = _mosaic_matmuls(text, "kmeans_accumulate_walk")
        assert len(walks) == 2
        for products in walks:
            assert sorted(products) == [
                (False, "512x1024xbf16", "1024x256xf32")] * 3 + [
                (True, "512x256xf32", "512x1024xf32")]

    @pytest.mark.parametrize("mode", ["high", "highest"])
    def test_walk_at_the_dispatch_rule_edge(self, one_chip, mode):
        """The largest resident blocks ``pallas_preferred`` admits: a
        shape just inside the bound must compile, at both tiers whose
        sums hold several ``(k, d)`` f32 partials (two at ``high``, three
        at ``highest``)."""
        from oap_mllib_tpu.ops import kmeans_ops
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk

        k, d = kmeans_ops.PALLAS_MAX_K, (
            kmeans_ops.PALLAS_MAX_KD // kmeans_ops.PALLAS_MAX_K
        )
        assert kmeans_ops.pallas_preferred(d, k, "high")
        assert not kmeans_ops.pallas_preferred(d, 2 * k, "high")
        assert not kmeans_ops.pallas_preferred(2 * d, k, "high")
        _compile(
            lambda x, w, c, live: kk._pallas_accumulate_dbuf(
                x, w, c, mode, False, False, TILE, DEPTH, live),
            *_kmeans_shapes(one_chip, n=1 << 16, k=k, d=d),
        )

    def test_candidate_reduction_at_the_cells_shape(self, one_chip):
        """The k-means|| reduction as the benchmark's cell runs it: 1 +
        4k * 2 = 8001 slots x 256, k = 1000 dependent draws in ONE
        program whose buffers stay small (no (slots, k) sheet)."""
        import functools

        from oap_mllib_tpu.ops import kmeans_ops

        m, d, k = 8001, 256, 1000
        compiled = jax.jit(
            functools.partial(kmeans_ops._reduce_candidates, k=k)
        ).lower(
            _s((m, d), one_chip), _s((m,), one_chip), _s((m,), one_chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        ).compile()
        text = compiled.as_text()
        assert " while(" in text  # the k draws are one loop on the device
        assert "bf16" not in text  # f32 throughout: no product, no argmin
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes == k * d * 4
        assert mem.temp_size_in_bytes < m * d * 4


class TestAssignment:
    """The XLA assignment every non-Pallas route shares (serving, the
    sharded Lloyd, k-means||): at ``highest`` it promises the f32
    nearest centre.  ``jnp.argmin`` does not keep that promise on this
    compiler — the value output of its (value, index) reduction is
    typed bfloat16, and on the chip two centres within 2^-8 relative
    then tie (1 row in 4096 of ``chip_smoke.py``'s served batch named a
    centre 6.7e-4 farther than the nearest) — so the programs go through
    ``kmeans_ops.argmin_rows``.  The narrowing shows in the compiled
    text, which is what this case holds: no bfloat16 anywhere in an f32
    assignment program."""

    @pytest.mark.parametrize("rows", [1024, 4096])
    def test_serving_assign_keeps_f32(self, one_chip, rows):
        from oap_mllib_tpu.serving import batcher

        program = batcher._build_assign("highest", "f32")
        text = program.lower(
            _s((rows, 256), one_chip), _s((1000, 256), one_chip)
        ).compile().as_text()
        assert "operand_precision={highest,highest}" in text
        assert "bf16" not in text

    def test_lloyd_loop_body_keeps_f32(self, one_chip):
        from oap_mllib_tpu.ops import kmeans_ops

        text = jax.jit(
            lambda x, w, c: kmeans_ops._accumulate(
                x, w, c, "highest", False)
        ).lower(
            _s((8192, 256), one_chip), _s((8192,), one_chip),
            _s((1000, 256), one_chip),
        ).compile().as_text()
        assert "bf16" not in text


class TestPCAKernels:
    @pytest.mark.parametrize("need_gram", [True, False],
                             ids=["gram", "colsum"])
    def test_walk_d128(self, one_chip, need_gram):
        from oap_mllib_tpu.ops.pallas import pca_kernel as pk

        _compile(
            lambda x, m, mu: pk._pallas_moments_dbuf(
                x, m, mu, "highest", False, need_gram, TILE, DEPTH),
            _s((N_KMEANS, 128), one_chip), _s((N_KMEANS, 1), one_chip),
            _s((1, 128), one_chip),
        )

    def test_grid_kernel_d128(self, one_chip):
        from oap_mllib_tpu.ops.pallas import pca_kernel as pk

        _compile(
            lambda x, m, mu: pk._pallas_moments(
                x, m, mu, "highest", False, True, TILE),
            _s((N_KMEANS, 128), one_chip), _s((N_KMEANS, 1), one_chip),
            _s((1, 128), one_chip),
        )

    def test_walk_at_the_dispatch_rule_edge_d2048(self, one_chip):
        """``bench.py --all``'s 128k x 2048 shape sits exactly on the
        bound of ``pallas_gram_preferred`` (a 16 MB Gram block); the
        "high" tier's three split passes are its hungriest form."""
        from oap_mllib_tpu.ops.pallas import pca_kernel as pk

        assert pk.pallas_gram_preferred(2048, "high")
        assert not pk.pallas_gram_preferred(2049, "high")
        _compile(
            lambda x, m, mu: pk._pallas_moments_dbuf(
                x, m, mu, "high", False, True, TILE, DEPTH),
            _s((1 << 17, 2048), one_chip), _s((1 << 17, 1), one_chip),
            _s((1, 2048), one_chip),
        )

    def test_streamed_colsum_chunk(self, one_chip, monkeypatch):
        """``stream_ops._colsum_chunk_pallas`` picks its kernel from
        ``jax.default_backend()``; steer that here, in the test, to the
        branch the chip takes."""
        from oap_mllib_tpu.ops import stream_ops

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        rows = 1 << 16  # data/stream.DEFAULT_CHUNK_ROWS
        compiled = stream_ops._colsum_chunk_pallas.lower(
            _s((128,), one_chip), _s((rows, 128), one_chip),
            _s((rows,), one_chip), tile_rows=TILE, depth=DEPTH,
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()


class TestALSKernels:
    """Rank 10 at MovieLens-1M scale: 6040 users pad to 6144 columns of
    the moment sheet, whose 111 rows pad to 112."""

    RANK = 10

    @pytest.mark.parametrize("walk", [True, False], ids=["walk", "grid"])
    def test_solve(self, one_chip, walk):
        from oap_mllib_tpu.ops.pallas import als_kernel as ak

        r = self.RANK
        rows = ak.pad_to(r * r + r + 1, ak.SUBLANE)

        def fn(m, g, reg):
            if walk:
                return ak._pallas_solve_dbuf(m, g, reg, r, True, False,
                                             ak._BATCH, DEPTH)
            return ak._pallas_solve(m, g, reg, r, True, False, ak._BATCH)

        _compile(fn, _s((rows, 6144), one_chip), _s((r, r), one_chip),
                 _s((1, 1), one_chip))

    @pytest.mark.parametrize("walk", [True, False], ids=["walk", "grid"])
    def test_factor_gram(self, one_chip, walk):
        from oap_mllib_tpu.ops.pallas import als_kernel as ak

        def fn(f):
            if walk:
                return ak._pallas_factor_gram_dbuf(f, "highest", False,
                                                   TILE, DEPTH)
            return ak._pallas_factor_gram(f, "highest", False, TILE)

        _compile(fn, _s((6144, 128), one_chip))


class TestRingKernel:
    def test_remote_dma_ring_on_four_chips(self, topo):
        """The packed K-Means moments of k=1000, d=256 over a 4-device
        ring: (1000, 258) pads to (1024, 512)."""
        from oap_mllib_tpu.ops.pallas import ring_reduce as rr
        from oap_mllib_tpu.utils.jax_compat import shard_map

        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
        spec = P("data", None, None)
        _compile(
            shard_map(
                lambda blk: rr._ring_pallas(blk[0], "data", 4)[None],
                mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
            ),
            _s((4, 1024, 512), NamedSharding(mesh, spec)),
        )


class TestDataParallelKMeans:
    """The four-chip K-Means cell (``kmeans_d256_k1000_host4``) at its
    shapes — 2,097,152 x 256 float32 rows a device, k=1000 — before any
    chip time is spent on it: the Lloyd loop as ONE shard_map with the
    walk on every device's shard and the moments all-reduced, and the
    k-means|| round on the row-sharded table as GSPMD cuts it."""

    ROWS, D, K = 4 * 2097152, 256, 1000
    HBM = 15.75 * 2**30  # what the v5e's compiler lets one program hold

    @pytest.fixture()
    def mesh(self, topo):
        return Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))

    def test_sharded_lloyd_program(self, mesh, monkeypatch):
        from oap_mllib_tpu.ops import kmeans_ops

        # the walk's dispatch asks the backend: take its TPU branch
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fn = kmeans_ops._build_lloyd(
            mesh, "data", 4, 20, "highest", "f32", True, TILE, DEPTH,
            False, 1,
        )
        rows = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())
        compiled = fn.lower(
            _s((self.ROWS, self.D), rows),
            _s((self.ROWS,), NamedSharding(mesh, P("data"))),
            _s((self.K, self.D), rep),
            _s((), rep),
        ).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text  # the kernel, on every device
        assert "kmeans_accumulate_walk" in text
        assert "all-reduce" in text  # the moments
        assert "all-gather" not in text  # no device ever sees the table
        # each device walks to the bound it read from its own shard
        _assert_walks_to_a_bound_read_on_the_device(text, 2)
        mem = compiled.memory_analysis()
        # a device holds its shard and the walk's padded copy of it,
        # not a (rows, k) sheet
        held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert held < 3 * (self.ROWS // 4) * self.D * 4

    def _compiled_pll_round(self, rows, table, row, rep):
        """The k-means|| round at the cells' widths for ``rows`` rows, and
        what one device may hold while it runs."""
        from oap_mllib_tpu.ops import kmeans_ops

        cap = 4 * self.K
        chunk = kmeans_ops._slot_chunk_size(cap)
        compiled = kmeans_ops._pll_round.lower(
            _s((rows, self.D), table),
            _s((rows,), row),
            _s((rows,), row),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=row),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
            _s((), rep),
            cap=cap, chunk=chunk,
        ).compile()
        text = compiled.as_text()
        # a shard's picked rows are gathered where they lie and summed
        assert "all-gather" not in text
        _assert_round_gathers_its_picks(text)
        mem = compiled.memory_analysis()
        on_device = self.ROWS // 4
        # a step's distance sheet is a DEVICE's rows x the slot chunk,
        # never rows x k nor the table's rows, and nothing table-sized
        # stands beside it: the picked rows are gathered from the table
        sheet = on_device * chunk * 4
        assert chunk < self.K and sheet > on_device * self.D * 4
        assert sheet <= mem.temp_size_in_bytes < 1.05 * sheet
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < self.HBM
        return compiled

    def test_pll_round_on_a_row_sharded_table(self, mesh):
        compiled = self._compiled_pll_round(
            self.ROWS,
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P()),
        )
        # slots come back replicated: the host fetch needs no re-gather
        slots_sharding = compiled.output_shardings[0]
        assert slots_sharding.is_fully_replicated

    def test_pll_round_on_one_chip(self, one_chip):
        """The one-chip cell's round: the same rows a device, no mesh."""
        self._compiled_pll_round(self.ROWS // 4, one_chip, one_chip, one_chip)

    @pytest.mark.parametrize("rows_a_chip", [2097152, 4194304])
    @pytest.mark.parametrize("chips", [1, 4])
    def test_seed_row_distances_are_one_pass_over_the_table(
        self, topo, mesh, chips, rows_a_chip
    ):
        """The k-means|| init starts from every row's distance to the
        seed row, ``min_sq_dists`` against ONE candidate: one program that
        reads the table once and holds no ``x * x`` beside it (run op by
        op, that product stood whole beside the table and set the fit's
        peak), and on the mesh every chip reads its own shard."""
        from oap_mllib_tpu.ops import kmeans_ops

        if chips == 1:
            table = row = rep = SingleDeviceSharding(topo.devices[0])
        else:
            table = NamedSharding(mesh, P("data", None))
            row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
        compiled = kmeans_ops.min_sq_dists.lower(
            _s((chips * rows_a_chip, self.D), table), _s((1, self.D), rep)
        ).compile()
        for collective in ("all-gather", "all-reduce", "collective-permute",
                           "all-to-all"):
            assert collective not in compiled.as_text()
        if chips > 1:
            assert compiled.output_shardings == row
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 1.01 * rows_a_chip * self.D * 4
        assert mem.output_size_in_bytes == rows_a_chip * 4
        assert mem.temp_size_in_bytes < MiB

    def test_candidate_reduction_runs_replicated(self, mesh):
        """On the host's mesh every chip reduces the same 8001 slots from
        the same key: inputs and centres replicated, no traffic."""
        from oap_mllib_tpu.ops import kmeans_ops
        from oap_mllib_tpu.utils import progcache

        rep = NamedSharding(mesh, P())
        progcache.clear()  # the registry may hold another mesh's program
        m = 1 + 2 * 4 * self.K
        compiled = kmeans_ops._reduce_candidates_fn(self.K, mesh).lower(
            _s((m, self.D), rep), _s((m,), rep), _s((m,), rep),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        ).compile()
        text = compiled.as_text()
        for collective in ("all-gather", "all-reduce", "collective-permute",
                           "all-to-all"):
            assert collective not in text
        assert compiled.output_shardings.is_fully_replicated

    def test_upload_writes_a_shards_pieces_in_place(self, mesh):
        """The cell's shard (2 GiB) goes up as two pieces of 1 GiB, one a
        device in flight, each written into its device's one shard-sized
        buffer, made as zeros there: the shard is donated, nothing
        crosses to another chip, and the device holds one shard and one
        piece."""
        from oap_mllib_tpu.data import table as table_mod
        from oap_mllib_tpu.utils import progcache

        shard_rows = self.ROWS // 4
        step, in_flight = table_mod._geometry(4, False, self.D * 4)
        assert (step, in_flight) == (shard_rows // 2, 1)
        dev = SingleDeviceSharding(mesh.devices.flat[1])
        progcache.clear()  # the registry may hold another backend's program
        compiled = table_mod._write_piece().lower(
            _s((shard_rows, self.D), dev), _s((step, self.D), dev),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=dev),
        ).compile()
        text = compiled.as_text()
        assert "input_output_alias={ {}: (0, {}" in text
        for collective in ("all-gather", "all-reduce", "collective-permute",
                           "all-to-all"):
            assert collective not in text
        mem = compiled.memory_analysis()
        shard, piece = shard_rows * self.D * 4, step * self.D * 4
        assert mem.alias_size_in_bytes == shard == mem.output_size_in_bytes
        held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert held <= shard + piece + 2**20
        # the shard's zeros are made on its own device, by one program
        # that holds the shard alone
        zeros = table_mod._zeros(dev).lower(
            (shard_rows, self.D), np.dtype(np.float32)
        ).compile()
        assert zeros.output_shardings == dev
        mem = zeros.memory_analysis()
        assert mem.output_size_in_bytes == shard
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**20


class TestOneChipUpload:
    """The PCA cell (``pca_d512_k10``): an 8 GiB float32 table on ONE
    chip, up in 32 pieces of 256 MiB, each written into the one donated
    table-sized buffer — or a fit would ask for 17 GB where
    ``fallback=False`` hides nothing."""

    ROWS, D, PIECE_ROWS = 4194304, 512, 131072

    def test_writer_aliases_the_donated_table(self, topo):
        from oap_mllib_tpu.data import table as table_mod
        from oap_mllib_tpu.utils import progcache

        mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
        rows = NamedSharding(mesh, P("data", None))
        piece = self.PIECE_ROWS * self.D * 4
        assert piece * table_mod._ONE_DEVICE_PIECES_IN_FLIGHT == (
            table_mod._UPLOAD_PIECE_BYTES
        )
        progcache.clear()  # the registry may hold another backend's program
        compiled = table_mod._write_piece().lower(
            _s((self.ROWS, self.D), rows),
            _s((self.PIECE_ROWS, self.D), rows),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        ).compile()
        assert "input_output_alias={ {}: (0, {}" in compiled.as_text()
        assert compiled.output_shardings == rows
        # the output IS the donated table: the device holds the table and
        # the piece (and the offset), never the table twice
        mem = compiled.memory_analysis()
        table = self.ROWS * self.D * 4
        assert mem.alias_size_in_bytes == table == mem.output_size_in_bytes
        assert mem.temp_size_in_bytes < piece
        held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert held <= table + piece + 2**20


class TestListedScaleOnOneChip:
    """``kmeans_d256_k1000_f64rows``: the listed 3,125,000 rows a chip pad
    to the 2^22-row bucket, twice the rows of every other K-Means cell.
    Every program of the fit that holds the table, at 4,194,304 x 256,
    k=1000, for one described v5e, with what ``memory_analysis`` says it
    holds under what the compiler lets one program have."""

    ROWS, VALID, D, K = 4194304, 3125000, 256, 1000
    HBM = 15.75 * 2**30
    TABLE = ROWS * D * 4

    @staticmethod
    def _held(compiled):
        mem = compiled.memory_analysis()
        return (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)

    def test_pll_round(self, one_chip):
        from oap_mllib_tpu.ops import kmeans_ops

        cap = 4 * self.K
        chunk = kmeans_ops._slot_chunk_size(cap)
        compiled = kmeans_ops._pll_round.lower(
            _s((self.ROWS, self.D), one_chip),
            _s((self.ROWS,), one_chip),
            _s((self.ROWS,), one_chip),
            jax.ShapeDtypeStruct((self.ROWS,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
            _s((), one_chip),
            cap=cap, chunk=chunk,
        ).compile()
        _assert_round_gathers_its_picks(compiled.as_text())
        mem = compiled.memory_analysis()
        # the table and its row vectors; the sheet of one slot chunk
        # (rows x 500 x 4 = 8.4 GB) is the temporary, and no table-sized
        # operand beside it: the picked rows are gathered from the table
        assert self.TABLE <= mem.argument_size_in_bytes < 1.02 * self.TABLE
        sheet = self.ROWS * chunk * 4
        assert sheet <= mem.temp_size_in_bytes < 1.05 * sheet
        assert self._held(compiled) < self.HBM

    def test_lloyd_program(self, one_chip, monkeypatch):
        from oap_mllib_tpu.ops import kmeans_ops

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fn = kmeans_ops._build_lloyd(
            None, "data", 1, 20, "highest", "f32", True, TILE, DEPTH,
            False, 1,
        )
        compiled = fn.lower(
            _s((self.ROWS, self.D), one_chip), _s((self.ROWS,), one_chip),
            _s((self.K, self.D), one_chip), _s((), one_chip),
        ).compile()
        assert "kmeans_accumulate_walk" in compiled.as_text()
        _assert_walks_to_a_bound_read_on_the_device(compiled.as_text(), 2)
        mem = compiled.memory_analysis()
        # the table once: no padded copy of it, no (rows, k) sheet
        assert mem.argument_size_in_bytes < 1.01 * self.TABLE
        assert mem.temp_size_in_bytes < 4 * MiB
        assert self._held(compiled) < self.HBM

    # a whole cast block of 64 MiB, and the one the valid rows end in
    @pytest.mark.parametrize("piece_rows", [65536, 3125000 % 65536])
    def test_write_piece(self, topo, piece_rows):
        from oap_mllib_tpu.data import table as table_mod
        from oap_mllib_tpu.utils import progcache

        assert table_mod._CAST_BLOCK_BYTES == 65536 * self.D * 4
        dev = SingleDeviceSharding(topo.devices[0])
        progcache.clear()  # the registry may hold another backend's program
        compiled = table_mod._write_piece().lower(
            _s((self.ROWS, self.D), dev), _s((piece_rows, self.D), dev),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=dev),
        ).compile()
        assert "input_output_alias={ {}: (0, {}" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == self.TABLE == mem.output_size_in_bytes
        piece = piece_rows * self.D * 4
        assert self._held(compiled) <= self.TABLE + 2 * piece + 2**20
        assert self._held(compiled) < self.HBM


class TestALSCellOnOneChip:
    """``als_implicit_r10_kddcup11``: one user block of the KDD-Cup'11
    table, 500,495 users x 624,961 items, rank 10, five iterations, both
    grouped sides at the width ``als_ops.group_sizes_for`` picks for the
    cell's degree laws (ISSUE 39; the mean's 256 before) on the group
    bucket that width fills.  The programs of the fit's
    ``als_iterations`` for one described v5e, with what
    ``memory_analysis`` says they hold."""

    USERS, ITEMS, RANK, ITERS = 500495, 624961, 10, 5
    RATINGS = 126_400_138
    HBM = 15.75 * 2**30

    @pytest.fixture(scope="class")
    def cell(self):
        """``(P, G, layout bytes)``, one entry a side, of the widths the
        rule picks for degrees re-drawn from the configuration's laws
        (``benchmarks/estimators/als_implicit.py``: a user's degree 10 + a
        lognormal share of the rest, an item's a power law, here as one
        multinomial), under the described chip's memory."""
        import json
        import os

        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.ops import als_ops
        from oap_mllib_tpu.utils import membudget

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(
                root, "benchmarks", "configs", "als_implicit_r10_kddcup11.json")) as f:
            cfg = json.load(f)
        assert (cfg["users"], cfg["items"], cfg["rows_per_chip"], cfg["rank"]) == (
            self.USERS, self.ITEMS, self.RATINGS, self.RANK)
        law, rng = cfg["data"], np.random.default_rng(39)
        w = rng.lognormal(0.0, law["degree_sigma"], self.USERS)
        rest = self.RATINGS - law["degree_min"] * self.USERS
        by_user = law["degree_min"] + np.floor(rest * w / w.sum())
        edges = (np.arange(self.ITEMS + 1) + law["item_offset"]) ** (
            1.0 - law["item_exponent"])
        by_item = rng.multinomial(self.RATINGS, np.diff(edges) / (edges[-1] - edges[0]))
        counts = [by_user.astype(np.int32)[None, :], by_item.astype(np.int32)[None, :]]
        set_config(memory_budget_hbm=str(int(self.HBM)))
        sizes = als_ops.group_sizes_for(
            counts, self.RANK,
            membudget.als_grouped_room(self.USERS, self.ITEMS, self.RANK))
        buckets = [als_ops.group_bucket(als_ops.padded_edges(c, p) // p)
                   for c, p in zip(counts, sizes)]
        plan = membudget.plan_als(
            self.RATINGS, self.USERS, self.ITEMS, self.RANK,
            grouped=list(zip(buckets, sizes)))
        assert plan.route == "in-memory" and not plan.estimates[0].reject
        return [(p, g, g * p * 12 + g * 4) for p, g in zip(sizes, buckets)]

    @staticmethod
    def _side(sharding, p, g):
        i32 = jax.ShapeDtypeStruct((g, p), jnp.int32, sharding=sharding)
        return (i32, _s((g, p), sharding), _s((g, p), sharding),
                jax.ShapeDtypeStruct((g,), jnp.int32, sharding=sharding))

    @staticmethod
    def _lane_padded(text, rows):
        """float32 arrays of the compiled program whose minor dimension is
        under 32 (so padded to the 128 lanes of a tile) over at least
        ``rows`` rows: the gather of a block's edges with the rank minor
        (``als_ops.py``'s note on the 21 GB gather) would be one.  Not a
        single column (``live_group_count``'s ``valid_g[:, 0]``): that is
        read inside its reduction and never stored."""
        import re

        found = set()
        for dims, order in re.findall(r"f32\[([\d,]+)\]\{([\d,]+)", text):
            dims = [int(d) for d in dims.split(",")]
            minor = dims[int(order.split(",")[0])]
            if 1 < minor < 32 and int(np.prod(dims)) // minor >= rows:
                found.add((tuple(dims), order))
        return found

    def _temporaries(self, p, g):
        """What a half-update holds beside its arguments: the sheet of
        group moments (544 + 1024 B a group of the bucket: the form the
        walk fills and the copy the segment-sum reads) and one block's
        lane-padded gather, its moment operands and products."""
        from oap_mllib_tpu.ops import als_ops
        from oap_mllib_tpu.utils import membudget

        block_slots = g // als_ops._grouped_block_count(g, p, self.RANK) * p
        return membudget.als_sheet_bytes(g, self.RANK), block_slots * 512

    def test_the_bucket_is_the_cells(self, cell):
        from oap_mllib_tpu.ops import als_ops

        # what the mean alone says, and the span reports as group_size_by_mean
        assert als_ops.auto_group_size(self.RATINGS, self.USERS) == 256
        assert als_ops.auto_group_size(self.RATINGS, self.ITEMS) == 256
        assert als_ops.group_bucket(822_501) == als_ops.group_bucket(927_730) == 1 << 20
        # the heavy tails' own width: half the mean's, on the next bucket,
        # so the same slots a side and the same block of 1,048,576 slots
        assert [(p, g) for p, g, _ in cell] == [(128, 1 << 21), (128, 1 << 21)]
        for p, g, _ in cell:
            blocks = als_ops._grouped_block_count(g, p, self.RANK)
            assert g % blocks == 0 and g // blocks * p == 1 << 20

    def test_moments_of_a_side(self, one_chip, cell):
        from oap_mllib_tpu.ops import als_ops

        p, g, layout = cell[1]

        def moments(src, conf, valid, group_dst, factors):
            return als_ops.normal_eq_partials_grouped(
                src, conf, valid, group_dst, factors, self.ITEMS, 40.0, True,
                "f32", als_ops.live_group_count(valid),
            )

        compiled = jax.jit(moments).lower(
            *self._side(one_chip, p, g), _s((self.USERS, self.RANK), one_chip)
        ).compile()
        text = compiled.as_text()
        blocks = als_ops._grouped_block_count(g, p, self.RANK)
        assert g % blocks == 0  # no remainder: no padded copy of a layout
        # a block's gather has the rank minor (512 MB of lanes); the whole
        # side's never
        assert self._lane_padded(text, g // blocks * p)
        assert not self._lane_padded(text, 2 * g // blocks * p)
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 1.01 * layout
        sheet, block = self._temporaries(p, g)
        assert sheet < mem.temp_size_in_bytes < sheet + 1.25 * block

    def test_solve_kernel(self, one_chip):
        from oap_mllib_tpu.ops import als_ops

        r = self.RANK
        solve_geo, _ = als_ops._tuned_geometry(r, "pallas", True)

        def solve(a, b, n_reg, gram):
            return als_ops.regularized_solve(
                a, b, n_reg, 0.1, jnp.eye(r, dtype=F32), gram, "pallas", solve_geo)

        compiled = _compile(
            solve, _s((self.ITEMS, r, r), one_chip), _s((self.ITEMS, r), one_chip),
            _s((self.ITEMS,), one_chip), _s((r, r), one_chip),
        )
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30

    @pytest.mark.parametrize("gather", ["xla", "pallas"])
    def test_the_whole_run_grouped_program(self, one_chip, cell, gather):
        from oap_mllib_tpu.ops import als_ops

        r = self.RANK
        (p_u, g_u, layout_u), (p_i, g_i, layout_i) = cell
        solve_geo, gram_geo = als_ops._tuned_geometry(r, "pallas", True)
        compiled = als_ops._als_run_grouped_jit.lower(
            *self._side(one_chip, p_u, g_u), *self._side(one_chip, p_i, g_i),
            _s((self.USERS, r), one_chip), _s((self.ITEMS, r), one_chip),
            n_users=self.USERS, n_items=self.ITEMS, max_iter=self.ITERS,
            reg=0.1, alpha=40.0, implicit=True, policy="f32",
            solve_kernel="pallas", solve_geo=solve_geo, gram_geo=gram_geo,
            gather_kernel=gather,
        ).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text  # the fused solve and the Gram walk
        for p, g, _ in cell:
            blocks = als_ops._grouped_block_count(g, p, r)
            assert not self._lane_padded(text, 2 * g // blocks * p)
        # one walk a side, and its block read by the moments as it lies
        walks = TestALSGatherWalk.calls(text)
        assert len(walks) == (2 if gather == "pallas" else 0), walks
        if gather == "pallas":
            assert not TestALSGatherWalk.relayouts(text)
        mem = compiled.memory_analysis()
        # both layouts once (no copy of one padded to its blocks) and the
        # initial factors
        assert mem.argument_size_in_bytes < 1.02 * (layout_u + layout_i)
        # the sheet of the side with more groups and one block (3.3 +
        # 0.5 GB at P = 128), never both sides' sheets
        sheet, block = max(self._temporaries(p, g) for p, g, _ in cell)
        assert sheet < mem.temp_size_in_bytes < sheet + 1.25 * block
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < self.HBM


class TestALSDstBlockedOnOneChip:
    """``als_implicit_r100_kddcup11``: the KDD-Cup'11 user block at rank
    100, on the destination-blocked route (``als_ops.als_run_dst_blocked``)
    the plan takes there: its two kernels at the cell's sizes and the
    whole five-iteration program for one described v5e, with what
    ``memory_analysis`` says it holds — never a sheet of group moments
    nor a side's ``(n_dst, r, r)`` equations."""

    USERS, ITEMS, RANK, ITERS = 500495, 624961, 100, 5
    RATINGS = 126_400_138
    HBM = 15.75 * 2**30

    @pytest.fixture(scope="class")
    def plan(self):
        """``(layouts [(P, G)], plan)``: the widths of whole lanes the
        route takes for degrees drawn from the configuration's laws (as
        ``TestALSCellOnOneChip.cell``), on their group buckets, planned
        under the described chip's memory."""
        import json
        import os

        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.ops import als_ops
        from oap_mllib_tpu.utils import membudget

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(
                root, "benchmarks", "configs", "als_implicit_r100_kddcup11.json")) as f:
            cfg = json.load(f)
        assert (cfg["users"], cfg["items"], cfg["rows_per_chip"], cfg["rank"]) == (
            self.USERS, self.ITEMS, self.RATINGS, self.RANK)
        law, rng = cfg["data"], np.random.default_rng(45)
        w = rng.lognormal(0.0, law["degree_sigma"], self.USERS)
        rest = self.RATINGS - law["degree_min"] * self.USERS
        by_user = law["degree_min"] + np.floor(rest * w / w.sum())
        edges = (np.arange(self.ITEMS + 1) + law["item_offset"]) ** (
            1.0 - law["item_exponent"])
        by_item = rng.multinomial(self.RATINGS, np.diff(edges) / (edges[-1] - edges[0]))
        counts = [by_user.astype(np.int32)[None, :], by_item.astype(np.int32)[None, :]]
        set_config(memory_budget_hbm=str(int(self.HBM)))
        sizes = als_ops.group_sizes_for(counts, self.RANK, None,
                                        least=als_ops.DST_LEAST_GROUP)
        layouts = [(p, als_ops.group_bucket(als_ops.padded_edges(c, p) // p))
                   for c, p in zip(counts, sizes)]
        plan = membudget.plan_als(self.RATINGS, self.USERS, self.ITEMS, self.RANK,
                                  grouped=[(g, p) for p, g in layouts])
        return layouts, plan

    def test_the_plan_takes_the_route(self, plan):
        from oap_mllib_tpu.utils import membudget

        layouts, plan = plan
        assert layouts == [(128, 1 << 21), (128, 1 << 21)]
        assert plan.route == membudget.ROUTE_DST_BLOCKED and plan.dst_block == 16384

    def test_the_moments_kernel(self, one_chip):
        """The kernel fetches a chunk's rows itself, one DMA a row into a
        sublane of its VMEM buffer, from the item side's table in HBM."""
        from oap_mllib_tpu.ops.pallas import als_dst

        gc, p, lanes, d = 2048, 128, als_dst.r_pad(self.RANK), 16384
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
        compiled = _compile(
            lambda rows, src, w, ldst, acc: als_dst.dst_moments(
                rows, src, w, ldst, acc, self.RANK),
            _s((self.ITEMS, lanes), one_chip), i32(gc, p),
            _s((gc, 8, p), one_chip), i32(gc), _s((d, lanes, lanes), one_chip),
        )
        assert "als_dst_moments" in compiled.as_text()
        # the block's tiles are written in place and the rows read where
        # they lie: no copy of either, no gathered chunk
        assert compiled.memory_analysis().temp_size_in_bytes < MiB

    def test_the_block_cholesky(self, one_chip):
        from oap_mllib_tpu.ops.pallas import als_dst

        r, d = self.RANK, 16384
        compiled = _compile(
            als_dst.block_cholesky, _s((r, r, d), one_chip), _s((r, d), one_chip),
            _s((1, d), one_chip),
        )
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * MiB

    def test_the_whole_program(self, one_chip, plan):
        from oap_mllib_tpu.ops import als_ops
        from oap_mllib_tpu.utils import membudget

        layouts, plan = plan
        r = self.RANK

        def side(p, g):
            i32 = jax.ShapeDtypeStruct((g, p), jnp.int32, sharding=one_chip)
            return (i32, _s((g, p), one_chip), _s((g, p), one_chip),
                    jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip))

        compiled = als_ops._als_run_dst_blocked_jit.lower(
            *side(*layouts[0]), *side(*layouts[1]),
            _s((self.USERS, r), one_chip), _s((self.ITEMS, r), one_chip),
            n_users=self.USERS, n_items=self.ITEMS, max_iter=self.ITERS,
            reg=0.1, alpha=40.0, implicit=True, policy="f32",
            dst_block=plan.dst_block,
            chunks=tuple(als_ops.dst_chunk_groups(p, r) for p, _ in layouts),
            moments="pallas", solve="pallas",
        ).compile()
        text = compiled.as_text()
        assert "als_dst_moments" in text and "als_block_cholesky" in text
        # the kernel reads the source rows itself: XLA gathers no chunk of
        # them, f32[262144,128] (2048 groups of 128 slots)
        from oap_mllib_tpu.ops.pallas.als_dst import r_pad

        gc = als_ops.dst_chunk_groups(128, r)
        assert f"f32[{gc * 128},{r_pad(r)}]" not in text
        mem = compiled.memory_analysis()
        layout_bytes = sum(g * p * 12 + g * 4 for p, g in layouts)
        assert mem.argument_size_in_bytes < 1.02 * layout_bytes + 0.5e9
        # one block's tiles and systems and the lane-dense source rows: a
        # few GB, where one side's (n_dst, r, r) equations would be 25 GB
        # and the grouped sheet 107 GB; and no chunk of gathered rows
        block = membudget.als_dst_block_bytes(plan.dst_block, r)
        assert mem.temp_size_in_bytes < 2.5 * block - als_ops.DST_CHUNK_BYTES
        assert membudget.als_sheet_bytes(1 << 21, r) > 100e9
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < self.HBM


class TestALSGatherWalk:
    """The factor-row gather of the grouped moments as the Pallas walk over
    the packed table held whole in VMEM (``ops/pallas/als_gather.py``): at
    the ALS cell's two tables (the items' 624,961 rows, which the user side
    gathers, and the users' 500,495), one block of 2^20 slots in groups of
    128, and at the rule's bound (1,048,576 rows, a 64 MiB table)."""

    R, P, SLOTS = 10, 128, 1 << 20

    @staticmethod
    def calls(text):
        """The walk's custom calls in a compiled program."""
        import re

        return re.findall(r"(%als_gather_walk[.\d]*) = \S+ custom-call\(", text)

    @staticmethod
    def relayouts(text):
        """Copies the program makes of a walk's output into another
        layout (following its bitcasts; a move to another memory space
        in the same layout is none): the moments must read the block as
        the kernel wrote it."""
        import re

        def layout(rest):
            # the first array type of an instruction's result, memory
            # space dropped
            return re.sub(r"S\(\d+\)", "", re.search(r"\{[^}]*\}", rest).group(0))

        defs = {}
        for line in text.splitlines():
            if " = " in line:
                name, rest = line.strip().replace("ROOT ", "").split(" = ", 1)
                defs[name] = rest
        names = {n for n in defs if n.startswith("%als_gather_walk")}
        found, grown = [], True
        while grown:
            grown = False
            for name, rest in defs.items():
                used = [n for n in names if re.search(re.escape(n) + r"\b", rest)]
                if not used or name in names:
                    continue
                if " bitcast(" in rest:
                    names.add(name)
                    grown = True
                elif (" copy(" in rest or " copy-start(" in rest) and any(
                        layout(rest) != layout(defs[n]) for n in used):
                    found.append(f"{name} = {rest[:160]}")
        return sorted(set(found))

    def _table(self, sharding, n_src):
        from oap_mllib_tpu.ops.pallas import als_gather

        return _s((als_gather.table_rows(n_src, self.R), 128), sharding)

    def _slots(self, sharding, p=None):
        p = p or self.P
        return jax.ShapeDtypeStruct((self.SLOTS // p, p), jnp.int32,
                                    sharding=sharding)

    @pytest.mark.parametrize("n_src", [624961, 500495])
    def test_a_block_of_the_cell(self, one_chip, n_src):
        from oap_mllib_tpu.ops.pallas import als_gather

        assert als_gather.fits(n_src, self.R)
        compiled = _compile(lambda t, s: als_gather._walk(t, s, self.R, False),
                            self._table(one_chip, n_src), self._slots(one_chip))
        # the output in the layout the moments read, (Gb, r, P), and in
        # HBM at most one more block beside it (r padded to 16 sublanes)
        # and the slots' packed rows and lane groups
        assert "f32[8192,10,128]{2,1,0" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes <= self.SLOTS * (16 + 2) * 4

    @pytest.mark.parametrize("n_src", [624961, 500495])
    def test_the_moments_read_the_walks_block_as_it_lies(self, one_chip, n_src):
        from oap_mllib_tpu.ops import als_ops

        groups = self.SLOTS // self.P

        def moments(src, conf, valid, factors):
            return als_ops.grouped_block_moments(
                src, conf, valid, factors, 40.0, True, "f32", "pallas")

        compiled = _compile(
            moments, self._slots(one_chip), _s((groups, self.P), one_chip),
            _s((groups, self.P), one_chip), _s((n_src, self.R), one_chip))
        text = compiled.as_text()
        assert len(self.calls(text)) == 1
        assert not self.relayouts(text)
        # XLA's gather from the (r, n_src) table is gone
        assert f"f32[{self.SLOTS},{self.R}]" not in text

    @pytest.mark.parametrize("p", [8, 64, 256])
    def test_the_widths_the_rule_may_choose(self, one_chip, p):
        from oap_mllib_tpu.ops.pallas import als_gather

        _compile(lambda t, s: als_gather._walk(t, s, self.R, False),
                 self._table(one_chip, 624961), self._slots(one_chip, p))

    def test_the_table_is_held_once_at_the_rules_bound(self, one_chip, monkeypatch):
        """At the bound the packed table is 64 MiB: the walk compiles under
        ``VMEM_LIMIT_BYTES``, and under the table's bytes plus 8 MiB (a
        double-buffered table would need twice), not under the table's
        bytes alone (it is resident, not streamed)."""
        from oap_mllib_tpu.ops.pallas import _tiers, als_gather

        n_src = 1 << 20
        assert als_gather.fits(n_src, self.R) and not als_gather.fits(n_src + 8, self.R)
        table = als_gather.table_bytes(n_src, self.R)
        assert table == als_gather.TABLE_BOUND_BYTES < _tiers.VMEM_LIMIT_BYTES

        def walk():
            # a function of its own a compile: the limit is read as the
            # walk is traced
            return lambda t, s: als_gather._walk(t, s, self.R, False)

        shapes = self._table(one_chip, n_src), self._slots(one_chip)
        _compile(walk(), *shapes)
        monkeypatch.setattr(als_gather, "VMEM_LIMIT_BYTES", table + 8 * MiB)
        _compile(walk(), *shapes)
        monkeypatch.setattr(als_gather, "VMEM_LIMIT_BYTES", table)
        with pytest.raises(Exception, match="(?i)vmem"):
            _compile(walk(), *shapes)
