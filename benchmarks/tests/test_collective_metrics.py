"""The collectives layer's readers (``collective_exposed_pct``,
``lloyd_reduce_share_pct``) and the device layer's ``shard_busy_skew_pct`` on
a hand-made four-device trace, and what ``lib/collectives.py`` takes for a
collective.  Each reader returns nothing where there is nothing to read (no
trace, one device, a program that reduces nothing: the parent of the PR that
added them).

    python -m pytest benchmarks/tests -q        (CPU)
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
from lib import collectives  # noqa: E402
from lib.trace_reduce import Trace  # noqa: E402

CELL = "kmeans_d256_k1000_host4.fit_loop"

# HLO text as the chip's ``XLA Ops`` line spells it
WHILE = "%while = (f32[1024,256]{1,0:T(8,128)S(1)}, s32[]{:T(128)}) while(%tuple), body=%body.3"
WALK = "%kmeans_accumulate_walk.5 = (f32[1024,256]{1,0:T(8,128)}) custom-call(%x), custom_call_target=\"tpu_custom_call\""
REDUCE = "%all-reduce.4 = (f32[1024,256]{1,0:T(8,128)}, f32[1,1024]{1,0:T(1,128)}) all-reduce(%walk), replica_groups=[1,4]<=[4]"
DIVIDE = "%fusion.9 = f32[1024,256]{1,0:T(8,128)} fusion(%all-reduce.4), kind=kLoop"
SHEET = "%fusion.28 = (f32[2097152]{0:T(1024)S(1)}, f32[2097152,1000]{1,0}) fusion(%p), kind=kOutput"
PERMUTE_DONE = "%collective-permute-done.2 = s32[1]{0:T(128)} collective-permute-done(%collective-permute-start.2)"


def _device(lag=0.0):
    """One fit on one device, seconds: an init sheet with a prefix exchange
    half under it, then a 2-iteration loop whose walk lags by ``lag``."""
    return [
        (1.0, 2.0, SHEET),
        (1.5, 2.5, PERMUTE_DONE),            # 0.5 s exposed after the sheet
        (4.0, 8.0 + 2 * lag, WHILE),         # encloses everything below
        (4.0, 5.5 + lag, WALK),
        (5.5 + lag, 5.9 + lag, REDUCE),      # alone on the device: exposed
        (5.9 + lag, 6.0 + lag, DIVIDE),
        (6.0 + lag, 7.5 + 2 * lag, WALK),
        (7.5 + 2 * lag, 7.9 + 2 * lag, REDUCE),
        (7.9 + 2 * lag, 8.0 + 2 * lag, DIVIDE),
    ]


SPANS = [(0.5, 3.0, "init_centers"), (3.5, 8.5, "lloyd_loop")]


def _ctx(trace):
    return harness.Context(trace=trace)


def _read(metric, trace):
    return harness._module("metrics", metric).read(_ctx(trace))


@pytest.fixture
def host4():
    ops = {f"/device:TPU:{i}": _device() for i in range(3)}
    ops["/device:TPU:3"] = _device(lag=0.25)  # the straggler, and busiest
    return Trace(ops, SPANS, window=(0.0, 10.0))


@pytest.mark.parametrize("name,verdict", [
    (REDUCE, True), (PERMUTE_DONE, True),
    ("%all-gather-start.1 = (f32[8]{0}, f32[32]{0}) all-gather-start(%p)", True),
    ("%psum.3 = f32[4000,256]{1,0} all-reduce(%fusion), to_apply=%add", True),
    ("%reduce-scatter.7 = f32[256]{0} reduce-scatter(%p)", True),
    ("all-reduce.5", True),
    (DIVIDE, False),   # names a collective as its OPERAND only
    (WHILE, False), (WALK, False), (SHEET, False), ("fusion.7", False),
    ("%all-reduce-like-name = f32[8]{0} fusion(%p)", False),
])
def test_what_counts_as_a_collective(name, verdict):
    assert collectives.is_collective(name) is verdict


def test_leaves_drop_what_encloses():
    names = [n for _, _, n in collectives.leaves(_device())]
    assert WHILE not in names
    assert names.count(WALK) == 2 and names.count(REDUCE) == 2
    # two events over the very same interval: the later one counts as inside
    assert len(collectives.leaves([(0, 1, "a"), (0, 1, "b")])) == 1


def test_collective_exposed_pct(host4):
    # busiest = the straggler: 0.5 s of the prefix exchange after the sheet
    # + two all-reduces of 0.4 s with nothing beside them, of 10 s
    assert _read("collective_exposed_pct", host4) == pytest.approx(13.0)
    # with the enclosing %while counted as compute the loop's reductions hide
    whole = host4.exposed_s(host4.busiest(), collectives.is_collective)
    assert whole == pytest.approx(0.5)


def test_lloyd_reduce_share_pct(host4):
    # inside lloyd_loop the straggler is busy 4.0..8.5 = 4.5 s, 0.8 s of it
    # in all-reduce; the init's exchange is outside the annotation
    assert _read("lloyd_reduce_share_pct", host4) == pytest.approx(100 * 0.8 / 4.5)


def test_shard_busy_skew_pct(host4):
    # compute (collectives taken out): sheet 1.0 + 2 x (walk 1.5 + divide
    # 0.1) = 4.2 s on three, + 2 x 0.25 s of walk on the straggler
    assert _read("shard_busy_skew_pct", host4) == pytest.approx(100 * 0.5 / 4.7)


def _absorbed(lag):
    """One fit on a device whose peers' walk takes ``lag`` longer: the loop
    ends when theirs does, and the difference is spent in the all-reduce."""
    return [
        (1.0, 2.0, SHEET),
        (4.0, 8.0 + 2 * lag, WHILE),
        (4.0, 5.5, WALK),
        (5.5, 5.9 + lag, REDUCE),            # waits for the straggler
        (5.9 + lag, 6.0 + lag, DIVIDE),
        (6.0 + lag, 7.5 + lag, WALK),
        (7.5 + lag, 7.9 + 2 * lag, REDUCE),
        (7.9 + 2 * lag, 8.0 + 2 * lag, DIVIDE),
    ]


def test_shard_busy_skew_sees_a_straggler_the_all_reduce_absorbs():
    """What a synchronous step looks like on the chip: every device is
    busy for the same time, the fast ones inside their all-reduce."""
    ops = {f"/device:TPU:{i}": _absorbed(0.25) for i in range(3)}
    ops["/device:TPU:3"] = [e for e in _device(lag=0.25) if e[2] != PERMUTE_DONE]
    tr = Trace(ops, SPANS, window=(0.0, 10.0))
    busy = [tr.busy_s(d) for d in tr.devices]
    assert max(busy) == pytest.approx(min(busy))  # whole busy time sees nothing
    # compute: 1.0 + 2 x 1.6 = 4.2 s on three, 4.7 s on the straggler
    assert _read("shard_busy_skew_pct", tr) == pytest.approx(100 * 0.5 / 4.7)
    # and the others' all-reduces grew by what the straggler's walk did
    fast, slow = (collectives.collective_intervals(tr, d)
                  for d in ("/device:TPU:0", "/device:TPU:3"))
    assert sum(b - a for a, b in fast) - sum(b - a for a, b in slow) == pytest.approx(0.5)


@pytest.mark.parametrize(
    "metric",
    ["collective_exposed_pct", "lloyd_reduce_share_pct", "shard_busy_skew_pct"],
)
def test_readers_find_nothing_where_there_is_nothing(metric):
    assert _read(metric, None) is None  # no trace: a --trace 0 run, a CPU
    one_chip = Trace(
        {"/device:TPU:0": [(4.0, 8.0, WHILE), (4.0, 5.5, WALK), (5.9, 6.0, DIVIDE)]},
        SPANS, window=(0.0, 10.0),
    )
    assert _read(metric, one_chip) is None  # reduces nothing; no other shard


def test_benchmark_json_names_the_readers():
    """The three metrics are the new cell's alone, and the cell is what the
    issue named.  The K-Means metrics that list their cells still end at
    the one-chip cell (``test_subspan_metrics.py`` holds them to it): the
    four-chip cell's readings of those five are in PERF.md, and a
    ``benchmark`` PR extends the lists."""
    bench = harness._load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric, layer in [("collective_exposed_pct", "collectives"),
                          ("lloyd_reduce_share_pct", "collectives"),
                          ("shard_busy_skew_pct", "device")]:
        e = entries[metric]
        assert (e["source"], e["layer"], e["moves"]) == ("device_trace", layer, "fit_s")
        assert e["workloads"] == [CELL] and e["better"] == "lower"
        assert e["unit"] == "%"
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["chips"], cell["traffic"]) == (4, "fit_loop")
    assert cell["config"] == "kmeans_d256_k1000_host4"
    # the unlisted metrics read the new cell as they are
    for metric in ("table_convert_s", "host_copy_s", "upload_s",
                   "estimator_other_s", "window_compiles", "device_idle_pct",
                   "peak_hbm_gb", "fit_mfu_pct"):
        assert "workloads" not in entries[metric]


def test_the_host_configuration_keeps_the_siblings_widths():
    host = harness._load_json(BENCH, "configs", "kmeans_d256_k1000_host4.json")
    one = harness._load_json(BENCH, "configs", "kmeans_d256_k1000.json")
    same = ["d", "k", "dtype", "matmul_precision", "init_mode", "init_steps",
            "max_iter", "tol", "rows_per_chip", "data", "program_config",
            "expect_kernel", "phases", "rehearse", "control_faults", "estimator"]
    assert {k: host[k] for k in same} == {k: one[k] for k in same}
    assert host["reduced"] == ["rows_per_chip"]
    assert set(host["limits"]) == set(one["limits"])
