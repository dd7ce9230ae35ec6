"""A/B the ALS gather levers on the real chip.

When first run on a v5e levers A-C were rejected: the
gather bound was per-index, not per-byte.  Re-run to reproduce;
protocol follows the kernel-table slope method.

Levers A-C, measured at the ML-1M attribution shape (6040x3706, nnz=1M,
r=10, P=256 grouped layout, user side):
  A. bf16 factor table for the gather (halves gathered BYTES; tests
     whether the measured gather bound is byte-bandwidth or per-index).
  B. hi/lo split bf16 gather (two bf16 gathers, f32-accurate sum; same
     bytes as f32 — only wins if per-GATHER overhead dominates, loses
     if per-index cost dominates).
  C. degree/src-sorted edge ordering ((dst, src)-lexsorted input ->
     ascending src ids within each group -> gather locality).

Lever D, at the ALS cell's shapes (``als_implicit_r10_kddcup11``: the
users' 500,495 and the items' 624,961 rank-10 rows, blocks of 8192
groups of 128 slots): the Pallas walk over the packed VMEM-resident
table (``ops/pallas/als_gather.py``) against XLA's gather — the gather
alone, the block's moments through either, and one half-update's group
moments at P = 256 / 128 / 64 over 2^27 slots — every variant cycled
three times in one process.  Prints ns a slot for each, the walk's
table fill per call, and the slot constant of the width rule's walk
route (``als_ops._WALK_SLOT_NS``) they imply.

Protocol: ONE process, interleaved variants, in-jit repeat slopes with
runtime trip counts (verify-skill gotchas 3-5); standalone gather slope
AND full-iteration slope for each lever; parity of final factors vs the
f32 fit for lever A.

    python dev/als_gather_ab.py [--levers ABCD]
"""

import argparse
import sys

import os

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from oap_mllib_tpu.ops import als_ops

NU, NI, NNZ, R = 6040, 3706, 1 << 20, 10
REG, ALPHA = 0.1, 40.0


def best_of(fn, reps=3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def slope(run, r1, r2, reps=3):
    run(r1)  # compile+warm
    t1 = best_of(lambda: run(r1), reps)
    t2 = best_of(lambda: run(r2), reps)
    return (t2 - t1) / (r2 - r1)


def lever_d(cycles=3):
    """The walk against XLA's gather at the ALS cell's shapes."""
    from oap_mllib_tpu.ops.pallas import als_gather

    rng = np.random.default_rng(1)
    r, p, groups = 10, 128, 8192
    slots = groups * p

    def looped(step, *arrays):
        """``reps`` runs of ``step(k, *arrays)`` (a float32 scalar a run)
        in one program, the trip count a runtime argument.  The arrays go
        in as arguments: closed over, they would be constants of the
        program (gigabytes of them for a half-update)."""
        @jax.jit
        def run(reps, *arrays):
            return lax.fori_loop(
                0, reps, lambda k, acc: acc + step(k, *arrays),
                jnp.float32(0),
            )

        return lambda reps: float(run(jnp.int32(reps), *arrays))

    def moved(src, k, n_src):
        # another block each run, so that nothing is hoisted out of the loop
        return (src + k) % n_src

    def first(a):
        return lax.optimization_barrier(a)[0, 0, 0]

    variants = {}
    for side, n_src in (("item", 624961), ("user", 500495)):
        f = jnp.asarray(rng.normal(size=(n_src, r)).astype(np.float32))
        table = als_gather.pack_table(f)
        src = jnp.asarray(rng.integers(0, n_src, (groups, p)).astype(np.int32))
        small = src[: groups // 8]
        conf = jnp.asarray((rng.integers(0, 11, (groups, p)) * 10).astype(np.float32))
        valid = jnp.ones((groups, p), jnp.float32)

        variants[f"{side}/gather_xla"] = (slots, looped(
            lambda k, f, s, n=n_src: first(f.T[:, moved(s, k, n)]), f, src))
        # the kernel's own (Gb, r, P) output, before the transpose that
        # the moments read as a bitcast
        variants[f"{side}/gather_walk"] = (slots, looped(
            lambda k, t, s, n=n_src: first(
                als_gather._walk(t, moved(s, k, n), r, False)), table, src))
        variants[f"{side}/gather_walk_eighth"] = (slots // 8, looped(
            lambda k, t, s, n=n_src: first(
                als_gather._walk(t, moved(s, k, n), r, False)), table, small))
        for g in ("xla", "pallas"):
            variants[f"{side}/moments_{g}"] = (slots, looped(
                lambda k, f, s, c, v, n=n_src, g=g: jnp.sum(
                    als_ops.grouped_block_moments(
                        moved(s, k, n), c, v, f, ALPHA, True, "f32", g,
                        als_gather.pack_table(f) if g == "pallas" else None,
                    )), f, src, conf, valid))

    # one half-update's group moments over 2^27 slots, the item side's
    # sources, at the widths the rule weighs
    n_src = 624961
    f = jnp.asarray(rng.normal(size=(n_src, r)).astype(np.float32))
    half_slots = 1 << 27
    for width in (256, 128, 64):
        g_count = half_slots // width
        key = jax.random.PRNGKey(width)
        src = jax.random.randint(key, (g_count, width), 0, n_src, jnp.int32)
        conf = (jax.random.randint(key, (g_count, width), 0, 11) * 10).astype(
            jnp.float32)
        valid = jnp.ones((g_count, width), jnp.float32)
        dst = jnp.arange(g_count, dtype=jnp.int32) // 4
        for g in ("xla", "pallas"):
            variants[f"half/P{width}_{g}"] = (half_slots, looped(
                lambda k, f, s, c, v, d, n=g_count, g=g: sum(
                    jnp.sum(m) for m in als_ops.normal_eq_partials_grouped(
                        moved(s, k, n_src), c, v, d, f, n // 4, ALPHA, True,
                        "f32", None, g)), f, src, conf, valid, dst))

    found = {name: [] for name in variants}
    for name, (_, run) in variants.items():
        run(1)  # compile + warm
    for cycle in range(cycles):
        for name, (n_slots, run) in variants.items():
            if name.startswith("half/"):
                s = slope(run, 1, 2, reps=2)
            else:
                s = slope(run, 2, 10)
            found[name].append(s / n_slots * 1e9)
            print(f"cycle {cycle} {name}: {s * 1e3:.3f} ms a run, "
                  f"{s / n_slots * 1e9:.4f} ns a slot", flush=True)
    med = {name: float(np.median(v)) for name, v in found.items()}
    for side in ("item", "user"):
        whole = med[f"{side}/gather_walk"] * slots
        eighth = med[f"{side}/gather_walk_eighth"] * slots / 8
        # t(slots) = fill + slots * per-slot: two block sizes give both
        per_slot = (whole - eighth) / (slots - slots // 8)
        print(f"{side}: walk {per_slot:.4f} ns a slot beyond a fill of "
              f"{(whole - per_slot * slots) / 1e3:.1f} us a call; XLA gather "
              f"{med[f'{side}/gather_xla']:.4f}; moments XLA "
              f"{med[f'{side}/moments_xla']:.4f}, walk "
              f"{med[f'{side}/moments_pallas']:.4f} ns a slot", flush=True)
    for width in (256, 128, 64):
        xla, walk = med[f"half/P{width}_xla"], med[f"half/P{width}_pallas"]
        print(f"half-update P={width}: XLA {xla:.4f}, walk {walk:.4f} ns a "
              f"slot -> walk slot constant {als_ops._SLOT_NS - (xla - walk):.3f}",
              flush=True)
    print({k: round(v, 4) for k, v in med.items()}, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--levers", default="ABCD",
                    help="A-C at ML-1M's shape, D at the ALS cell's")
    args = ap.parse_args(argv)
    if "D" in args.levers:
        lever_d()
    if set(args.levers) & set("ABC"):
        levers_abc()


def levers_abc():
    rng = np.random.default_rng(0)
    u = rng.integers(0, NU, NNZ).astype(np.int64)
    i = rng.integers(0, NI, NNZ).astype(np.int64)
    r = (rng.random(NNZ) * 4 + 1).astype(np.float32)

    # unsorted (input-order) grouped layout, user side
    by_u = als_ops.build_grouped_edges(u, i, r, NU)
    # (dst, src)-lexsorted input -> ascending src within groups
    order = np.lexsort((i, u))
    by_u_sorted = als_ops.build_grouped_edges(
        u[order], i[order], r[order], NU
    )
    src_g = jnp.asarray(by_u[0])
    src_g_sorted = jnp.asarray(by_u_sorted[0])
    G, P = by_u[0].shape
    print(f"grouped layout: G={G} P={P} padded={G*P} "
          f"({G*P/NNZ:.2f}x nnz)", flush=True)

    table = jnp.asarray((rng.normal(size=(NI, R)) * 0.1).astype(np.float32))

    # ---- standalone gather slopes -------------------------------------
    @jax.jit
    def g_f32(idx, reps):
        def body(k, acc):
            t2 = table * (1.0 + acc[0] * 0.0)
            ys = t2.T[:, idx]
            return acc + ys.sum(axis=(1, 2))
        return lax.fori_loop(0, reps, body, jnp.zeros((R,), jnp.float32))

    table_bf = table.astype(jnp.bfloat16)

    @jax.jit
    def g_bf16(idx, reps):
        def body(k, acc):
            t2 = table_bf * (1.0 + acc[0] * 0.0).astype(jnp.bfloat16)
            ys = t2.T[:, idx].astype(jnp.float32)
            return acc + ys.sum(axis=(1, 2))
        return lax.fori_loop(0, reps, body, jnp.zeros((R,), jnp.float32))

    hi = table.astype(jnp.bfloat16)
    lo = (table - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    @jax.jit
    def g_hilo(idx, reps):
        def body(k, acc):
            s = (1.0 + acc[0] * 0.0).astype(jnp.bfloat16)
            ys = (hi * s).T[:, idx].astype(jnp.float32) + \
                 (lo * s).T[:, idx].astype(jnp.float32)
            return acc + ys.sum(axis=(1, 2))
        return lax.fori_loop(0, reps, body, jnp.zeros((R,), jnp.float32))

    r1, r2 = 8, 128
    res = {}
    # interleaved rounds
    for name, fn, idx in [
        ("f32", g_f32, src_g), ("bf16", g_bf16, src_g),
        ("hilo", g_hilo, src_g), ("f32_sorted", g_f32, src_g_sorted),
        ("bf16_sorted", g_bf16, src_g_sorted),
    ]:
        s = slope(lambda reps, f=fn, ix=idx: np.asarray(f(ix, reps)), r1, r2)
        res[name] = s * 1e3
        print(f"standalone gather {name}: {s*1e3:.2f} ms", flush=True)

    # ---- full-iteration slopes ----------------------------------------
    by_i = als_ops.build_grouped_edges(i, u, r, NI)
    by_i_sorted_o = np.lexsort((u, i))
    by_i_sorted = als_ops.build_grouped_edges(
        i[by_i_sorted_o], u[by_i_sorted_o], r[by_i_sorted_o], NI
    )
    dev_u = tuple(jnp.asarray(a) for a in by_u)
    dev_i = tuple(jnp.asarray(a) for a in by_i)
    dev_us = tuple(jnp.asarray(a) for a in by_u_sorted)
    dev_is = tuple(jnp.asarray(a) for a in by_i_sorted)
    x0 = jnp.asarray((rng.normal(size=(NU, R)) * 0.1).astype(np.float32))
    y0 = jnp.asarray((rng.normal(size=(NI, R)) * 0.1).astype(np.float32))

    def run_f32(iters, du=dev_u, di=dev_i):
        return als_ops.als_run_grouped(
            *du, *di, x0, y0, NU, NI, iters, REG, ALPHA, True
        )

    # bf16-gather variant of the full loop (local copy of the kernel
    # with the table cast around the gather only — moments/solve f32)
    from functools import partial

    def moments_bf16(src_b, conf_b, valid_b, fac, alpha):
        ys = fac.astype(jnp.bfloat16).T[:, src_b].astype(jnp.float32)
        a_w = alpha * jnp.abs(conf_b) * valid_b
        pos = (conf_b > 0).astype(conf_b.dtype) * valid_b
        b_w = (1.0 + alpha * jnp.abs(conf_b)) * pos
        n_w = pos
        lhs = jnp.concatenate([ys, jnp.ones_like(conf_b)[None]], axis=0)
        rhs = jnp.concatenate([ys * a_w[None], b_w[None], n_w[None]], axis=0)
        return jnp.einsum("agp,bgp->gab", lhs, rhs,
                          precision=lax.Precision.HIGHEST)

    from oap_mllib_tpu.ops.als_ops import regularized_solve

    @partial(jax.jit, static_argnames=("iters",))
    def run_bf16(iters, du=dev_u, di=dev_i):
        eye = jnp.eye(R, dtype=jnp.float32)

        def half(grp, fac, n_dst):
            sg, cg, vg, gd = grp
            m = jax.ops.segment_sum(
                moments_bf16(sg, cg, vg, fac, ALPHA), gd,
                num_segments=n_dst, indices_are_sorted=True,
            )
            a, b, n_reg = m[:, :R, :R], m[:, :R, R], m[:, R, R + 1]
            gram = jnp.matmul(fac.T, fac, precision=lax.Precision.HIGHEST)
            return regularized_solve(a, b, n_reg, REG, eye, gram).astype(
                jnp.float32
            )

        def body(carry, _):
            x, y = carry
            x = half(du, y, NU)
            y = half(di, x, NI)
            return (x, y), None

        (x, y), _ = lax.scan(body, (x0, y0), None, length=iters)
        return x, y

    runs = {
        "iter_f32": lambda it: np.asarray(run_f32(it)[0]),
        "iter_bf16gather": lambda it: np.asarray(run_bf16(it)[0]),
        "iter_f32_srcsorted": lambda it: np.asarray(
            run_f32(it, dev_us, dev_is)[0]
        ),
    }
    # NOTE: run_f32 with static iters compiles per window; warm both
    for name, fn in runs.items():
        s = slope(fn, 4, 64)
        res[name] = s * 1e3
        print(f"full iteration {name}: {s*1e3:.2f} ms/iter", flush=True)

    # ---- parity of the bf16-gather fit --------------------------------
    xf, yf = (np.asarray(a) for a in run_f32(10))
    xb, yb = (np.asarray(a) for a in run_bf16(10))
    rel = np.abs(xb - xf) / np.maximum(np.abs(xf), 1e-6)
    print(f"bf16-gather factor parity after 10 iters: "
          f"max_rel={rel.max():.3e} p99_rel={np.percentile(rel, 99):.3e}",
          flush=True)
    # held-out-style score impact: RMS prediction delta over the edges
    pf = (xf[u] * yf[i]).sum(1)
    pb = (xb[u] * yb[i]).sum(1)
    print(f"prediction RMS delta: "
          f"{np.sqrt(np.mean((pb-pf)**2)) / np.sqrt(np.mean(pf**2)):.3e}",
          flush=True)
    print({k: round(v, 3) for k, v in res.items()})


if __name__ == "__main__":
    main()
