"""Closed loop of whole fits: one caller, the same host table, a fresh
estimator object each time, the model's arrays fetched before the next fit
starts.  A new fit starts while the elapsed time is under ``seconds`` (the
first one always starts, so ``seconds=0`` is one fit: the warm-up); the last
one finishes, and the real elapsed time (overshoot included) is what
``fit_s`` divides by the count.
"""

import time

SEED_MODULUS = 2 ** 31 - 1  # the program's seeds are 32-bit signed


def run(adapter, cfg, traffic, x, seed, seconds):
    fits = []
    t0 = time.perf_counter()
    while True:
        i = len(fits)
        started = time.perf_counter()
        try:
            result, info = adapter.fit(cfg, x, (int(seed) + i) % SEED_MODULUS)
            error = None
        except Exception as e:  # a fit that raises is a failed request
            result, info, error = None, {}, f"{type(e).__name__}: {e}"
        fits.append({
            "index": i, "wall_s": time.perf_counter() - started,
            "result": result, "info": info, "error": error,
        })
        if time.perf_counter() - t0 >= seconds:
            break
    return {"fits": fits, "elapsed_s": time.perf_counter() - t0}
