"""Latency-robust device timing shared by bench.py and the sweep tools.

Where the dispatch+fetch round-trip is long next to one kernel call,
these helpers size iteration counts so the measured loop dominates the
round-trip, force completion with a device-side reduction fetched as a
scalar, and subtract the measured round-trip — falling back to the unsubtracted (conservative) figure when the
loop did not dominate.

The timed blocks ride :class:`..utils.timing.PhaseTimer` (its ``best``
min-tracking is exactly the best-of-trials these helpers need) instead of
a private perf_counter idiom — one copy of the timed-block convention,
and the trials land in any active ``RS_TRACE`` session for free.
"""

from __future__ import annotations

from ..utils.timing import PhaseTimer


def rt_latency():
    """Measured dispatch+fetch round-trip of a trivial op."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: jnp.sum(x))
    x = jnp.ones((8, 8), jnp.float32)
    float(tiny(x))
    t = PhaseTimer()
    for _ in range(5):
        with t.phase("rt"):
            float(tiny(x))
    return t.best["rt"]


def time_device_fn(fn, trials=2, target_s=1.5):
    """Per-call seconds of ``fn`` (a thunk returning a device array)."""
    import jax
    import jax.numpy as jnp

    reduce_ = jax.jit(lambda x: jnp.sum(x.astype(jnp.int32)))
    float(reduce_(fn()))  # warmup/compile (incl. the reduction)
    rt = rt_latency()
    t = PhaseTimer()
    with t.phase("probe"):
        float(reduce_(fn()))
    t1 = max(t.best["probe"] - rt, 1e-4)
    # Size the loop so the round-trip is noise (<5%), not the signal; the
    # cap only bounds pathological cases.
    target = max(target_s, 20.0 * rt)
    iters = max(1, min(2000, int(target / t1)))
    best = float("inf")
    prev_acc = 0.0
    for _ in range(trials):
        with t.phase("loop"):
            for _ in range(iters):
                out = fn()
            float(reduce_(out))
        # Per-trial total (acc delta), not t.best: the 4*rt subtraction
        # threshold must apply to EACH trial's raw figure — per(total) is
        # non-monotone at the threshold, so min-of-totals could pick a
        # different branch than the minimum per-trial value.
        total = t.acc["loop"] - prev_acc
        prev_acc = t.acc["loop"]
        # If the loop didn't dominate the round-trip the subtraction is
        # unreliable — report the unsubtracted (conservative) figure.
        per = (total - rt) / iters if total > 4.0 * rt else total / iters
        best = min(best, per)
    return best
