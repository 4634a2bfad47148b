"""Shared paper-figure utilities: wall-clock timing + CSV row emission."""
from __future__ import annotations

import time
from typing import Callable

import jax


def time_call(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median-of-``iters`` wall time per call in microseconds (post-jit).

    Every sample is ``block_until_ready``-fenced (async dispatch would
    otherwise time the enqueue, not the compute), and warmup runs absorb
    compilation and first-touch allocation."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us_per_call: float, derived: str) -> None:
    """Print one ``name,us_per_call,derived`` CSV row."""
    print(f"{name},{us_per_call:.1f},{derived}")
