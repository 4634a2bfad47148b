"""Pallas TPU kernel: latch threshold-crossing solver (bisection in VMEM).

The analog circuit finds the crossing time for free (the S-R latch fires when
V_C crosses V_TH).  Digitally, each column's charge Q(t) is monotone
piecewise-linear, so `iters` bisection steps resolve t* to T / 2^iters — at
p-bit precision, iters = p + 2 suffices.

TPU blocking rationale (the hardware-codesign point): the (K x bn) current
tile and the (K,) onset vector are loaded into VMEM ONCE and reused for every
bisection iteration — arithmetic intensity scales with `iters` instead of
being memory-bound per iteration.  A naive XLA lowering of the bisection loop
would re-stream the currents from HBM each iteration (K*N*4 bytes x iters);
this kernel streams them exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(t_ref, i_ref, o_ref, *, iters: int, k_charge: float,
            t_lo: float, t_hi: float):
    t_on = t_ref[...]            # (1, K)   this batch row's onsets
    cur = i_ref[...]             # (K, bn)  current tile, VMEM-resident
    bn = cur.shape[1]

    lo = jnp.full((1, bn), t_lo, jnp.float32)
    hi = jnp.full((1, bn), t_hi, jnp.float32)

    def body(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)                             # (1, bn)
        # Q(mid) per column: sum_k I[k,n] * relu(mid[n] - t_on[k])
        dt = jnp.maximum(mid - t_on.T, 0.0)               # (K, bn)
        q = jnp.sum(cur * dt, axis=0, keepdims=True)      # (1, bn)
        too_low = q < k_charge
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    o_ref[...] = 0.5 * (lo + hi)


@functools.partial(jax.jit, static_argnames=("iters", "k_charge", "t_lo",
                                              "t_hi", "bn", "interpret"))
def crossing_kernel(
    t_on: jax.Array,        # (B, K) onset times
    currents: jax.Array,    # (K, N)
    k_charge: float,
    t_lo: float = 0.0,
    t_hi: float = 1.0,
    iters: int = 24,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, k = t_on.shape
    k2, n = currents.shape
    assert k == k2
    bn = min(bn, n)
    assert n % bn == 0

    return pl.pallas_call(
        functools.partial(_kernel, iters=iters, k_charge=float(k_charge),
                          t_lo=float(t_lo), t_hi=float(t_hi)),
        grid=(b, n // bn),
        in_specs=[
            pl.BlockSpec((1, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(t_on.astype(jnp.float32), currents.astype(jnp.float32))
