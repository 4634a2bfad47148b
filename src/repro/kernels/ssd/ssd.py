"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

Grid = (B, H, L/Q): batch and heads are parallel; the chunk axis is the
sequential ('arbitrary') dim, carrying the (P, S) recurrent state in VMEM
scratch between chunk steps — the state NEVER visits HBM (a naive scan
lowering writes it back per step).

Per chunk (length Q), with scalar-per-head decay a = -exp(A_log):

    cum_i   = cumsum_j<=i dt_j*a                      (log decay within chunk)
    y_intra = ((C B^T) .* M .* dt) x        M_ij = exp(cum_i - cum_j), j <= i
    y_inter = C_i exp(cum_i) state_prev
    state   = exp(cum_Q) state_prev + sum_j exp(cum_Q - cum_j) dt_j B_j x_j^T

All inner products are (Q x S)(S x Q), (Q x Q)(Q x P), (S x Q)(Q x P) matmuls
— MXU work with Q = S = 128-aligned tiles.  B/C are group-shared: the
index_map routes head h to group h // (H/G), so a group's B/C tile is fetched
once per group, not per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, st_ref, *, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)       # (Q,)
    b = b_ref[0, :, 0, :].astype(jnp.float32)      # (Q, S)
    c = c_ref[0, :, 0, :].astype(jnp.float32)      # (Q, S)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))  # scalar

    q = x.shape[0]
    dta = dt * a                                   # (Q,) negative log decays
    cum = jnp.cumsum(dta)                          # (Q,)
    total = cum[-1]

    # ---- intra-chunk: masked decay-weighted attention ----
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ii >= jj
    m = jnp.where(causal, jnp.exp(cum[:, None] - cum[None, :]), 0.0)  # (Q,Q)
    g = jnp.dot(c, b.T, preferred_element_type=jnp.float32)           # (Q,Q)
    w = g * m * dt[None, :]
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)             # (Q,P)

    # ---- inter-chunk: contribution of the carried state ----
    state = st_ref[...]                                               # (P,S)
    y += jnp.exp(cum)[:, None] * jnp.dot(
        c, state.T, preferred_element_type=jnp.float32)               # (Q,P)

    # ---- state update ----
    decay_to_end = jnp.exp(total - cum) * dt                          # (Q,)
    new_state = jnp.dot(
        (x * decay_to_end[:, None]).T, b,
        preferred_element_type=jnp.float32)                           # (P,S)
    st_ref[...] = state * jnp.exp(total) + new_state

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_kernel(
    x: jax.Array,        # (B, L, H, P)
    dt: jax.Array,       # (B, L, H)  post-softplus step sizes
    a_log: jax.Array,    # (H,)
    b: jax.Array,        # (B, L, G, S)
    c: jax.Array,        # (B, L, G, S)
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bsz, L, H, Pd = x.shape
    G, S = b.shape[2], b.shape[3]
    rep = H // G
    q = min(chunk, L)
    assert L % q == 0
    nc = L // q

    return pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=(bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, q, 1, Pd), lambda bi, h, ci: (bi, ci, h, 0)),
            pl.BlockSpec((1, q, 1), lambda bi, h, ci: (bi, ci, h)),
            pl.BlockSpec((1,), lambda bi, h, ci: (h,)),
            pl.BlockSpec((1, q, 1, S), lambda bi, h, ci: (bi, ci, h // rep, 0)),
            pl.BlockSpec((1, q, 1, S), lambda bi, h, ci: (bi, ci, h // rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, 1, Pd), lambda bi, h, ci: (bi, ci, h, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, L, H, Pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((Pd, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a_log, b, c)
