"""Property tests for the paper's core claims (Eq. 1-7, sections 2-3).

The event-driven crossing simulator must reproduce the closed form EXACTLY
(the paper's central identity) for every quadrant variant, every weight/input
draw, and chained layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import currents as cur
from repro.core import encoding as enc
from repro.core import tdcore
from repro.core.constants import TDVMMSpec


@pytest.fixture(autouse=True, scope="module")
def _x64():
    # The closed-form identities are checked to 1e-9..1e-12, which needs
    # f64.  Scoped to this module: set at import, the flag would leak into
    # every other module an xdist worker imports.
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


SPEC = TDVMMSpec(bits=8)


def _rand(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float64, lo, hi)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 48), st.integers(1, 16))
def test_single_quadrant_matches_closed_form(seed, n_in, n_out):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k1, (n_in,), 0.0, 1.0)
    w = _rand(k2, (n_in, n_out), 0.0, 1.0)
    y_sim = tdcore.td_vmm_single_quadrant(x, w, SPEC)
    y_ref = tdcore.ideal_single_quadrant(x, w, SPEC.w_max)
    np.testing.assert_allclose(np.asarray(y_sim), np.asarray(y_ref),
                               rtol=1e-9, atol=1e-12)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 32), st.integers(1, 8))
def test_four_quadrant_matches_closed_form(seed, n_in, n_out):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k1, (n_in,), -1.0, 1.0)
    w = _rand(k2, (n_in, n_out), -1.0, 1.0)
    y_sim = tdcore.td_vmm_four_quadrant(x, w, SPEC)
    y_ref = tdcore.ideal_four_quadrant(x, w, SPEC.w_max)
    np.testing.assert_allclose(np.asarray(y_sim), np.asarray(y_ref),
                               rtol=1e-9, atol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_mlp_chain_in_time_domain(seed):
    """Fig. 2: two VMMs + ReLU (AND gate) chained purely via crossing times."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = _rand(k1, (10,), -1.0, 1.0)
    w1 = _rand(k2, (10, 10), -1.0, 1.0)
    w2 = _rand(k3, (10, 10), -1.0, 1.0)
    y_sim = tdcore.td_mlp_forward(x, w1, w2, SPEC)
    y_ref = tdcore.ideal_mlp(x, w1, w2, SPEC.w_max)
    np.testing.assert_allclose(np.asarray(y_sim), np.asarray(y_ref),
                               rtol=1e-9, atol=1e-12)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 64))
def test_current_programming_invariants(seed, n):
    """Eq. 6-7: 0 <= I_i <= I_max and I_0 >= 0 for any weights in range."""
    w = _rand(jax.random.PRNGKey(seed), (n, 4), 0.0, 1.0)
    i_mat, bias = cur.program_matrix(w, SPEC.i_max, SPEC.w_max)
    assert float(jnp.min(i_mat)) >= 0.0
    assert float(jnp.max(i_mat)) <= SPEC.i_max * (1 + 1e-9)
    assert float(jnp.min(bias)) >= -1e-18


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 32))
def test_output_window_bounds(seed, n):
    """Section 2.2: outputs always land inside [T, 2T] regardless of weights."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k1, (n,), -1.0, 1.0)
    w = _rand(k2, (n, 3), -1.0, 1.0)
    _, (tp, tm) = tdcore.td_vmm_four_quadrant(x, w, SPEC, return_times=True)
    t = SPEC.t_window_s
    for tt in (tp, tm):
        assert float(jnp.min(tt)) >= t - 1e-12
        assert float(jnp.max(tt)) <= 2 * t + 1e-12


def test_relu_and_gate_semantics():
    """AND-gate pulse duration == relu of the differential output."""
    t = 1.0
    tp = jnp.array([1.2, 1.7, 1.5])
    tm = jnp.array([1.5, 1.4, 1.5])
    d = tdcore.relu_duration(tp, tm)
    np.testing.assert_allclose(np.asarray(d), [0.3, 0.0, 0.0], atol=1e-12)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(4, 10))
def test_quantization_roundtrip(seed, bits):
    x = _rand(jax.random.PRNGKey(seed), (64,), 0.0, 1.0)
    q = enc.fake_quant(x, bits)
    assert float(jnp.max(jnp.abs(q - x))) <= 0.5 / ((1 << bits) - 1) + 1e-9
    codes = enc.quantize_code(x, bits)
    assert int(jnp.min(codes)) >= 0 and int(jnp.max(codes)) <= (1 << bits) - 1


def test_pulse_duration_equivalence():
    """Section 3.1: duration encoding injects the same charge as rising-edge."""
    t = SPEC.t_window_s
    x = jnp.array([0.3, 0.8, 0.0, 1.0])
    onset_charge_time = t - enc.value_to_onset(x, t)   # time the source is ON in [0,T]
    dur = enc.value_to_duration(x, t)
    np.testing.assert_allclose(np.asarray(onset_charge_time), np.asarray(dur))


def test_pipeline_schedule():
    s = tdcore.pipeline_schedule(n_stages=2, n_samples=100, spec=TDVMMSpec(bits=6))
    assert s["period_s"] == pytest.approx(2 * SPEC.t0_s * 64 + 2e-9)
    assert s["total_s"] > 99 * s["period_s"]
    assert s["throughput_samples_per_s"] == pytest.approx(1.0 / s["period_s"])
