"""Trip-count-aware HLO cost parser: exact on scans, sane on grad+remat."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_parse import analyze_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_multiplied_by_trip_count():
    n, L = 64, 30
    Ws = jnp.zeros((L, n, n))
    x = jnp.zeros((n, n))

    def f(x, Ws):
        def step(c, W):
            return jnp.dot(c, W, preferred_element_type=jnp.float32), None
        return jax.lax.scan(step, x, Ws)[0]

    comp = _compile(f, x, Ws)
    out = analyze_hlo(comp.as_text())
    expect = L * 2 * n ** 3
    assert out["dot_flops"] == pytest.approx(expect, rel=0.01)
    # XLA's own analysis counts the body once — our reason for existing
    xla_cost = comp.cost_analysis()
    assert xla_cost["flops"] < expect / (L / 2)


def test_grad_of_scan_counts_fwd_plus_bwd():
    n, L = 32, 10
    Ws = jnp.zeros((L, n, n))
    x = jnp.zeros((n, n))

    def f(x, Ws):
        def step(c, W):
            return jnp.tanh(jnp.dot(c, W)), None
        return jnp.sum(jax.lax.scan(step, x, Ws)[0])

    comp = _compile(jax.grad(f), x, Ws)
    out = analyze_hlo(comp.as_text())
    fwd = L * 2 * n ** 3
    # at least the two backward dots per step (XLA may DCE/fuse the forward
    # dot when only the gradient is returned), at most fwd+bwd+remat
    assert 1.9 * fwd <= out["dot_flops"] <= 4.5 * fwd


def test_nested_scan_multiplies():
    n, L1, L2 = 16, 4, 5
    x = jnp.zeros((n, n))
    W = jnp.zeros((L1, L2, n, n))

    def f(x, W):
        def outer(c, Wi):
            def inner(ci, Wj):
                return jnp.dot(ci, Wj, preferred_element_type=jnp.float32), None
            return jax.lax.scan(inner, c, Wi)[0], None
        return jax.lax.scan(outer, x, W)[0]

    comp = _compile(f, x, W)
    out = analyze_hlo(comp.as_text())
    assert out["dot_flops"] == pytest.approx(
        L1 * L2 * 2 * n ** 3, rel=0.01
    )


def test_no_loops_plain_dot():
    a = jnp.zeros((8, 16))
    b = jnp.zeros((16, 24))
    comp = _compile(lambda a, b: a @ b, a, b)
    out = analyze_hlo(comp.as_text())
    assert out["dot_flops"] == pytest.approx(2 * 8 * 16 * 24, rel=0.01)
