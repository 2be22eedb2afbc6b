"""Pin the loop-aware HLO analyzer against XLA's own cost_analysis on
programs where XLA is correct (no loops), and against hand-computed totals
on scanned programs (where XLA undercounts — the reason the module exists).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.hlo_analysis import analyze_hlo, collective_profile, memory_profile

L, B, D = 6, 4, 64


def _cost(compiled) -> dict:
    """XLA's own per-program cost totals (a dict on the installed jax)."""
    return compiled.cost_analysis()


def _body(x, w):
    return jnp.tanh(x @ w), None


def _scanned(x, W):
    y, _ = jax.lax.scan(_body, x, W)
    return y.sum()


def _unrolled(x, W):
    for i in range(L):
        x, _ = _body(x, W[i])
    return x.sum()


@pytest.fixture(scope="module")
def compiled_pair():
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    W = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    cs = jax.jit(_scanned).lower(x, W).compile()
    cu = jax.jit(_unrolled).lower(x, W).compile()
    return cs, cu


def test_matches_xla_on_unrolled(compiled_pair):
    _, cu = compiled_pair
    got = analyze_hlo(cu.as_text())
    want = _cost(cu)
    # dot flops must match exactly; elementwise conventions differ slightly
    dot_flops = L * 2 * B * D * D
    assert got.flops >= dot_flops
    assert abs(got.flops - float(want["flops"])) / float(want["flops"]) < 0.2
    assert (abs(got.bytes_accessed - float(want["bytes accessed"]))
            / float(want["bytes accessed"]) < 0.5)


def test_corrects_scan_undercount(compiled_pair):
    cs, cu = compiled_pair
    got_s = analyze_hlo(cs.as_text())
    xla_s = _cost(cs)
    dot_flops = L * 2 * B * D * D
    # XLA counts the body once -> ~1/L of the true dot flops
    assert float(xla_s["flops"]) < dot_flops
    # the analyzer recovers the full trip count
    assert got_s.flops >= dot_flops
    assert got_s.flops < dot_flops * 2.5


def test_collectives_multiplied_by_trip_count():
    from jax.sharding import NamedSharding, PartitionSpec as P
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("model",))

    def body(x, w):
        y = x @ w
        y = jax.lax.psum(y, "model")
        return y, None

    def f(x, W):
        return jax.lax.scan(
            jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                          out_specs=(P(), P()), check_vma=False),
            x, W)[0].sum()

    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    W = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    c = jax.jit(f).lower(x, W).compile()
    got = analyze_hlo(c.as_text())
    if got.collective_counts:  # single-device builds may elide the psum
        assert got.collective_counts.get("all-reduce", 0) == L
        assert got.collective_bytes["all-reduce"] == L * B * D * 4


def test_cost_analysis_normalizer_yields_mapping(compiled_pair):
    """Whatever container this jax version returns, the normalized view is
    a mapping with the keys the suite reads — the version-compat contract
    the (fixed) seed debt was about."""
    cs, cu = compiled_pair
    for c in (cs, cu):
        d = _cost(c)
        assert isinstance(d, dict)
        assert "flops" in d and "bytes accessed" in d


def test_parser_tolerates_degenerate_text():
    """Empty / unrecognized HLO text reports zero cost instead of crashing
    — the analyzer's own latent parser debt, pinned."""
    for text in ("", "HloModule empty\n", "garbage {{{ not hlo"):
        got = analyze_hlo(text)
        assert got.flops == 0.0
        assert got.bytes_accessed == 0.0
        assert got.collective_counts == {}
    assert memory_profile("") == []
    assert collective_profile("") == []


def test_nested_loops_multiply():
    def inner(x, w):
        return jnp.tanh(x @ w), None

    def outer(x, Ws):
        def step(c, W):
            y, _ = jax.lax.scan(inner, c, W)
            return y, None
        return jax.lax.scan(step, x, Ws)[0].sum()

    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    Ws = jax.ShapeDtypeStruct((3, L, D, D), jnp.float32)
    c = jax.jit(outer).lower(x, Ws).compile()
    got = analyze_hlo(c.as_text())
    dot_flops = 3 * L * 2 * B * D * D
    assert got.flops >= dot_flops
    assert got.flops < dot_flops * 2.5
