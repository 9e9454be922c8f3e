"""HLO analyzers: exact dot-FLOP counting through nested while loops, and
the collective parser's wire-byte model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import collectives, hlo_analysis


def test_flops_exact_through_scan():
    L, B, D = 7, 8, 64

    def loss(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        x, _ = jax.lax.scan(body, x, w)
        return (x ** 2).sum()

    def step(x, w):
        return jax.value_and_grad(loss, argnums=1)(x, w)

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    comp = jax.jit(step).lower(xs, ws).compile()
    res = hlo_analysis.analyze(comp.as_text())
    true = 3 * L * 2 * B * D * D            # fwd + dx + dw
    assert res["flops"] == pytest.approx(true, rel=0.02)


def test_nested_scan_multiplies():
    L_out, L_in, D = 3, 5, 32

    def f(x, w):
        def outer(c, wo):
            def inner(ci, _):
                return ci @ wo, None
            c, _ = jax.lax.scan(inner, c, None, length=L_in)
            return c, None
        x, _ = jax.lax.scan(outer, x, w)
        return x.sum()

    xs = jax.ShapeDtypeStruct((4, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L_out, D, D), jnp.float32)
    comp = jax.jit(f).lower(xs, ws).compile()
    res = hlo_analysis.analyze(comp.as_text())
    true = L_out * L_in * 2 * 4 * D * D
    assert res["flops"] == pytest.approx(true, rel=0.02)


def test_collective_wire_bytes_model():
    txt = """
HloModule test, entry_computation_layout={()->f32[]}

ENTRY %main (p: f32[8,16]) -> f32[] {
  %p = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%p), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  %ag = f32[32,16]{1,0} all-gather(%ar), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %r = f32[] reduce(%ag)
}
"""
    out = collectives.parse_collectives(txt, 8)
    ar_bytes = 8 * 16 * 4
    ag_bytes = 32 * 16 * 4
    expected = 2 * (3 / 4) * ar_bytes + (3 / 4) * ag_bytes
    assert out["total_wire_bytes"] == pytest.approx(expected)
    assert out["n_collectives"] == 2


def test_collectives_inside_while_multiplied():
    import re

    def f(x):
        def body(c, _):
            return c * jax.lax.psum(c.sum(), "i"), None
        c, _ = jax.lax.scan(body, x, None, length=6)
        return c.sum()

    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # shard_map over 1 device still emits the collective structure
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs[:1]), ("i",))
    fm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("i"), out_specs=P(),
                               check_vma=False))
    comp = fm.lower(jax.ShapeDtypeStruct((4, 8), jnp.float32)).compile()
    out = collectives.parse_collectives(comp.as_text(), 1)
    # the in-loop psum must appear with count 6 (or be optimised out on 1
    # device — accept either, but if present it must carry the multiplier)
    counts = [c[3] for c in out["items"]]
    if counts:
        assert max(counts) >= 6
