"""HLO analyzer: trip-count-aware totals must match ground truth on
loop-free programs and correct the known while-body undercount on scans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils import hlo_analyzer as H


def _cost(f, *args):
    comp = jax.jit(f).lower(*args).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return H.analyze(comp.as_text()), ca


def test_matmul_exact():
    x = jnp.ones((128, 64))
    w = jnp.ones((64, 32))
    tot, ca = _cost(lambda a, b: a @ b, x, w)
    assert tot.flops == 2 * 128 * 64 * 32
    np.testing.assert_allclose(tot.flops, ca.get("flops"), rtol=1e-6)


def test_scan_multiplies_trip_count():
    w = jnp.ones((64, 64))

    def scanned(x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jnp.ones((64, 64))
    tot, ca = _cost(scanned, x)
    truth = 7 * 2 * 64 ** 3
    assert tot.flops == truth
    # the raw cost_analysis undercounts (body counted once)
    assert ca.get("flops") < truth


def test_nested_scan():
    w = jnp.ones((32, 32))

    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    tot, _ = _cost(nested, jnp.ones((32, 32)))
    assert tot.flops == 15 * 2 * 32 ** 3


def test_bytes_close_to_xla_on_loop_free():
    x = jnp.ones((256, 256))
    w = jnp.ones((256, 256))

    def f(a, b):
        return jnp.tanh(a @ b) + a

    tot, ca = _cost(f, x, w)
    assert 0.5 * ca.get("bytes accessed") <= tot.bytes <= 2.0 * ca.get("bytes accessed")


def test_collectives_scaled_by_trip_count():
    hlo = """
ENTRY %main (p0: f32[64,128]) -> f32[64,128] {
  %p0 = f32[64,128]{1,0} parameter(0)
  %t = (s32[], f32[64,128]) tuple(%c, %p0)
  %w = (s32[], f32[64,128]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %out = f32[64,128]{1,0} get-tuple-element(%w), index=1
}
%body (a: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {
  %a = (s32[], f32[64,128]) parameter(0)
  %g = f32[64,128]{1,0} get-tuple-element(%a), index=1
  %ar = f32[64,128]{1,0} all-reduce(%g), to_apply=%sum
  ROOT %r = (s32[], f32[64,128]) tuple(%i, %ar)
}
%cond (a: (s32[], f32[64,128])) -> pred[] {
  %a2 = (s32[], f32[64,128]) parameter(0)
  ROOT %lt = pred[] compare(%x, %y), direction=LT
}
"""
    tot = H.analyze(hlo)
    assert tot.coll_bytes["all-reduce"] == 4 * 64 * 128 * 4


def test_collective_extraction_with_scope_and_trip():
    """collectives(): per-op records carry operand bytes, enclosing trip
    multipliers, and the name-stack metadata used to gate the per-client
    encode region collective-free."""
    hlo = """
ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ag = f32[64,16]{1,0} all-gather(%p0), dimensions={0}, metadata={op_name="jit(f)/shmap_body/all_gather"}
  %t = (s32[], f32[8,16]) tuple(%c, %p0)
  %w = (s32[], f32[8,16]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
}
%body (a: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %a = (s32[], f32[8,16]) parameter(0)
  %g = f32[8,16]{1,0} get-tuple-element(%a), index=1
  %ar = f32[8,16]{1,0} all-reduce(%g), to_apply=%sum, metadata={op_name="jit(f)/fl_client_local/bad_collective"}
  ROOT %r = (s32[], f32[8,16]) tuple(%i, %ar)
}
%cond (a: (s32[], f32[8,16])) -> pred[] {
  %a2 = (s32[], f32[8,16]) parameter(0)
  ROOT %lt = pred[] compare(%x, %y), direction=LT
}
"""
    cols = H.collectives(hlo)
    assert sorted(c.kind for c in cols) == ["all-gather", "all-reduce"]
    ag = next(c for c in cols if c.kind == "all-gather")
    ar = next(c for c in cols if c.kind == "all-reduce")
    assert ag.bytes == 8 * 16 * 4 and ag.trip == 1
    assert ar.bytes == 8 * 16 * 4 and ar.trip == 3       # while-body multiplier
    assert ar.total_bytes == 3 * 8 * 16 * 4
    assert H.collective_bytes(hlo) == ag.total_bytes + ar.total_bytes
    scoped = H.collectives_in_scope(hlo, "fl_client_local")
    assert [c.kind for c in scoped] == ["all-reduce"]
    assert H.collectives_in_scope(hlo, "nonexistent_scope") == []


# ---------------------------------------------------------------------------
# edge cases: the analyzer is fed arbitrary optimized-HLO text by the
# contract checker and benches — degenerate modules must yield zeros, not
# crashes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("text", ["", "\n\n", "// no module here",
                                  "%orphan = f32[4]{0} add(%a, %b)"])
def test_empty_or_entryless_module(text):
    """No ENTRY computation -> zero totals and empty extractions."""
    tot = H.analyze(text)
    assert tot.flops == 0.0 and tot.bytes == 0.0
    assert all(v == 0.0 for v in tot.coll_bytes.values())
    assert H.collectives(text) == []
    assert H.collective_bytes(text) == 0.0
    assert H.collectives_in_scope(text, "any") == []


def test_no_collective_module():
    """A real loop-free compiled program with zero collectives: flop/byte
    totals populate, every collective bucket stays exactly zero."""
    x = jnp.ones((64, 32))
    w = jnp.ones((32, 16))
    tot, _ = _cost(lambda a, b: jnp.tanh(a @ b), x, w)
    assert tot.flops == 2 * 64 * 32 * 16
    assert tot.bytes > 0.0
    assert all(v == 0.0 for v in tot.coll_bytes.values())
    comp = jax.jit(lambda a, b: jnp.tanh(a @ b)).lower(x, w).compile()
    assert H.collectives(comp.as_text()) == []
    assert H.collective_bytes(comp.as_text()) == 0.0


def test_nested_scopes_and_nested_trip_counts():
    """A collective inside a while-within-a-while under a nested name stack:
    trip multipliers compound (2*3=6) and every enclosing named_scope level
    matches by substring on the op_name metadata."""
    hlo = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %t = (s32[], f32[8,8]) tuple(%c, %p0)
  %w = (s32[], f32[8,8]) while(%t), condition=%ocond, body=%obody, backend_config={"known_trip_count":{"n":"2"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
%obody (a: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %a = (s32[], f32[8,8]) parameter(0)
  %t2 = (s32[], f32[8,8]) tuple(%i, %g)
  %w2 = (s32[], f32[8,8]) while(%t2), condition=%icond, body=%ibody, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %r = (s32[], f32[8,8]) tuple(%i, %g2)
}
%ibody (b: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %b = (s32[], f32[8,8]) parameter(0)
  %g3 = f32[8,8]{1,0} get-tuple-element(%b), index=1
  %ar = f32[8,8]{1,0} all-reduce(%g3), to_apply=%sum, metadata={op_name="jit(f)/outer_scope/inner_scope/all_reduce"}
  ROOT %r2 = (s32[], f32[8,8]) tuple(%j, %ar)
}
%ocond (a: (s32[], f32[8,8])) -> pred[] {
  %a2 = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] compare(%x, %y), direction=LT
}
%icond (b: (s32[], f32[8,8])) -> pred[] {
  %b2 = (s32[], f32[8,8]) parameter(0)
  ROOT %lt2 = pred[] compare(%x, %y), direction=LT
}
"""
    cols = H.collectives(hlo)
    assert len(cols) == 1                  # one op, trip-annotated
    ar = cols[0]
    assert ar.kind == "all-reduce"
    assert ar.trip == 2 * 3
    assert ar.total_bytes == 6 * 8 * 8 * 4
    # totals walk agrees with the extraction walk
    assert H.analyze(hlo).coll_bytes["all-reduce"] == ar.total_bytes
    # nested scopes both match; sibling/unknown scopes do not
    for scope in ("outer_scope", "inner_scope", "outer_scope/inner_scope"):
        assert [c.kind for c in H.collectives_in_scope(hlo, scope)] == \
            ["all-reduce"], scope
    assert H.collectives_in_scope(hlo, "other_scope") == []
