"""Launch-layer cell construction + HLO analyzer unit tests (host mesh)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, smoke_config
from repro.distributed.sharding import make_rules, sharding_ctx, spec_for
from repro.launch.hlo_analysis import analyze_hlo_text, parse_hlo
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import _batch_rule_for, build_cell


# --------------------------------------------------------------- build_cell
@pytest.mark.parametrize("shape_name,kind", [
    ("train_4k", "train"), ("prefill_32k", "prefill"), ("decode_32k", "decode"),
])
def test_build_cell_structure(shape_name, kind):
    """Cells assemble abstract args + shardings without allocating; the host
    mesh (1 device) stands in for the production mesh in tests."""
    cfg = get_config("llama3.2-1b")
    mesh = make_host_mesh()
    cell = build_cell(cfg, SHAPES[shape_name], mesh)
    assert cell.kind == kind
    assert len(cell.args) == len(cell.in_shardings)
    for leaf in jax.tree.leaves(cell.args):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_build_cell_lowers_on_host_mesh():
    """A reduced config actually lowers+compiles through the cell machinery."""
    cfg = smoke_config("llama3.2-1b")
    from repro.configs.base import ShapeConfig

    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=2, kind="train")
    mesh = make_host_mesh()
    cell = build_cell(cfg, shape, mesh)
    with mesh, sharding_ctx(mesh, cell.rules):
        compiled = jax.jit(cell.step, in_shardings=cell.in_shardings,
                           donate_argnums=cell.donate_argnums
                           ).lower(*cell.args).compile()
    assert compiled.cost_analysis() is not None


def test_batch_rule_fallback():
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    assert _batch_rule_for(256, FakeMesh()) == ("pod", "data")
    assert _batch_rule_for(16, FakeMesh()) == ("data",)  # 16 % 32 != 0
    assert _batch_rule_for(1, FakeMesh()) is None        # replicated


def test_serving_2d_rules():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cfg = get_config("llama4-maverick-400b-a17b").with_updates(serve_2d_ffn=True)
    r_train = make_rules(cfg, FakeMesh(), serving=False)
    r_serve = make_rules(cfg, FakeMesh(), serving=True)
    assert r_train["expert_mlp"] is None          # experts own "model"
    assert r_serve["expert_mlp"] == ("data",)     # 2-D: expert-FF over data
    assert r_serve["mlp"] == ("model", "data")


# ------------------------------------------------------------- HLO analyzer
SYNTH_HLO = """
HloModule synth

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups=[16,16]<=[256], to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%z, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_analyzer_trip_count_multiplication():
    r = analyze_hlo_text(SYNTH_HLO)
    # one 8x8x8 dot per trip, 4 trips: 2*8*8*8*4 = 4096 FLOPs
    assert r["dot_flops"] == pytest.approx(4096)
    # all-reduce of 256B per trip over group size 16: 2*(15/16)*256*4 trips
    assert r["collective_link_bytes"] == pytest.approx(2 * 15 / 16 * 256 * 4)


def test_analyzer_parses_tuple_types_and_comments():
    txt = SYNTH_HLO.replace("%t0 = (s32[], f32[8,8]{1,0}) tuple",
                            "%t0 = (s32[], /*index=5*/f32[8,8]{1,0}) tuple")
    comps = parse_hlo(txt)
    assert comps["__entry_name__"] is not None
    names = {i.opcode for i in comps["__entry__"]}
    assert "while" in names


# ------------------------------------------------------------ cp attention
def test_cp_attention_matches_plain(rng):
    import jax.numpy as jnp

    from repro.modeling.attention import chunked_attention, cp_chunked_attention

    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    for window in (0, 24):
        a = chunked_attention(q, k, v, causal=True, window=window, q_chunk=16)
        b = cp_chunked_attention(q, k, v, causal=True, window=window,
                                 q_chunk=16, ways=4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


def test_cp_attention_grad_matches(rng):
    import jax.numpy as jnp

    from repro.modeling.attention import chunked_attention, cp_chunked_attention

    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    g1 = jax.grad(lambda q: chunked_attention(q, k, v, q_chunk=8).sum())(q)
    g2 = jax.grad(lambda q: cp_chunked_attention(q, k, v, q_chunk=8,
                                                 ways=2).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-5)


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table for the device a cell was compiled for;
    a device without published peaks is an error, never a default."""
    roofline = pytest.importorskip("benchmarks.roofline")
    v5e = "TPU v5 lite"  # jax's device_kind of a v5e chip

    assert roofline.peaks(v5e)["flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
    cell = {"hlo": {"flops": 197e12, "hbm_bytes": 0.0,
                    "collective_link_bytes": 0.0},
            "kind": "train", "devices": 1, "param_count": 1,
            "shape": "train_4k", "arch": "a", "mesh": "pod"}
    with pytest.raises(ValueError):
        roofline.analyze_cell(cell)
    out = roofline.analyze_cell({**cell, "device_kind": v5e})
    assert out["dominant"] == "compute" and out["compute_s"] == 1.0
