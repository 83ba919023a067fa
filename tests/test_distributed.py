"""Sharding rules + HLO cost analyzer unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch.hlo_analysis import analyze_hlo_text


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_logical_to_spec_drops_unknown_axes():
    mesh = _mesh()
    spec = sh.logical_to_spec(("batch", "seq", "heads"), mesh)
    assert spec == P(("data",), None, "model")


def _abstract_mesh(shape=(16, 16), names=("data", "model")):
    # spec computation works on an AbstractMesh: the production 16x16 shape
    # without needing 256 devices
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(names))


def test_fsdp_specs_sharding_first_free_dim():
    mesh = _abstract_mesh()
    with sh.use_mesh(mesh):
        shapes = {"w": jax.ShapeDtypeStruct((2048, 16, 128), jnp.float32),
                  "norm": jax.ShapeDtypeStruct((2048,), jnp.float32)}
        specs = {"w": ("embed", "heads", None), "norm": (None,)}
        out = sh.fsdp_specs(specs, shapes)
    assert out["w"][0] == "fsdp"          # embed maps to nothing -> free
    assert out["norm"] == (None,)          # 1-D params untouched


def test_resolve_preserves_tuple_rules_and_collapses_strings():
    # string rule -> bare axis; tuple rule -> tuple, even with one survivor
    assert sh._resolve("heads", ("data", "model")) == "model"
    assert sh._resolve("batch", ("data", "model")) == ("data",)
    assert sh._resolve("batch", ("pod", "data", "model")) == ("pod", "data")
    assert sh._resolve("batch", ("model",)) is None
    assert sh._resolve("unknown_axis", ("data", "model")) is None
    assert sh._resolve(None, ("data", "model")) is None


def test_sanitize_spec_non_divisible_dims():
    mesh = _abstract_mesh((4, 2), ("data", "model"))
    # dim 6 % 4 != 0 -> dropped; dim 8 % 2 == 0 -> kept
    spec = sh.sanitize_spec(P("data", "model"), (6, 8), mesh)
    assert spec == P(None, "model")
    # tuple entry: product of axis sizes (4*2=8) must divide the dim
    assert sh.sanitize_spec(P(("data", "model")), (16,), mesh) \
        == P(("data", "model"))
    assert sh.sanitize_spec(P(("data", "model")), (12,), mesh) == P(None)
    # single-survivor tuple entries (post-_resolve form) survive sanitize
    assert sh.sanitize_spec(P(("data",), None), (8, 3), mesh) == P(("data",), None)


def test_sanitize_spec_rank_mismatch():
    mesh = _abstract_mesh((4, 2), ("data", "model"))
    # spec longer than shape: trailing entries pass through untouched
    assert sh.sanitize_spec(P("data", "model"), (8,), mesh) == P("data", "model")
    # spec shorter than shape: missing dims stay unsharded
    assert sh.sanitize_spec(P("data"), (8, 6, 4), mesh) == P("data")


def test_fsdp_specs_edge_cases():
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    with sh.use_mesh(mesh):
        shapes = {
            # first dim non-divisible by fsdp=16 -> second free dim taken
            "w_odd": jax.ShapeDtypeStruct((1000, 4096), jnp.float32),
            # all dims occupied or too small -> untouched
            "w_small": jax.ShapeDtypeStruct((256, 256), jnp.float32),
            # spec is None -> treated as fully replicated, still sharded
            "w_none": jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
        }
        specs = {"w_odd": (None, None), "w_small": (None, None), "w_none": None}
        out = sh.fsdp_specs(specs, shapes)
    assert out["w_odd"] == (None, "fsdp")
    assert out["w_small"] == (None, None)
    assert out["w_none"] == ("fsdp", None)


def test_div_axis_guards_divisibility():
    mesh = _mesh()
    with sh.use_mesh(mesh):
        assert sh.div_axis("heads", 32) in ("heads", None)
        # axis size 1 -> always None
        assert sh.mesh_axis_size("heads") == 1


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert sh.shard(x, "batch", None) is x


# -- HLO analyzer --------------------------------------------------------------


def test_hlo_flops_scan_vs_unroll():
    x = jnp.ones((128, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)

    def f_scan(x, w):
        def body(c, _):
            return c @ w, None
        return jax.lax.scan(body, x, None, length=16)[0]

    def f_unroll(x, w):
        for _ in range(16):
            x = x @ w
        return x

    fs = analyze_hlo_text(jax.jit(f_scan).lower(x, w).compile().as_text())
    fu = analyze_hlo_text(jax.jit(f_unroll).lower(x, w).compile().as_text())
    want = 2 * 16 * 128**3
    assert abs(fs["flops_per_device"] - want) / want < 0.05
    assert abs(fu["flops_per_device"] - want) / want < 0.05


def test_hlo_matches_xla_on_plain_matmul():
    a = jnp.ones((256, 512), jnp.bfloat16)
    b = jnp.ones((512, 1024), jnp.bfloat16)
    comp = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    mine = analyze_hlo_text(comp.as_text())
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert abs(mine["flops_per_device"] - ca["flops"]) / ca["flops"] < 0.02


def test_hlo_nested_scan_trip_counts():
    x = jnp.ones((64, 64), jnp.float32)

    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        return jax.lax.scan(outer, x, None, length=5)[0]

    r = analyze_hlo_text(jax.jit(f).lower(x).compile().as_text())
    want = 2 * 15 * 64**3
    assert abs(r["flops_per_device"] - want) / want < 0.1


def test_cells_input_specs_have_shardings():
    from repro.configs import get_smoke_config
    from repro.launch import cells
    mesh = _mesh()
    cfg = get_smoke_config("granite-34b")
    fn, args, donate = cells.build_cell(cfg, "train_4k", mesh)
    leaves = jax.tree.leaves(args)
    assert all(hasattr(l, "sharding") and l.sharding is not None for l in leaves)
    assert donate == (0, 1)
