"""HLO analyzer correctness (trip counts, dots, collectives) + cell builder."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import analyze_hlo
from repro.roofline.analyze import roofline_terms


def test_scan_trip_count_multiplied():
    def f(x, ws):
        def body(c, w):
            return jnp.dot(c, w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    t = analyze_hlo(hlo)
    assert t.flops == pytest.approx(7 * 2 * 128**3, rel=0.01)


def test_nested_scan_trip_counts():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return jnp.dot(ci, w), None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    t = analyze_hlo(hlo)
    assert t.flops == pytest.approx(5 * 3 * 2 * 64**3, rel=0.02)


def test_plain_matmul_flops_exact():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((512, 128), jnp.bfloat16)
    hlo = jax.jit(f).lower(a, b).compile().as_text()
    t = analyze_hlo(hlo)
    assert t.flops == pytest.approx(2 * 256 * 512 * 128, rel=0.01)
    assert t.dot_bytes >= (256 * 512 + 512 * 128 + 256 * 128) * 2


def test_roofline_terms_dominance():
    r = roofline_terms(flops=197e12, bytes_accessed=0.0, collective_bytes=0.0,
                       chips=1)
    assert r["dominant"] == "compute" and r["compute_s"] == pytest.approx(1.0)
    r = roofline_terms(flops=0.0, bytes_accessed=819e9, collective_bytes=0.0,
                       chips=1)
    assert r["dominant"] == "memory" and r["memory_s"] == pytest.approx(1.0)


def test_build_cell_host_mesh_lowers():
    """The cell-builder machinery itself, exercised on the host mesh with a
    smoke config (the 512-device version is the dry-run deliverable)."""
    from repro.configs.archs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_mesh, named_shardings

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("llama3.2-3b", smoke=True)
    shape = ShapeSpec("tiny", 64, 2, "train")
    bundle = steps_lib.build_cell(cfg, shape, mesh, remat="full",
                                  q_chunk=32, kv_chunk=32, dtype=jnp.float32)
    with jax.set_mesh(mesh):
        compiled = jax.jit(bundle.fn,
                           in_shardings=named_shardings(mesh,
                                                        bundle.in_shardings),
                           donate_argnums=bundle.donate_argnums
                           ).lower(*bundle.args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # pre-0.5 returns [dict], newer dict
        cost = cost[0]
    assert cost.get("flops", 0) > 0


def test_build_cell_decode_host_mesh():
    from repro.configs.archs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_mesh, named_shardings

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("mamba2-1.3b", smoke=True)
    shape = ShapeSpec("tinydec", 128, 2, "decode")
    bundle = steps_lib.build_cell(cfg, shape, mesh, dtype=jnp.float32)
    with jax.set_mesh(mesh):
        compiled = jax.jit(bundle.fn,
                           in_shardings=named_shardings(mesh,
                                                        bundle.in_shardings),
                           donate_argnums=bundle.donate_argnums
                           ).lower(*bundle.args).compile()
    assert compiled is not None


def test_pad_heads_inert():
    """Padded-head model computes the same function as the unpadded one once
    the real weights are grafted in and the pad rows are zero (the inertness
    argument behind steps.pad_heads_for)."""
    from repro.configs.archs import get_config
    from repro.models import lm

    cfg = get_config("llama3.2-3b", smoke=True)     # 4 heads, kv 2
    key = jax.random.PRNGKey(0)
    # single-layer comparison keeps the graft simple
    cfg_1 = cfg.replace(groups=((cfg.groups[0][0], 1),))
    cfg_1p = cfg_1.replace(attn_pad=(8, 4))
    pu, _ = lm.init_lm(cfg_1, key, dtype=jnp.float32)
    pp, _ = lm.init_lm(cfg_1p, key, dtype=jnp.float32)
    pp2 = jax.tree.map(lambda x: x, pp)
    a_p = pp2["group0"]["l0"]["attn"]
    a_u = pu["group0"]["l0"]["attn"]
    a_p["wq"] = a_p["wq"].at[:, :, :4, :].set(a_u["wq"]).at[:, :, 4:, :].set(0)
    a_p["wk"] = a_p["wk"].at[:, :, :2, :].set(a_u["wk"]).at[:, :, 2:, :].set(0)
    a_p["wv"] = a_p["wv"].at[:, :, :2, :].set(a_u["wv"]).at[:, :, 2:, :].set(0)
    a_p["wo"] = a_p["wo"].at[:, :4].set(a_u["wo"]).at[:, 4:].set(0)
    for k in ("embed", "ln_f_w"):
        pp2[k] = pu[k]
    for k in ("ln1_w", "ln2_w"):
        pp2["group0"]["l0"][k] = pu["group0"]["l0"][k]
    pp2["group0"]["l0"]["mlp"] = pu["group0"]["l0"]["mlp"]

    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    lu, _, _ = lm.apply_lm(pu, cfg_1, toks, q_chunk=8, kv_chunk=8)
    lp, _, _ = lm.apply_lm(pp2, cfg_1p, toks, q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(lu), np.asarray(lp),
                               rtol=2e-5, atol=2e-5)
