"""Weight-only quantized serving under one PrecisionPolicy.

What must hold at weight_bits < 16 (and is tested here): exp2i constructs
*exact* powers of two over its whole exponent range (the shift-only dequant
contract); pack_tensor round-trips any tensor within one grid step along any
contraction axis at 8 and 4 bits; the Pallas kernel, the dense fallback, and
the jnp oracle dequantize bit-identically — with and without the fused GRAU
epilogue; packed trees follow the policy's per-layer rules (stacked-group
leaves slice correctly under lax.scan; PAPER_MIXED stays a pure
stage/activation scheme); the serving engine packs once at construction and
keeps zero recompiles, agrees with the raw-f32 engine at int8 top-1, serves
identical tokens through kernel and dense paths, and places packed leaves
under a device mesh — including the 2x2 int4 case that exercises sharded
nibble unpacking; and the packed tree actually shrinks resident weight bytes
>= 1.8x (int8) / 3.6x (int4), matching core/hwcost.weight_cost exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.core.build import build_grau
from repro.core.folding import fold
from repro.core.hwcost import weight_cost
from repro.kernels import ops
from repro.kernels.ref import matmul_wq_ref
from repro.launch.mesh import make_serve_mesh
from repro.models import lm
from repro.quant import pot
from repro.quant import weights as wq
from repro.quant.policy import (PAPER_MIXED, PrecisionPolicy, kv_policy,
                                weight_policy)
from repro.serve.engine import EngineConfig, Request, ServeEngine

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                      reason="hypothesis not installed")

BS = 8  # page size under test


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _serve(engine, cfg, *, n=5, max_new=6, seed=0):
    r = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=r.integers(2, cfg.vocab_size,
                                             size=int(r.integers(3, 12))),
                    max_new_tokens=max_new) for i in range(n)]
    engine.run(reqs)
    return {q.rid: q.out_tokens for q in reqs}


def _grau_spec():
    return build_grau(fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8),
                      mac_range=(-30000, 30000), segments=6, num_exponents=8,
                      mode="apot", bias_mode="lsq").spec


# ---------------------------------------------------------------------------
# PoT substrate: exp2i exactness, pack_tensor round-trip
# ---------------------------------------------------------------------------

def test_exp2i_exact_over_full_exponent_range():
    """2^e must be *exact* for every exponent the planes can store — jnp.exp2
    approximates on XLA CPU (8192.0039 for exp2(13.0)), which would break the
    shift-only dequant contract.  Regression-pins the bitcast construction
    over the whole legal range, including EXP_EMPTY."""
    e = jnp.arange(-126, 127, dtype=jnp.int32)
    got = np.asarray(pot.exp2i(e), np.float64)
    want = np.ldexp(1.0, np.arange(-126, 127))
    np.testing.assert_array_equal(got, want)      # bit-exact, not allclose
    # and the jit path sees the same constants
    np.testing.assert_array_equal(np.asarray(jax.jit(pot.exp2i)(e)), want)


def _pack_roundtrip_check(w, bits, caxis):
    qw = wq.pack_tensor(w, bits, caxis)
    back = wq.dense(qw)
    assert back.shape == w.shape and back.dtype == jnp.float32
    ca = caxis if caxis < 0 else caxis - w.ndim
    # per-(tile, out-channel) grid step: |x - dq(q(x))| <= step/2 (+ one
    # clipped step at the very top, pot_exponent's documented edge)
    step = np.asarray(pot.exp2i(np.moveaxis(
        np.asarray(qw.e, np.int32), ca, -1)), np.float64)
    err = np.moveaxis(np.asarray(jnp.abs(back - w)), ca, -1)
    err = err.reshape(step.shape + (-1,)).max(-1)
    assert (err <= step * 1.5 + 1e-6).all()


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("caxis", [-1, -2, -3])
def test_pack_tensor_roundtrip_error_bound(rng, bits, caxis):
    for scale in (1e-3, 1.0, 1e3):
        w = jnp.asarray(rng.normal(size=(4, 8, 6)) * scale, jnp.float32)
        _pack_roundtrip_check(w, bits, caxis)


@needs_hypothesis
def test_pack_tensor_roundtrip_hypothesis():
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=4,
                    max_size=64),
           st.sampled_from([8, 4]), st.sampled_from([-1, -2]))
    def prop(vals, bits, caxis):
        n = len(vals) - len(vals) % 4
        if n < 4:
            return
        w = jnp.asarray(vals[:n], jnp.float32).reshape(4, -1)
        _pack_roundtrip_check(w, bits, caxis)

    prop()


def test_pack_tensor_layout_and_tiling(rng):
    w = jnp.asarray(rng.normal(size=(1024, 16)), jnp.float32)
    q8 = wq.pack_tensor(w, 8, -2)
    assert q8.q.shape == (1024, 16) and q8.q.dtype == jnp.int8
    assert q8.tile == 512 and q8.e.shape == (2, 16)   # gcd(1024, 512) tiles
    assert q8.caxis == -2 and q8.kdim == 1024
    q4 = wq.pack_tensor(w, 4, -2)
    assert q4.q.shape == (512, 16)                    # two nibbles per byte
    assert q4.e.shape == (2, 16)
    # small dims collapse to a single whole-K tile, no padding ever
    assert wq.effective_tile(48) == 48
    assert wq.pack_tensor(w[:48], 8, -2).e.shape == (1, 16)
    with pytest.raises(ValueError, match="odd"):
        wq.pack_tensor(jnp.zeros((7, 4)), 4, -2)
    with pytest.raises(ValueError, match="16-bit"):
        wq.pack_tensor(w, 16, -2)
    with pytest.raises(ValueError, match="weight_bits"):
        wq.pack_tensor(w, 5, -2)


def test_take_rows_matches_dense_rows(rng):
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    idx = jnp.asarray([[3, 31, 0], [7, 7, 12]], jnp.int32)
    for bits in (8, 4):
        qw = wq.pack_tensor(w, bits, -1)    # embed layout: caxis = d_model
        np.testing.assert_array_equal(np.asarray(wq.take_rows(qw, idx)),
                                      np.asarray(wq.dense(qw))[np.asarray(idx)])
    # raw arrays pass straight through
    np.testing.assert_array_equal(np.asarray(wq.take_rows(w, idx)),
                                  np.asarray(w)[np.asarray(idx)])
    with pytest.raises(ValueError, match="take_rows"):
        wq.take_rows(wq.pack_tensor(w, 8, -2), idx)


def test_scan_slicing_keeps_static_aux(rng):
    """Stacked-group leaves: slicing the payload/exponent children along the
    leading repeats axis (what lax.scan does) must leave the negative-caxis
    static aux valid — dense(slice) == slice(dense)."""
    w = jnp.asarray(rng.normal(size=(3, 64, 10)), jnp.float32)  # (repeats, K, out)
    for bits in (8, 4):
        qw = wq.pack_tensor(w, bits, -2)
        full = np.asarray(wq.dense(qw))

        def body(carry, leaf):
            return carry, wq.dense(leaf)

        _, scanned = jax.lax.scan(body, 0, qw)
        np.testing.assert_array_equal(np.asarray(scanned), full)


# ---------------------------------------------------------------------------
# Differential: Pallas kernel vs oracle vs dense fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m", [2, 32])          # decode- and prefill-shaped
def test_matmul_wq_kernel_matches_ref_and_dense(rng, bits, m):
    k, n = 1024, 48                              # two 512-wide k tiles
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    qw = wq.pack_tensor(w, bits, -2)
    got = ops.matmul_wq(x, qw, tiles=(8, 16), interpret=True)
    want = matmul_wq_ref(x, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # the dense fallback is the oracle's own dequant — identical by
    # construction, pinned anyway
    np.testing.assert_array_equal(np.asarray(x @ wq.dense(qw)),
                                  np.asarray(want))


@pytest.mark.parametrize("bits", [8, 4])
def test_matmul_wq_grau_epilogue_bitexact(rng, bits):
    """Fused GRAU epilogue in the weight-quantized kernel: the emitted int8
    activation bus must be bit-identical to dequant-matmul -> epilogue."""
    spec = _grau_spec()
    x = jnp.asarray(rng.normal(size=(16, 512)) * 4, jnp.float32)
    w = jnp.asarray(rng.normal(size=(512, 32)), jnp.float32)
    qw = wq.pack_tensor(w, bits, -2)
    got = ops.matmul_wq(x, qw, spec, s_in=2**-8, tiles=(8, 16),
                        interpret=True)
    want = matmul_wq_ref(x, qw, spec, s_in=2**-8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_dispatch_impls(rng):
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 12)), jnp.float32)
    qw = wq.pack_tensor(w, 8, -2)
    with wq.use_impl("dense"):
        d = wq.matmul(x, qw)
    with wq.use_impl("kernel_interpret"):
        ki = wq.matmul(x, qw)
    np.testing.assert_allclose(np.asarray(ki), np.asarray(d),
                               rtol=3e-5, atol=3e-5)
    # raw arrays never touch the kernel path
    np.testing.assert_array_equal(np.asarray(wq.matmul(x, w)),
                                  np.asarray(x @ w))
    with pytest.raises(ValueError, match="impl"):
        wq.use_impl("vector").__enter__()
    # a forced compiled kernel off the TPU fails loudly, never interprets
    if jax.default_backend() != "tpu":
        with wq.use_impl("kernel"), pytest.raises(RuntimeError, match="TPU"):
            wq.matmul(x, qw)


# ---------------------------------------------------------------------------
# Policy -> packed tree
# ---------------------------------------------------------------------------

def test_weight_policy_rules(tiny_lm):
    cfg, _ = tiny_lm
    pol = PrecisionPolicy(weight_rules=((r"group0\.l0", 4), (r"embed", 8)),
                          weight_default_bits=16)
    bits = wq.weight_bits_by_layer(cfg, pol)
    assert bits["group0.l0"] == 4 and bits["embed"] == 8
    assert pol.weights_quantized
    assert not weight_policy(16).weights_quantized
    # the paper's stage scheme stays a pure weight/activation-QAT policy:
    # serving weights (and KV) keep the raw-float default
    assert not PAPER_MIXED.weights_quantized
    assert not PAPER_MIXED.kv_quantized
    assert PAPER_MIXED.weight_bits_for("group0.l0") == 16
    with pytest.raises(ValueError, match="weight_bits"):
        PrecisionPolicy(weight_default_bits=5)


def test_pack_params_structure(tiny_lm):
    cfg, params = tiny_lm
    packed = wq.pack_params(params, cfg, weight_policy(8))
    l0 = packed["group0"]["l0"]
    for key in ("wq", "wk", "wv", "wo"):
        assert isinstance(l0["attn"][key], wq.QuantWeight)
    for key in ("w_gate", "w_up", "w_down"):
        assert isinstance(l0["mlp"][key], wq.QuantWeight)
    assert isinstance(packed["embed"], wq.QuantWeight)
    assert packed["embed"].caxis == -1          # vocab rows stay gatherable
    # norms stay float, and untouched leaves are shared, not copied
    assert l0["ln1_w"] is params["group0"]["l0"]["ln1_w"]
    assert packed["ln_f_w"] is params["ln_f_w"]
    # per-layer rule packs only the matching layer
    pol = PrecisionPolicy(weight_rules=((r"group0\.l0", 8),),
                          weight_default_bits=16)
    part = wq.pack_params(params, cfg, pol)
    assert isinstance(part["group0"]["l0"]["attn"]["wq"], wq.QuantWeight)
    assert not isinstance(part["embed"], wq.QuantWeight)


def test_validate_weight_packing_errors(tiny_lm):
    cfg, _ = tiny_lm
    odd = cfg.replace(d_ff=255)
    with pytest.raises(ValueError, match="d_ff=255 is odd"):
        wq.validate_weight_packing(odd, weight_policy(4))
    # int8 never needs evenness
    wq.validate_weight_packing(odd, weight_policy(8))
    oddd = cfg.replace(d_model=127)
    with pytest.raises(ValueError, match="d_model=127 is odd"):
        wq.validate_weight_packing(oddd, weight_policy(4))


def test_packed_forward_logits_close(tiny_lm):
    """Teacher-forced logits through the packed tree stay close to f32, and
    any int8 top-1 flip happens only at an f32 near-tie: a disagreement with
    margin wider than twice the logit error would mean quantization changed
    a *decided* token — the test-sized form of the >= 0.99 agreement gate
    (which serving_bench's weight_quant section holds as a hard floor)."""
    cfg, params = tiny_lm
    toks = jnp.asarray(np.random.default_rng(3).integers(
        2, cfg.vocab_size, size=(2, 24)), jnp.int32)
    ref, _, _ = lm.apply_lm(params, cfg, toks)
    p8 = wq.pack_params(params, cfg, weight_policy(8))
    got8, _, _ = lm.apply_lm(p8, cfg, toks)
    err = float(jnp.max(jnp.abs(got8 - ref)))
    assert err < 0.05
    agree = np.asarray(got8.argmax(-1) == ref.argmax(-1))
    assert agree.mean() >= 0.9
    top2 = np.sort(np.asarray(ref), axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    assert (margin[~agree] < 2 * err).all()     # flips are near-ties only
    p4 = wq.pack_params(params, cfg, weight_policy(4))
    got4, _, _ = lm.apply_lm(p4, cfg, toks)
    assert float(jnp.max(jnp.abs(got4 - ref))) < 0.5   # bounded, coarser


# ---------------------------------------------------------------------------
# Engine end-to-end at weight_bits < 16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_engine_zero_recompiles_and_stream(tiny_lm, bits):
    cfg, params = tiny_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=BS,
                                      weight_bits=bits))
    warm = engine.warmup()
    out = _serve(engine, cfg)
    assert engine.compile_count() == warm       # packing is construction-time
    assert all(len(v) == 6 for v in out.values())


@pytest.mark.parametrize("bits", [8, 4])
def test_engine_kernel_interpret_matches_dense(tiny_lm, bits):
    cfg, params = tiny_lm
    out = {}
    for impl in ("dense", "kernel_interpret"):
        with wq.use_impl(impl):
            engine = ServeEngine(cfg, params,
                                 EngineConfig(slots=2, max_seq=64,
                                              page_size=BS, weight_bits=bits))
            engine.warmup()
            out[impl] = _serve(engine, cfg)
    assert out["kernel_interpret"] == out["dense"]


@pytest.mark.parametrize("bits,mesh_shape", [(8, (1, 2)), (4, (2, 2))])
def test_engine_weight_quant_under_mesh(tiny_lm, bits, mesh_shape):
    """Packed leaves place natively under a (data, model) mesh and serve the
    same tokens as the unsharded engine.  The (2, 2) int4 case regression-
    pins the sharded nibble-unpack path (GSPMD may shard any internal axis;
    dense() must stay concat-free and the payload contraction axis
    replicated — see serve/sharding._wq_leaf_spec)."""
    cfg, params = tiny_lm
    out = {}
    for mesh in (None, make_serve_mesh(*mesh_shape)):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          weight_bits=bits),
                             mesh=mesh)
        engine.warmup()
        out[mesh is None] = _serve(engine, cfg)
    assert out[True] == out[False]


def test_engine_composition_wq4_kv4_grau(tiny_lm):
    """The fully shift-based decode datapath: int4 weights + int4 KV pools +
    GRAU attention epilogue in one engine — completes, zero recompiles, and
    both weight impls agree token-for-token."""
    cfg, params = tiny_lm
    from repro.nn.common import build_lm_grau
    g = build_lm_grau("identity", segments=6, num_exponents=8, mode="apot",
                      out_bits=8)
    out = {}
    for impl in ("dense", "kernel_interpret"):
        with wq.use_impl(impl):
            engine = ServeEngine(cfg, params,
                                 EngineConfig(slots=2, max_seq=64,
                                              page_size=BS, weight_bits=4,
                                              kv_bits=4, attn_grau=g))
            warm = engine.warmup()
            out[impl] = _serve(engine, cfg)
            assert engine.compile_count() == warm
    assert out["kernel_interpret"] == out["dense"]
    assert all(len(v) == 6 for v in out["dense"].values())


def test_engine_weight_bytes_shrink_and_metrics(tiny_lm):
    """The acceptance gate, engine-level: packed trees cut resident weight
    bytes >= 1.8x at int8 and >= 3.6x at int4, the metrics surface reports
    the width, and decode_cost's HLO param accounting sees the f32 -> s8
    byte shift."""
    cfg, params = tiny_lm
    wb, engines = {}, {}
    for bits in (16, 8, 4):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          weight_bits=bits if bits != 16
                                          else None))
        wb[bits] = engine.metrics()["weight_bytes"]
        engines[bits] = engine
    assert wb[16] / wb[8] >= 1.8
    assert wb[16] / wb[4] >= 3.6
    m = engines[4].metrics()
    assert m["weight_bits"] == 4 and m["weights_quantized"] is True
    m16 = engines[16].metrics()
    assert m16["weight_bits"] == 16 and m16["weights_quantized"] is False
    c4 = engines[4].decode_cost(engines[4].decode_buckets[-1])
    c16 = engines[16].decode_cost(engines[16].decode_buckets[-1])
    assert c4["weight_bytes"] == wb[4]
    assert c4["param_bytes_by_dtype"].get("s8", 0.0) > 0
    assert (c4["param_bytes_by_dtype"]["f32"]
            < c16["param_bytes_by_dtype"]["f32"])


def test_engine_precision_xor_weight_bits(tiny_lm):
    cfg, params = tiny_lm
    with pytest.raises(ValueError, match="not both"):
        ServeEngine(cfg, params,
                    EngineConfig(slots=1, max_seq=32, weight_bits=8,
                                 precision=weight_policy(8)))
    # weight_bits + kv_bits shorthands compose into one policy
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=32, page_size=BS,
                                      weight_bits=8, kv_bits=4))
    assert engine.precision.weight_default_bits == 8
    assert engine.precision.kv_default_bits == 4


def test_engine_explicit_policy_packs(tiny_lm):
    """A full PrecisionPolicy with weight rules drives packing too (the
    shorthand is just sugar)."""
    cfg, params = tiny_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=32, page_size=BS,
                                      precision=kv_policy(16).with_weights(8)))
    assert isinstance(engine.params["embed"], wq.QuantWeight)


# ---------------------------------------------------------------------------
# hwcost: weight memory accounting
# ---------------------------------------------------------------------------

def test_weight_cost_model_matches_packed_tree(tiny_lm):
    """The analytic model is exact, not approximate: per-bits totals equal
    the packed tree's payload + exponent bytes on the real model."""
    cfg, params = tiny_lm
    layers = sum(sum(1 for s in p if s.kind == "attn" and s.mlp == "dense")
                 * r for p, r in cfg.groups)
    for bits in (8, 4):
        packed = wq.pack_params(params, cfg, weight_policy(bits))
        measured = sum(
            leaf.q.nbytes + leaf.e.nbytes
            for leaf in jax.tree_util.tree_leaves(
                packed, is_leaf=lambda x: isinstance(x, wq.QuantWeight))
            if isinstance(leaf, wq.QuantWeight))
        rep = weight_cost(num_layers=layers, d_model=cfg.d_model,
                          num_heads=cfg.num_heads, kv_heads=cfg.kv_heads_phys,
                          head_dim=cfg.head_dim, d_ff=cfg.d_ff,
                          gated=cfg.gated_mlp, vocab_size=cfg.vocab_size,
                          tied=cfg.tie_embeddings, weight_bits=bits)
        assert rep.total_bytes == measured


def test_weight_cost_model_ratios():
    base = dict(num_layers=4, d_model=512, num_heads=8, kv_heads=2,
                head_dim=64, d_ff=2048, gated=True, vocab_size=32000,
                tied=True)
    r16 = weight_cost(weight_bits=16, **base)
    r8 = weight_cost(weight_bits=8, **base)
    r4 = weight_cost(weight_bits=4, **base)
    assert r16.scale_bytes == 0.0 and r8.scale_bytes > 0
    assert r8.scale_bytes == r4.scale_bytes      # exponent plane is width-free
    assert r16.total_bytes / r8.total_bytes >= 3.9   # ~4x minus scale overhead
    assert r16.total_bytes / r4.total_bytes >= 7.7
    assert r4.bytes_per_decode_step == r4.total_bytes
    with pytest.raises(ValueError, match="weight_bits"):
        weight_cost(weight_bits=5, **base)
