"""Sharded-vs-single-device serving equivalence, device-count parametrized.

conftest.py forces 4 host CPU devices (XLA_FLAGS) before jax initializes, so
every test here builds real multi-device meshes — (1,2), (1,4), (2,2), (4,1)
— from explicit device subsets of one process and checks that sharding is
purely a placement decision:

  * model-level: prefill/decode logits match the single-device run,
  * engine-level: identical generated tokens AND bit-identical-within-
    tolerance paged KV pool contents after mixed submit/poll traffic,
  * sampling: per-slot heterogeneous sampler state partitions without
    changing any drawn token,
  * kernels: the GRAU datapath is bit-identical on every forced device.

Tests skip (rather than fail) when the process has fewer devices than a
mesh needs, so the suite stays green under any forced device count >= 1 —
CI runs it at 4 and 8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.core.build import build_grau
from repro.core.folding import fold
from repro.kernels import ops
from repro.kernels.ref import grau_ref
from repro.launch.mesh import make_serve_mesh, parse_mesh_spec
from repro.models import lm
from repro.serve import sharding as shard_lib
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.sampling import SamplingParams

CFG = get_config("llama3.2-3b", smoke=True)
SLOTS, MAX_SEQ = 4, 64


def _mesh_or_skip(data: int, model: int):
    if jax.device_count() < data * model:
        pytest.skip(f"needs {data * model} devices, "
                    f"have {jax.device_count()}")
    return make_serve_mesh(data, model)


@pytest.fixture(scope="module")
def params():
    p, _ = lm.init_lm(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------

def test_parse_mesh_spec():
    assert parse_mesh_spec("4") == (1, 4)
    assert parse_mesh_spec("2x2") == (2, 2)
    assert parse_mesh_spec(" 4X1 ") == (4, 1)
    for bad in ("", "0", "2x0", "axb", "1x2x3"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


def test_make_serve_mesh_shapes():
    mesh = _mesh_or_skip(2, 2)
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (2, 2)
    with pytest.raises(ValueError):
        make_serve_mesh(1, 2, devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# Kernels across devices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nd", [1, 2, 4])
def test_grau_kernel_bit_identical_on_every_device(nd, rng):
    """The executable RTL spec must not depend on which device runs it."""
    if jax.device_count() < nd:
        pytest.skip(f"needs {nd} devices")
    folded = fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8)
    spec = build_grau(folded, mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    x = rng.integers(-70000, 70000, size=(64, 200))
    want = np.asarray(grau_ref(jnp.asarray(x, jnp.int32), spec))
    for dev in jax.devices()[:nd]:
        xd = jax.device_put(jnp.asarray(x, jnp.int32), dev)
        np.testing.assert_array_equal(
            np.asarray(ops.grau(xd, spec, interpret=True)), want)


# ---------------------------------------------------------------------------
# Model-level logits equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("data,model", [(1, 2), (2, 2), (4, 1)])
def test_sharded_prefill_decode_logits_match(data, model, params):
    mesh = _mesh_or_skip(data, model)
    b, ctx = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, ctx), 2,
                              CFG.vocab_size)
    tl = jnp.full((b,), ctx, jnp.int32)

    def prefill(p, t, c):
        return lm.prefill_step(p, CFG, t, c, true_length=tl,
                               q_chunk=8, kv_chunk=8)

    def decode(p, t, c):
        return lm.decode_step(p, CFG, t, c)

    caches = lm.init_caches(CFG, b, MAX_SEQ, dtype=jnp.float32)
    base_last, base_caches = jax.jit(prefill)(params, toks, caches)
    next_tok = jnp.argmax(base_last, axis=-1).astype(jnp.int32)[:, None]
    base_dec, _ = jax.jit(decode)(params, next_tok, base_caches)

    sp = shard_lib.place_params(params, CFG, mesh)
    scaches = shard_lib.place_dense_caches(
        lm.init_caches(CFG, b, MAX_SEQ, dtype=jnp.float32), CFG, mesh, b)
    sh_last, sh_caches = jax.jit(
        shard_lib.with_shard_ctx(prefill, mesh, CFG))(sp, toks, scaches)
    sh_dec, _ = jax.jit(
        shard_lib.with_shard_ctx(decode, mesh, CFG))(sp, next_tok, sh_caches)

    np.testing.assert_allclose(np.asarray(sh_last), np.asarray(base_last),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sh_dec), np.asarray(base_dec),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Engine-level equivalence under mixed submit/poll traffic
# ---------------------------------------------------------------------------

def _requests(sampling_for=None):
    rng = np.random.default_rng(42)
    reqs = []
    for i, n in enumerate((5, 9, 3, 14, 7, 11)):
        sampling = (sampling_for(i) if sampling_for is not None
                    else SamplingParams())
        reqs.append(Request(rid=i,
                            prompt=rng.integers(2, CFG.vocab_size, size=n),
                            max_new_tokens=4 + (i % 3), sampling=sampling))
    return reqs


def _mixed_traffic(engine, reqs):
    """Staggered submits interleaved with steps and polls (not a single
    run(): admissions must land mid-flight for the block pool to churn)."""
    pending = list(reqs)
    schedule = {0: 2, 2: 2, 4: len(reqs) - 4}    # tick -> #submissions
    finished, tick = [], 0
    while (pending or engine.scheduler.waiting
           or any(s is not None for s in engine.slot_req)):
        for _ in range(schedule.get(tick, 0)):
            engine.submit(pending.pop(0))
        engine.step()
        finished.extend(engine.poll())
        tick += 1
        assert tick < 500, "traffic did not drain"
    return {r.rid: tuple(r.out_tokens) for r in finished}


def _assert_cache_trees_match(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("data,model,paged", [(1, 4, True), (2, 2, True),
                                              (2, 2, False)])
def test_engine_sharded_matches_single_device(data, model, paged, params):
    mesh = _mesh_or_skip(data, model)
    ecfg = EngineConfig(slots=SLOTS, max_seq=MAX_SEQ, paged=paged)
    base = ServeEngine(CFG, params, ecfg)
    base_toks = _mixed_traffic(base, _requests())

    eng = ServeEngine(CFG, params, ecfg, mesh=mesh)
    sh_toks = _mixed_traffic(eng, _requests())

    assert sh_toks == base_toks
    # same traffic => same block allocations => the *pool contents* (or the
    # dense buffers) must agree, including writes routed to the null block
    _assert_cache_trees_match(base.caches, eng.caches)
    if paged:
        assert np.array_equal(base.block_table, eng.block_table)
        assert base.allocator.free_blocks == eng.allocator.free_blocks


def test_engine_sharded_sampling_state_partitions(params):
    """Per-slot heterogeneous sampler params (greedy next to top-k next to
    top-p) must survive partitioning bit-for-bit: same PRNG fold, same
    drawn tokens."""
    mesh = _mesh_or_skip(1, 4)

    def sampling_for(i):
        return [SamplingParams(),                                  # greedy
                SamplingParams(temperature=0.7, top_k=20),
                SamplingParams(temperature=1.1, top_p=0.9)][i % 3]

    ecfg = EngineConfig(slots=SLOTS, max_seq=MAX_SEQ, seed=3)
    base_toks = _mixed_traffic(ServeEngine(CFG, params, ecfg),
                               _requests(sampling_for))
    sh_toks = _mixed_traffic(ServeEngine(CFG, params, ecfg, mesh=mesh),
                             _requests(sampling_for))
    assert sh_toks == base_toks


@pytest.mark.parametrize("data,model", [(2, 2), (1, 4), (4, 1)])
def test_engine_sharded_never_recompiles_after_warmup(data, model, params):
    """The static-shape serving invariant must hold under a mesh too: after
    warmup() traces every decode/prefill bucket, donated caches and slot
    state cycling through two full traffic waves add zero jit signatures.
    warmup() traces one signature per program and bucket, as on one device:
    a second placement of the donated trees (construction vs program
    output) would show up here as extra warm traces."""
    mesh = _mesh_or_skip(data, model)
    ecfg = EngineConfig(slots=SLOTS, max_seq=MAX_SEQ)
    eng = ServeEngine(CFG, params, ecfg, mesh=mesh)
    warm = eng.warmup()
    # 3 decode buckets + 2 chunk-table widths + cow_copy + scrub_block
    assert warm == ServeEngine(CFG, params, ecfg).warmup() == 7
    _mixed_traffic(eng, _requests())
    _mixed_traffic(eng, _requests())
    assert eng.compile_count() == warm


def test_kernel_engine_matches_sharded_gather_engine(params):
    """Cross-impl differential under the CI mesh matrix: a single-device
    engine on the Pallas paged-attention kernel must emit the same tokens
    as a mesh-sharded engine on the dense-gather oracle path."""
    mesh = _mesh_or_skip(1, 4)
    ecfg = EngineConfig(slots=SLOTS, max_seq=MAX_SEQ)
    kern = ServeEngine(CFG, params,
                       EngineConfig(slots=SLOTS, max_seq=MAX_SEQ,
                                    paged_impl="kernel"))
    kern_toks = _mixed_traffic(kern, _requests())
    sharded = ServeEngine(CFG, params, ecfg, mesh=mesh)
    assert sharded.paged_impl == "gather"    # auto: kernel never under mesh
    sh_toks = _mixed_traffic(sharded, _requests())
    assert kern_toks == sh_toks
    # explicit kernel+mesh is rejected: the kernel has no GSPMD rule and
    # would silently rematerialize per-slot tensors every step
    with pytest.raises(ValueError, match="mesh"):
        ServeEngine(CFG, params,
                    EngineConfig(slots=SLOTS, max_seq=MAX_SEQ,
                                 paged_impl="kernel"), mesh=mesh)


def test_init_params_built_into_shards(params):
    """serve/sharding.init_params builds the tree straight into its serving
    placement — the same values as on one device, each leaf already laid
    out as place_params would put it."""
    mesh = _mesh_or_skip(1, 4)
    key = jax.random.PRNGKey(0)
    one = shard_lib.init_params(CFG, key)
    sharded = shard_lib.init_params(CFG, key, mesh=mesh)
    want = shard_lib.place_params(one, CFG, mesh)
    for a, b, w in zip(jax.tree.leaves(one), jax.tree.leaves(sharded),
                       jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding.is_equivalent_to(w.sharding, b.ndim)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
