"""Plan/execute GEMM dispatch API tests (the api_redesign acceptance
grid): policy lever selection on the paper's twelve prefill shapes,
plan-cache hit/miss/eviction behavior, bit-exactness of execute vs
kernels/ref in interpret mode, the retired-shim contract, and the
backend registry hook.  Deliberately hypothesis-free — this module must
run on a bare container."""
import dataclasses
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import gemm as G
from repro.core import bitexact, packing
from repro.kernels import ref
from repro.models.model_zoo import PAPER_GEMM_SHAPES, PAPER_M

RNG = np.random.default_rng(11)


def _rand(shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


@pytest.fixture(autouse=True)
def _fresh_cache():
    G.plan_cache_clear()
    yield
    G.plan_cache_clear()


# ------------------------------------------------------------------ policy
@pytest.mark.parametrize("model,op,n,k", PAPER_GEMM_SHAPES)
def test_policy_levers_on_paper_shapes(model, op, n, k):
    """The acceptance criterion: K >= N resolves to fine panels, N > K to
    pre-packed plans — per shape, not per process."""
    p = G.plan(PAPER_M, n, k)
    if k >= n:
        assert p.lever == G.LEVER_FINE_PANELS, (model, op, p)
        assert not p.prepack
        assert p.pack == G.PACK_PERCALL
    else:
        assert p.lever == G.LEVER_PREPACK, (model, op, p)
        assert p.prepack
        assert p.pack == G.PACK_PREPACKED
        assert p.block_k >= 512          # the deep-K (Kc=2048 class) pack


def test_fine_panels_sized_for_occupancy():
    """K >= N plans feed all cores when the shape allows it (the paper's
    idle-second-block failure, avoided)."""
    p = G.plan(128, 2048, 2048, num_cores=8)
    panels = p.grid[0] * p.grid[1]
    assert panels >= 8 and p.occupancy == 1.0
    coarse = G.plan(128, 2048, 2048, num_cores=8, block_n=1024)
    assert panels > coarse.grid[0] * coarse.grid[1]
    assert p.t_pred < coarse.t_pred


def test_plan_is_static_hashable_pytree():
    p = G.plan(128, 256, 512)
    assert jax.tree_util.tree_leaves(p) == []        # no array leaves
    assert hash(p) == hash(G.plan(128, 256, 512))
    with pytest.raises(Exception):
        object.__setattr__  # frozen: direct assignment raises
        p.backend = "other"  # type: ignore[misc]


# ------------------------------------------------------------- plan cache
def test_plan_cache_hit_miss():
    info0 = G.plan_cache_info()
    assert (info0.hits, info0.misses, info0.currsize) == (0, 0, 0)
    p1 = G.plan(128, 2048, 2048)
    assert G.plan_cache_info().misses == 1
    p2 = G.plan(128, 2048, 2048)
    assert G.plan_cache_info().hits == 1
    assert p2 is p1                       # cached object, not a rebuild
    G.plan(128, 2048, 4096)               # different shape -> miss
    assert G.plan_cache_info().misses == 2
    G.plan(128, 2048, 2048, backend="interpret")   # key includes backend
    assert G.plan_cache_info().misses == 3
    G.plan(64, 2048, 2048)                # key includes m
    assert G.plan_cache_info().misses == 4


def test_plan_cache_keyed_on_sharding_and_dtype():
    a = G.plan(128, 256, 512, dtype=jnp.float32)
    b = G.plan(128, 256, 512, dtype=jnp.bfloat16)
    assert b is not a
    c = G.plan(128, 256, 512, dtype=jnp.float32, sharding="model:0")
    assert c is not a and c.sharding_key == "model:0"
    assert G.plan_cache_info().misses == 3


def test_plan_cache_eviction_bounded():
    from repro.gemm import policy as pol
    for i in range(pol._CACHE_MAXSIZE + 10):
        G.plan(8, 128, 128 * (i + 1), block_n=128, block_k=128)
    assert G.plan_cache_info().currsize <= pol._CACHE_MAXSIZE


# ------------------------------------------------- execute / bit-exactness
@pytest.mark.parametrize("m,n,k", [
    (128, 256, 256), (128, 512, 128), (256, 128, 384),
    (128, 2048 // 4, 2048 // 4),   # scaled QKV class (K >= N)
    (128, 8192 // 16, 2048 // 8),  # scaled FFN1 (N > K)
    (128, 2048 // 8, 8192 // 16),  # scaled FFN2 (K > N)
])
def test_execute_interpret_bitexact_vs_ref(m, n, k):
    """execute() on the interpret backend is BIT-identical to the blocked
    oracle at the plan's block_k — packed and per-call operands alike."""
    x, w = _rand((m, k)), _rand((k, n))
    p = G.plan(m, n, k, backend="interpret", block_m=128, block_n=128,
               block_k=min(128, k), validate=True)
    assert p.validated
    y_percall = G.execute(p, x, w)
    pw = G.pack_for_plan(p, w)
    y_packed = G.execute(p, x, pw)
    oracle = ref.gemm_blocked(x, w, p.block_k)
    bitexact.assert_bit_identical(np.asarray(y_percall), np.asarray(oracle))
    bitexact.assert_bit_identical(np.asarray(y_packed), np.asarray(oracle))


def test_execute_policy_plans_bitexact_both_levers():
    """Policy-resolved (not hand-blocked) plans for one K>=N and one N>K
    shape, interpret backend, against the XLA reference (allclose) and
    each other's pack variants (bitwise)."""
    for (m, n, k) in [(128, 256, 512), (128, 640, 256)]:
        x, w = _rand((m, k)), _rand((k, n))
        p = G.plan(m, n, k, backend="interpret")
        assert G.validate_plan(p)
        pw = G.pack_for_plan(p, w)
        y1, y2 = G.execute(p, x, w), G.execute(p, x, pw)
        bitexact.assert_bit_identical(np.asarray(y1), np.asarray(y2))
        np.testing.assert_allclose(y1, ref.gemm_xla(x, w),
                                   rtol=1e-4, atol=1e-4)


def test_execute_batched_leading_dims_and_mismatch_errors():
    x = _rand((2, 64, 384))
    w = _rand((384, 256))
    p = G.plan(128, 256, 384, backend="xla")
    y = G.execute(p, x, w)
    np.testing.assert_allclose(
        y, np.einsum("bsk,kn->bsn", np.asarray(x), np.asarray(w)),
        rtol=1e-4, atol=1e-4)
    with pytest.raises(G.PlanMismatchError):
        G.execute(p, _rand((64, 384)), w)          # M != plan.m
    with pytest.raises(G.PlanMismatchError):
        G.execute(G.plan(128, 256, 512), _rand((2, 64, 384)), w)  # K
    pw_other = packing.pack(w, block_n=256, block_k=384)
    with pytest.raises(G.PlanMismatchError):
        G.execute(p, x, pw_other)                  # pack blocks != plan


def test_pack_none_skips_relayout_on_xla():
    """The raw-dot analogue: PACK_NONE + xla backend must equal the plain
    XLA dot bitwise (no padding, no re-layout in the way)."""
    x, w = _rand((100, 300)), _rand((300, 200))
    p = G.plan(100, 200, 300, backend="xla", pack=G.PACK_NONE)
    bitexact.assert_bit_identical(
        np.asarray(G.execute(p, x, w)), np.asarray(ref.gemm_xla(x, w)))


# -------------------------------------------- retired legacy shims
def test_legacy_shim_import_raises_with_pointer():
    """The core/panel_gemm shims completed their deprecation timeline:
    importing the module is now a HARD error carrying the migration
    pointer, and repro.core no longer re-exports the legacy names."""
    import repro.core as core
    with pytest.raises(ImportError, match="repro.gemm"):
        import repro.core.panel_gemm  # noqa: F401
    for name in ("gemm", "gemm_percall", "gemm_xla"):
        assert not hasattr(core, name)


def test_env_var_never_steers_a_plan(monkeypatch):
    """REPRO_GEMM_IMPL died with the shims: no surface reads it."""
    monkeypatch.setenv("REPRO_GEMM_IMPL", "interpret")
    p = G.plan(8, 128, 128)
    assert p.backend == "xla"                      # process default wins


# --------------------------------------------------------- backend registry
def test_register_backend_hook():
    calls = []

    def run(x_p, w_p, *, block_m, block_n, block_k, out_dtype):
        calls.append((x_p.shape, w_p.shape))
        return jnp.dot(x_p, w_p,
                       preferred_element_type=jnp.float32).astype(
            out_dtype or x_p.dtype)

    G.register_backend("test-custom", run, description="unit-test")
    try:
        assert "test-custom" in G.list_backends()
        x, w = _rand((16, 128)), _rand((128, 128))
        p = G.plan(16, 128, 128, backend="test-custom", block_m=16,
                   block_n=128, block_k=128)
        y = G.execute(p, x, w)
        assert calls, "custom backend was not dispatched"
        np.testing.assert_allclose(y, ref.gemm_xla(x, w), rtol=1e-5,
                                   atol=1e-5)
        with pytest.raises(ValueError):
            G.register_backend("test-custom", run)   # no silent overwrite
    finally:
        G.unregister_backend("test-custom")
    with pytest.raises(G.UnknownBackendError):
        G.plan(16, 128, 128, backend="test-custom")
    with pytest.raises(ValueError):
        G.unregister_backend("xla")                  # builtins protected


def test_use_backend_scope_nests():
    assert G.default_backend() == "xla"
    with G.use_backend("interpret"):
        assert G.default_backend() == "interpret"
        with G.use_backend("pallas"):
            assert G.default_backend() == "pallas"
        assert G.default_backend() == "interpret"
        assert G.plan(8, 128, 128).backend == "interpret"
    assert G.default_backend() == "xla"
    with G.use_backend(None):                        # optional scope no-op
        assert G.default_backend() == "xla"


@pytest.mark.parametrize("platform,fmt,stacked,m,want", [
    ("tpu", "fp32", True, 128, "pallas"),     # a layer's pack, one panel
    ("tpu", "fp32", True, 4, "pallas"),       # the decode step
    ("tpu", "fp32", True, 1024, "xla"),       # rows past one row panel
    ("tpu", "fp32", False, 1, "xla"),         # a 2-D weight: the LM head
    ("tpu", "int8", True, 128, "xla"),        # quantized packs
    ("cpu", "fp32", True, 128, "xla"),
])
def test_device_default_backend(platform, fmt, stacked, m, want):
    """Off the TPU every plan runs ``xla``; on it the panel kernel runs
    the float32 plans of stacked packs up to one row panel of rows."""
    assert G.backends.device_default(platform, weight_format=fmt,
                                     stacked=stacked, m=m) == want


def test_plan_for_a_stacked_pack_takes_the_device_default(monkeypatch):
    """``plan_for_packed`` tells the default whether the pack is stacked
    (and a scope still wins over the device's choice)."""
    monkeypatch.setattr(G.backends.jax, "default_backend", lambda: "tpu")
    stack = packing.PackedWeight(data=jnp.zeros((2, 256, 256)), n=256,
                                 k=256, block_n=128, block_k=128)
    flat = packing.PackedWeight(data=jnp.zeros((256, 256)), n=256, k=256,
                                block_n=128, block_k=128)
    assert G.plan_for_packed(128, stack).backend == "pallas"
    assert G.plan_for_packed(256, stack).backend == "xla"
    assert G.plan_for_packed(128, flat).backend == "xla"
    with G.use_backend("interpret"):
        assert G.plan_for_packed(128, flat).backend == "interpret"


_MESH_SCRIPT = """
import jax, jax.numpy as jnp
from repro import obs
from repro.gemm import backends
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo
from repro.runtime.serve_loop import Engine
chip = backends.device_default
backends.device_default = lambda _platform, **kw: chip("tpu", **kw)
cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
params = model_zoo.build(cfg)
tokens = jnp.zeros((2, 8), jnp.int32)
for mesh in (None, make_host_mesh()):
    eng = Engine(cfg, params, mesh=mesh, max_len=32, packed=True)
    jax.eval_shape(eng._prefill, eng.params, tokens)
    recs = obs.manifests().pop("prefill_m16")
    print(mesh is not None and mesh.size, eng.backend,
          sorted({r["backend"] for r in recs if r["stacked"]}))
    if mesh is not None:
        eng.prefill(tokens)[0].block_until_ready()
        print("ran")
"""


def _on_two_devices(script: str) -> list[str]:
    """Run ``script`` on a CPU host of two devices; its output lines."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.splitlines()


def test_a_multi_device_engine_keeps_the_kernel_off_its_plans():
    """The panel kernel is one device's custom call, which XLA cannot
    partition: with the TPU's default in force, an engine on one device
    runs its layer projections on ``pallas`` and one whose mesh spans
    two devices pins ``xla`` (and its step runs; on the kernel the CPU
    could not even lower it)."""
    assert _on_two_devices(_MESH_SCRIPT) == [
        "False None ['pallas']", "2 xla ['xla']", "ran"]


# ------------------------------------- stacked expert banks read in place
_BANK = (4, 4, 64, 48)           # layers, groups, K, N


def _bank(glu: bool) -> packing.PackedWeight:
    """A stacked grouped pack [L, G, K, N]: gate and up fused for the glu
    epilogue, or one part (the down bank); unit-scale outputs."""
    rng = np.random.default_rng(5)
    return packing.pack_grouped(
        [jnp.asarray((rng.standard_normal(_BANK) / 8).astype(np.float32))
         for _ in range(2 if glu else 1)])


def _single_tpu(monkeypatch):
    """Resolve the grouped plans as a single TPU would, on this CPU host
    (as tests/test_tpu_compile.py forces the device default)."""
    chip = G.backends.device_grouped_in_place
    monkeypatch.setattr(G.backends, "device_grouped_in_place",
                        lambda _platform, **kw: chip("tpu", **kw))


@pytest.mark.parametrize("platform,dtype,stacked,want", [
    ("tpu", "float32", True, True),
    ("tpu", "float32", False, False),      # one layer's bank [G, K, N]
    ("tpu", "bfloat16", True, False),
    ("cpu", "float32", True, False),
])
def test_device_grouped_in_place(platform, dtype, stacked, want):
    """Only a single TPU folds, and only a float32 stacked bank."""
    assert G.backends.device_grouped_in_place(
        platform, dtype=dtype, stacked=stacked) is want


def test_a_stacked_bank_keeps_the_slice_where_no_single_tpu_folds(
        monkeypatch):
    """The CPU's default slices the layer's bank out; with the TPU's
    choice in force a scope (what a multi-device ``Engine(mesh=)``
    opens) or an explicit backend still slices, and so does a bank with
    no layer axis."""
    pw = _bank(False)
    assert not G.plan_for_packed(40, pw).in_place
    _single_tpu(monkeypatch)
    p = G.plan_for_packed(40, pw)
    assert p.in_place and p.describe().endswith(", in_place]")
    assert p is G.plan_for_packed(40, pw)
    assert not G.plan_for_packed(40, pw, backend="xla").in_place
    with G.use_backend("xla"):
        assert not G.plan_for_packed(40, pw).in_place
    one_layer = packing.PackedWeight(
        data=pw.data[0], n=pw.n, k=pw.k, block_n=pw.block_n,
        block_k=pw.block_k, groups=pw.groups)
    assert not G.plan_for_packed(40, one_layer).in_place


@pytest.mark.parametrize("sizes", [(10, 0, 20, 5), (0, 0, 0, 40),
                                   (3, 4, 5, 6)],
                         ids=["zeros", "one_group", "below_m"])
@pytest.mark.parametrize("layer", [0, 1, 3])
@pytest.mark.parametrize("glu", [True, False], ids=["gate_up", "down"])
def test_a_folded_bank_computes_what_the_slice_computes(monkeypatch, glu,
                                                        layer, sizes):
    """On a single TPU the layer folds into the group axis; the live rows
    equal the sliced run's at the CPU's deviation (XLA:CPU's ragged dot
    sums in another order over the folded bank: 1.5e-7 at most at unit
    scale), the rows past the sizes' sum come back exactly zero, and the
    output keeps its shape."""
    _single_tpu(monkeypatch)
    pw = _bank(glu)
    m = 40
    fold = G.plan_for_packed(
        m, pw, epilogue=G.EpilogueSpec(glu="silu") if glu else None)
    sliced = dataclasses.replace(fold, in_place=False)
    x = _rand((m, pw.k))
    gs = jnp.asarray(sizes, jnp.int32)
    li = jnp.int32(layer)
    want = G.execute(sliced, x, pw, group_sizes=gs, layer=li)
    got = jax.jit(lambda x, w, gs, li: G.execute(
        fold, x, w, group_sizes=gs, layer=li))(x, pw, gs, li)
    live = sum(sizes)
    assert fold.in_place and got.shape == want.shape == (m, _BANK[-1])
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-6,
                               atol=1e-6)
    assert not np.any(np.asarray(got[live:]))


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "single_tpu"])
def test_the_recorder_marks_a_folded_bank_in_place(monkeypatch, tpu):
    """A traced grouped dispatch of a stacked bank is ``stacked``, and
    ``in_place`` exactly where the layer folds."""
    from repro import obs
    if tpu:
        _single_tpu(monkeypatch)
    pw = _bank(False)

    def step(x, w, gs, li):
        with obs.manifest_scope("grouped_fold"):
            p = G.plan_for_packed(x.shape[0], w)
            return G.execute(p, x, w, group_sizes=gs, layer=li)
    jax.eval_shape(step, _rand((40, pw.k)), pw,
                   jnp.asarray((10, 0, 20, 5), jnp.int32), jnp.int32(2))
    (rec,) = obs.manifests().pop("grouped_fold")
    assert rec["stacked"] and rec["in_place"] is tpu
    assert obs.stacked_in_place_share([rec]) == float(tpu)


_MESH_MOE_SCRIPT = """
import jax, jax.numpy as jnp
from repro import obs
from repro.gemm import backends
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo
from repro.runtime.serve_loop import Engine
fold = backends.device_grouped_in_place
backends.device_grouped_in_place = lambda _platform, **kw: fold("tpu", **kw)
cfg = model_zoo.reduced_config(model_zoo.get_config("deepseek-v2-lite-ep8"))
params = model_zoo.build(cfg)
tokens = jnp.zeros((2, 8), jnp.int32)
for mesh in (None, make_host_mesh()):
    eng = Engine(cfg, params, mesh=mesh, max_len=32, packed=True)
    jax.eval_shape(eng._prefill, eng.params, tokens)
    recs = obs.manifests().pop("prefill_m16")
    print(mesh is not None and mesh.size,
          sorted({r["in_place"] for r in recs if "groups=" in r["plan"]}))
    if mesh is not None:
        eng.prefill(tokens)[0].block_until_ready()
        print("ran")
"""


def test_a_multi_device_engine_slices_the_expert_banks():
    """The mesh partitions the banks' expert axis, so folding the layer
    into it would move the bank between devices: with the single TPU's
    choice in force an engine on one device folds every grouped GEMM,
    and one whose mesh spans two devices slices the layer's bank out
    (and its step runs)."""
    assert _on_two_devices(_MESH_MOE_SCRIPT) == [
        "False [True]", "2 [False]", "ran"]


# ------------------------------------------- epilogue / fusion bitexact
EPI_SPECS = [
    G.EpilogueSpec(bias=True),
    G.EpilogueSpec(act="silu"),
    G.EpilogueSpec(act="gelu"),
    G.EpilogueSpec(act="tanh"),
    G.EpilogueSpec(softcap=30.0),
    G.EpilogueSpec(residual=True),
    G.EpilogueSpec(bias=True, act="silu", softcap=50.0, residual=True),
    G.EpilogueSpec(glu="silu"),
    G.EpilogueSpec(glu="gelu"),
    G.EpilogueSpec(glu="silu", bias=True, residual=True),
]


def _epi_id(s):
    parts = [k for k, v in (("bias", s.bias), ("res", s.residual)) if v]
    if s.act:
        parts.insert(0, s.act)
    if s.glu:
        parts.insert(0, f"glu-{s.glu}")
    if s.softcap:
        parts.append("softcap")
    return "+".join(parts)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("spec", EPI_SPECS, ids=_epi_id)
def test_epilogue_bitexact_vs_unfused_sequence(spec, backend):
    """THE fusion contract: for fp32 operands, every EpilogueSpec x
    backend is BIT-identical to the unfused ``execute -> jnp op``
    sequence (ops under jit, as the model runs them)."""
    m, k = 32, 256
    n = 512 if spec.glu else 256
    x, w = _rand((m, k)), _rand((k, n))
    kw = dict(backend=backend, block_m=32, block_n=128, block_k=128)
    base = G.plan(m, n, k, **kw)
    pw = G.pack_for_plan(base, w)
    p = G.plan(m, n, k, epilogue=spec, **kw)
    assert G.validate_plan(p)       # interpret gate covers this spec
    bias = _rand((n,)) if spec.bias else None
    res = _rand((m, p.n_out)) if spec.residual else None

    # both sides under jit — exactly how the model invokes them (jit
    # generates FMAs eager dispatch does not, so eager-vs-jit is NOT
    # bit-stable; jit-vs-jit is the deployed contract)
    @jax.jit
    def fused(x, pw):
        return G.execute(p, x, pw, bias=bias, residual=res)

    @jax.jit
    def unfused(x, pw):
        acc = G.execute(base, x, pw, out_dtype=jnp.float32)
        return G.apply_epilogue(acc, spec, bias=bias,
                                residual=res).astype(jnp.float32)

    bitexact.assert_bit_identical(np.asarray(fused(x, pw)),
                                  np.asarray(unfused(x, pw)))


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_fused_qkv_pack_split_matches_separate(backend):
    """Horizontal fusion: one pass over a pack_fused weight, split by the
    static map, bit-identical per part to the separate GEMMs (ragged
    part widths exercise the interior padding)."""
    m, k = 128, 256
    widths = (192, 64, 64)
    parts = [_rand((k, n)) for n in widths]
    x = _rand((m, k))
    pwf = packing.pack_fused(parts, block_n=128, block_k=128)
    assert pwf.n_splits == widths
    assert pwf.data.shape == (256, 512)      # parts padded to 256/128/128
    p = G.plan_for_packed(m, pwf, backend=backend)
    outs = G.split_fused(p, G.execute(p, x, pwf))
    assert tuple(o.shape[-1] for o in outs) == widths
    for out, part in zip(outs, parts):
        pw1 = packing.pack(part, block_n=128, block_k=128)
        p1 = G.plan_for_packed(m, pw1, backend=backend)
        bitexact.assert_bit_identical(np.asarray(out),
                                      np.asarray(G.execute(p1, x, pw1)))


def test_fused_glu_pack_blocks_flow():
    """pack_blocks(epilogue=glu) reserves the two-accumulator VMEM
    footprint, so pack and execute-time plan agree on blocks."""
    n_cat, k = 2 * 2048, 2048
    glu = G.EpilogueSpec(glu="silu")
    bn, bk = G.pack_blocks(n_cat, k, epilogue=glu)
    wg, wu = _rand((k, n_cat // 2)), _rand((k, n_cat // 2))
    pw = packing.pack_fused([wg, wu], block_n=bn, block_k=bk)
    p = G.plan_for_packed(128, pw, epilogue=glu)
    assert (p.block_n, p.block_k) == (pw.block_n, pw.block_k)
    assert p.n_out == n_cat // 2
    from repro.kernels.panel_gemm import VMEM_BUDGET, vmem_bytes
    assert vmem_bytes(p.block_m, p.block_n, p.block_k,
                      epilogue=glu) <= VMEM_BUDGET


def test_fused_plan_rejects_raw_weights_and_bad_operands():
    parts = [_rand((256, 128)), _rand((256, 128))]
    pwf = packing.pack_fused(parts, block_n=128, block_k=128)
    x = _rand((8, 256))
    p = G.plan_for_packed(8, pwf)
    with pytest.raises(G.PlanMismatchError):
        G.execute(p, x, jnp.concatenate(parts, axis=1))   # raw concat
    with pytest.raises(G.PlanMismatchError):
        G.execute(p, x, pwf, bias=_rand((256,)))          # no epilogue
    pglu = G.plan_for_packed(8, pwf, epilogue=G.EpilogueSpec(glu="silu"))
    with pytest.raises(ValueError):
        G.split_fused(pglu, _rand((8, 128)))   # glu combines in-kernel
    with pytest.raises(ValueError):
        G.split_fused(G.plan(8, 128, 256), x)  # no split map


def test_plan_cache_keys_fusion_and_epilogue():
    """Fused / epilogue plans are distinct cache entries, and repeated
    fused planning is a cache hit (plans stay hot under fusion)."""
    a = G.plan(128, 512, 256)
    b = G.plan(128, 512, 256, epilogue=G.EpilogueSpec(act="silu"))
    c = G.plan(128, 512, 256, fused_n_splits=(256, 256))
    assert len({a, b, c}) == 3
    assert G.plan_cache_info().misses == 3
    G.plan(128, 512, 256, epilogue=G.EpilogueSpec(act="silu"))
    assert G.plan_cache_info().hits == 1
    # a no-op epilogue normalizes to the plain plan's key
    assert G.plan(128, 512, 256, epilogue=G.EpilogueSpec()) is a


def test_epilogue_spec_validation():
    with pytest.raises(ValueError):
        G.EpilogueSpec(act="relu")
    with pytest.raises(ValueError):
        G.EpilogueSpec(act="silu", glu="silu")
    assert G.EpilogueSpec().is_noop
    assert not G.EpilogueSpec(softcap=1.0).is_noop


# ------------------------------------------------------- vmem satellite
def test_policy_clamps_blocks_to_vmem_budget():
    """Satellite: an explicit (or fused-wide) block triple that exceeds
    the kernel VMEM budget is shrunk until it fits, and the plan says
    so."""
    from repro.kernels.panel_gemm import VMEM_BUDGET, vmem_bytes
    p = G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
    assert p.vmem_clamped
    assert vmem_bytes(p.block_m, p.block_n, p.block_k) <= VMEM_BUDGET
    assert "vmem_clamped" in p.describe()
    # glu doubles the weight/accumulator tiles: the same explicit triple
    # must clamp harder than the plain plan
    glu = G.EpilogueSpec(glu="silu")
    pg = G.plan(128, 4096, 8192, block_n=2048, block_k=4096, epilogue=glu)
    assert vmem_bytes(pg.block_m, pg.block_n, pg.block_k,
                      epilogue=glu) <= VMEM_BUDGET
    # policy-resolved plans stay un-clamped at sane shapes
    assert not G.plan(128, 2048, 2048).vmem_clamped


# ------------------------------------------- sharding-key satellite fix
def test_plan_for_packed_keys_on_named_sharding():
    """Satellite: packs placed with distinct NamedShardings no longer
    alias one plan entry (the sharding_key='' bug)."""
    import jax.sharding as JS
    dev = jax.devices()[0]
    mesh_a = JS.Mesh(np.array([dev]), ("model",))
    mesh_b = JS.Mesh(np.array([dev]), ("data",))
    w = _rand((256, 128))
    pa = packing.pack(w, block_n=128, block_k=128,
                      sharding=JS.NamedSharding(mesh_a, JS.PartitionSpec()))
    pb = packing.pack(w, block_n=128, block_k=128,
                      sharding=JS.NamedSharding(mesh_b, JS.PartitionSpec()))
    plan_a = G.plan_for_packed(8, pa)
    plan_b = G.plan_for_packed(8, pb)
    assert plan_a.sharding_key and plan_b.sharding_key
    assert plan_a.sharding_key != plan_b.sharding_key
    assert plan_a is not plan_b
    # unplaced packs keep the neutral key (cache behavior unchanged)
    pc = packing.pack(w, block_n=128, block_k=128)
    assert G.plan_for_packed(8, pc).sharding_key == ""


# ------------------------------------------------------------ model path
def test_linear_packed_routes_through_plan_cache():
    from repro.models.layers import linear
    w = _rand((384, 256))
    pw = packing.pack(w, block_n=128, block_k=128)
    x = _rand((4, 32, 384))
    y = linear(x, pw)
    np.testing.assert_allclose(
        y, np.einsum("bsk,kn->bsn", np.asarray(x), np.asarray(w)),
        rtol=1e-4, atol=1e-4)
    assert G.plan_cache_info().misses >= 1
    linear(x, pw)
    assert G.plan_cache_info().hits >= 1


# ----------------------------------------- plan-cache bugfix regressions
def test_vmem_warn_state_evicted_with_plan():
    """Bugfix: ``_vmem_warned`` entries die with their cached plan.

    Before the fix the warn-once set only ever grew: a clamped plan's
    LRU eviction left its warn key behind, so (a) the set leaked
    unboundedly under plan churn and (b) a re-resolved clamp of the
    same shape was silently un-warned forever."""
    from repro.gemm import policy as pol
    p = G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
    assert p.vmem_clamped
    wk = pol._warn_key(p)
    assert wk in pol._vmem_warned
    # churn the cache until the clamped plan is LRU-evicted
    for i in range(pol._CACHE_MAXSIZE + 1):
        G.plan(8, 128, 128 * (i + 1), block_n=128, block_k=128)
    from repro.gemm.policy import _plan_key
    assert _plan_key(128, 4096, 8192, block_n=2048,
                     block_k=4096) not in pol._cache
    assert wk not in pol._vmem_warned     # warn state evicted alongside
    # ...so the NEXT resolution of that shape warns again
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
    assert any("VMEM" in str(w.message) for w in wlog)


def test_vmem_warn_state_kept_while_sibling_cached():
    """A warn key shared by two cached clamped plans (same logical
    shape, different explicit blocks) survives one sibling's eviction —
    warn-once stays once while any holder is live."""
    from repro.gemm import policy as pol
    a = G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
    b = G.plan(128, 4096, 8192, block_n=4096, block_k=2048)
    assert a.vmem_clamped and b.vmem_clamped
    wk = pol._warn_key(a)
    assert pol._warn_key(b) == wk and wk in pol._vmem_warned
    with pol._cache_lock:                 # evict exactly plan ``a``
        ka = next(k for k, v in pol._cache.items() if v is a)
        del pol._cache[ka]
        # simulate the eviction path's warn-state scan
        if not any(q.vmem_clamped and pol._warn_key(q) == wk
                   for q in pol._cache.values()):
            pol._vmem_warned.discard(wk)
    assert wk in pol._vmem_warned         # sibling ``b`` still cached


def test_plan_cache_clear_resets_counters():
    """Bugfix contract: ``plan_cache_clear`` resets entries AND both
    hit/miss counters AND the vmem warn/clamp observability — a cleared
    cache reads (0, 0, maxsize, 0) exactly, so callers can treat
    counter deltas as absolute."""
    from repro.gemm import policy as pol
    G.plan(128, 2048, 2048)
    G.plan(128, 2048, 2048)
    G.plan(128, 4096, 8192, block_n=2048, block_k=4096)  # clamped
    info = G.plan_cache_info()
    assert info.hits == 1 and info.misses == 2 and info.currsize == 2
    assert G.vmem_clamped_count() == 1 and pol._vmem_warned
    G.plan_cache_clear()
    info = G.plan_cache_info()
    assert (info.hits, info.misses, info.currsize) == (0, 0, 0)
    assert info.maxsize == pol._CACHE_MAXSIZE
    assert G.vmem_clamped_count() == 0
    assert not pol._vmem_warned


def test_concurrent_plan_single_resolve(monkeypatch):
    """Bugfix: N threads racing one cold key share ONE resolution.

    Before the per-key in-flight dedup, every racer that read the miss
    before the first writer published ran its own ``_resolve`` — N
    analytic resolutions (and, with validate=True, N bit-exactness gate
    runs) for one plan, and the miss counter over-counted."""
    import threading
    from repro.gemm import policy as pol
    calls = []
    real = pol._resolve

    def counting(*a, **kw):
        calls.append(threading.get_ident())
        time.sleep(0.05)              # widen the race window
        return real(*a, **kw)

    monkeypatch.setattr(pol, "_resolve", counting)
    n_threads = 8
    barrier = threading.Barrier(n_threads)
    plans, errs = [], []

    def racer():
        try:
            barrier.wait()
            plans.append(G.plan(96, 1536, 1536, validate=True))
        except Exception as e:        # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=racer) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert len(calls) == 1, f"{len(calls)} resolves for one key"
    assert len({id(p) for p in plans}) == 1    # all adopted one object
    info = G.plan_cache_info()
    assert info.misses == 1 and info.hits == n_threads - 1


def test_inflight_owner_failure_hands_off(monkeypatch):
    """A failed owner releases its waiters, and one of them becomes the
    new owner instead of caching the failure or deadlocking."""
    import threading
    from repro.gemm import policy as pol
    real = pol._resolve
    fail_first = [True]
    calls = []

    def flaky(*a, **kw):
        calls.append(1)
        time.sleep(0.05)
        if fail_first[0]:
            fail_first[0] = False
            raise RuntimeError("injected resolve failure")
        return real(*a, **kw)

    monkeypatch.setattr(pol, "_resolve", flaky)
    results, errs = [], []
    barrier = threading.Barrier(2)

    def racer():
        try:
            barrier.wait()
            results.append(G.plan(80, 1280, 1280))
        except RuntimeError as e:
            errs.append(e)

    ts = [threading.Thread(target=racer) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(errs) == 1 and "injected" in str(errs[0])
    assert len(results) == 1               # the survivor got a real plan
    assert len(calls) == 2                 # failed owner + take-over
    assert not pol._inflight               # no leaked in-flight events
