"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
block shapes off the (8, 128) tiling, casts it does not lower, VMEM
over budget.  These tests compile each kernel at stablelm-3b's published
widths, at the blocks the dispatch policy resolves for them, against a
described ``v5e:2x2`` topology — no chip needed — and check that the
compiled program holds the kernel (``tpu_custom_call``).  The paged
serving steps compile at the benchmark cells' shapes too, on the chip's
dispatch default, and must index the KV pool and the stacked weight
packs in place.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, so a
worker that imports this file must not touch it unless it runs these
tests.  Keep every such compile in this one file.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import _kv_guard
import _weight_guard
from repro import gemm
from repro.gemm import backends
from repro.kernels.flash_attention import flash_attention
from repro.models import model_zoo
from repro.quant import formats as F
from repro.quant import kernels as QK
from repro.runtime.serve_loop import Engine

ARCH = "stablelm-3b"
PREFILL_M = 128          # one prefill chunk's rows
DECODE_M = 4             # a 4-slot decode tick


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _packs(quant=None):
    """stablelm-3b's packed tree, as shapes (nothing allocated)."""
    cfg = model_zoo.get_config(ARCH)
    return cfg, jax.eval_shape(
        lambda p: model_zoo.pack_for_inference(cfg, p, quant=quant),
        model_zoo.abstract_params(cfg))


def _on_chip(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _layer_pack(pw, sharding):
    """One layer's 2-D slice of a stacked [L, K, N] pack (what the scan
    body hands the kernel)."""
    kw = {"data": jax.ShapeDtypeStruct(pw.data.shape[-2:], pw.data.dtype,
                                       sharding=sharding)}
    if getattr(pw, "scales", None) is not None:
        kw["scales"] = jax.ShapeDtypeStruct(
            pw.scales.shape[-2:], pw.scales.dtype, sharding=sharding)
    return dataclasses.replace(pw, **kw)


def _compile_execute(p, pw, sharding, *, residual=False):
    """Compile ``gemm.execute`` of plan ``p`` — the serving dispatch —
    for the described chip; returns the compiled program text."""
    cdt = model_zoo.get_config(ARCH).cdtype
    args = [jax.ShapeDtypeStruct((p.m, p.k), cdt, sharding=sharding), pw]
    if residual:
        args.append(jax.ShapeDtypeStruct((p.m, p.n_out), cdt,
                                         sharding=sharding))

    def f(x, w, r=None):
        return gemm.execute(p, x, w, residual=r)

    return jax.jit(f).lower(*args).compile().as_text()


# (weight, rows, epilogue) — every GEMM a stablelm-3b serving step runs
_GLU = gemm.EpilogueSpec(glu="silu")
_RES = gemm.EpilogueSpec(residual=True)
SERVING_GEMMS = {
    "wqkv": ("attn/wqkv", PREFILL_M, None),
    "wo_residual": ("attn/wo", PREFILL_M, _RES),
    "glu_gate_up": ("ffn/w_gate_up", PREFILL_M, _GLU),
    "w_down_residual": ("ffn/w_down", PREFILL_M, _RES),
    "lm_head": ("lm_head", 1, None),       # the chunk's one logit row
    "decode_wqkv": ("attn/wqkv", DECODE_M, None),
    "decode_glu_gate_up": ("ffn/w_gate_up", DECODE_M, _GLU),
}


def _pack_at(packed, where, sharding):
    if where == "lm_head":
        return _on_chip(packed["lm_head"], sharding)
    group, name = where.split("/")
    return _layer_pack(packed["layers"][group][name], sharding)


@pytest.mark.parametrize("case", sorted(SERVING_GEMMS))
def test_fp32_serving_gemm_compiles(one_chip, case):
    where, m, spec = SERVING_GEMMS[case]
    _, packed = _packs()
    pw = _pack_at(packed, where, one_chip)
    p = gemm.plan_for_packed(m, pw, backend="pallas", epilogue=spec,
                             decode=m == DECODE_M)
    text = _compile_execute(p, pw, one_chip,
                            residual=spec is not None and spec.residual)
    assert "tpu_custom_call" in text, p.describe()


def test_splitk_decode_plan_compiles(one_chip):
    """The decode lane's split-K kernel at the wo pack's blocks (the
    policy picks split_k=1 at these widths, so the split is explicit:
    K = 2560 cuts into five whole 512-deep slices)."""
    _, packed = _packs()
    pw = _layer_pack(packed["layers"]["attn"]["wo"], one_chip)
    p = gemm.plan(8, pw.n, pw.k, backend="pallas", decode=True,
                  block_n=pw.block_n, block_k=pw.block_k,
                  pack="prepacked", split_k=5)
    assert p.split_k == 5 and p.decode
    assert "tpu_custom_call" in _compile_execute(p, pw, one_chip)


@pytest.mark.parametrize("fmt", ["int8", "ternary"])
@pytest.mark.parametrize("case", ["wqkv", "glu_gate_up", "decode_wqkv"])
def test_quant_serving_gemm_compiles(one_chip, fmt, case):
    where, m, spec = SERVING_GEMMS[case]
    _, packed = _packs(quant=fmt)
    pw = _pack_at(packed, where, one_chip)
    assert isinstance(pw, F.QuantizedPackedWeight) and pw.fmt == fmt
    p = gemm.plan_for_packed(m, pw, backend="pallas", epilogue=spec,
                             decode=m == DECODE_M)
    assert "tpu_custom_call" in _compile_execute(p, pw, one_chip)


@pytest.mark.parametrize("fmt", ["int8", "ternary"])
def test_quant_splitk_compiles(one_chip, fmt):
    _, packed = _packs(quant=fmt)
    pw = _layer_pack(packed["layers"]["attn"]["wo"], one_chip)
    split = pw.k_pad // pw.block_k
    p = gemm.plan(8, pw.n, pw.k, backend="pallas", decode=True,
                  block_n=pw.block_n, block_k=pw.block_k, pack="prepacked",
                  weight_format=fmt, split_k=split)
    assert p.split_k == split > 1
    assert "tpu_custom_call" in _compile_execute(p, pw, one_chip)


def test_sparse_ternary_gemm_compiles(one_chip):
    """The sparse-ternary group walk at the fused QKV's widths, half of
    its K groups occupied (a density-bucket-5 pack)."""
    cfg = model_zoo.get_config(ARCH)
    k, n = cfg.d_model, 3 * cfg.num_heads * cfg.head_dim
    p = gemm.plan(PREFILL_M, n, k, backend="pallas",
                  weight_format="ternary", density_bucket=5)
    kg = k // F.GROUP_K
    occupied = tuple(range(0, kg, 2))
    n_pad = -(-n // p.block_n) * p.block_n
    layout = (kg, occupied,
              (sum(1 << g for g in occupied),) * (n_pad // p.block_n),
              p.block_n)

    def f(x, codes, scales):
        return QK.sparse_quant_panel_gemm(
            x, codes, scales, sparse_layout=layout, block_m=p.block_m,
            block_n=p.block_n)

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((PREFILL_M, k), cfg.cdtype, sharding=one_chip),
        jax.ShapeDtypeStruct((len(occupied) * F.GROUP_K // 4, n_pad),
                             jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((len(occupied), n_pad), jnp.float32,
                             sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text


def _lowered(f, *shapes, sharding):
    return jax.jit(f).lower(*_on_chip(list(shapes), sharding)).as_text()


def _execute_text(p, pw, sharding, residual=False):
    """Lowered text of ``gemm.execute`` of plan ``p`` on the chip."""
    cdt = model_zoo.get_config(ARCH).cdtype
    args = [jax.ShapeDtypeStruct((p.m, p.k), cdt, sharding=sharding), pw]
    if residual:
        args.append(jax.ShapeDtypeStruct((p.m, p.n_out), cdt,
                                         sharding=sharding))

    def f(x, w, r=None):
        return gemm.execute(p, x, w, residual=r)

    return jax.jit(f).lower(*args).as_text()


def _serving_kernel_text(case, sharding, quant=None):
    where, m, spec = SERVING_GEMMS[case]
    _, packed = _packs(quant=quant)
    pw = _pack_at(packed, where, sharding)
    p = gemm.plan_for_packed(m, pw, backend="pallas", epilogue=spec,
                             decode=m == DECODE_M)
    return _execute_text(p, pw, sharding,
                         residual=spec is not None and spec.residual)


def _splitk_kernel_text(sharding, quant=None):
    """The wo pack's decode GEMM split along K (as the split-K compile
    tests above split it)."""
    _, packed = _packs(quant=quant)
    pw = _layer_pack(packed["layers"]["attn"]["wo"], sharding)
    split = 5 if quant is None else pw.k_pad // pw.block_k
    p = gemm.plan(8, pw.n, pw.k, backend="pallas", decode=True,
                  block_n=pw.block_n, block_k=pw.block_k, pack="prepacked",
                  weight_format=quant or "fp32", split_k=split)
    return _execute_text(p, pw, sharding)


KERNEL_NAMES = {
    "panel_gemm": lambda s: _serving_kernel_text("wqkv", s),
    "panel_gemm_glu": lambda s: _serving_kernel_text("glu_gate_up", s),
    "panel_gemm_splitk": lambda s: _splitk_kernel_text(s),
    "quant_panel_gemm": lambda s: _serving_kernel_text(
        "wqkv", s, quant="int8"),
    "quant_panel_gemm_glu": lambda s: _serving_kernel_text(
        "glu_gate_up", s, quant="int8"),
    "quant_panel_gemm_splitk": lambda s: _splitk_kernel_text(
        s, quant="int8"),
    "flash_attention": lambda s: _lowered(
        lambda q, k, v: flash_attention(q, k, v),
        *[jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16)] * 3,
        sharding=s),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_every_pallas_kernel_carries_its_name(one_chip, name):
    """Each ``pallas_call`` names its kernel, so a device profile names
    it too; the name rides in the lowered custom call.  (The SSD kernel
    does not lower for the chip — Mosaic has no ``cumsum`` — and is
    checked in interpret mode, tests/test_kernels.py.)"""
    text = KERNEL_NAMES[name](one_chip)
    assert f'kernel_name = "{name}"' in text


def test_sparse_kernel_carries_its_name(one_chip):
    cfg = model_zoo.get_config(ARCH)
    k, n = cfg.d_model, 512
    kg = k // F.GROUP_K
    occupied = tuple(range(0, kg, 2))
    layout = (kg, occupied, (sum(1 << g for g in occupied),) * 2, 256)
    text = _lowered(
        lambda x, codes, scales: QK.sparse_quant_panel_gemm(
            x, codes, scales, sparse_layout=layout, block_m=PREFILL_M,
            block_n=256),
        jax.ShapeDtypeStruct((PREFILL_M, k), cfg.cdtype),
        jax.ShapeDtypeStruct((len(occupied) * F.GROUP_K // 4, n), jnp.uint8),
        jax.ShapeDtypeStruct((len(occupied), n), jnp.float32),
        sharding=one_chip)
    assert 'kernel_name = "sparse_quant_panel_gemm"' in text


# the serving cells: one pipeline stage of each model at its published
# widths, 4 slots x 2064 tokens in pages of 16, 128-token prefill chunks
CELLS = {"stablelm-3b-16l": ("stablelm-3b", 16),
         "deepseek-7b-8l": ("deepseek-7b", 8)}
SLOTS, CELL_MAX_LEN, CELL_PAGE = 4, 2064, 16


@pytest.fixture(scope="module")
def cell_steps(one_chip):
    """Compiled paged steps of each serving cell (compiled once per
    module): ``{cell: (engine, {step: HLO text}, layer pool elements)}``."""
    cache = {}

    def get(cell):
        if cell not in cache:
            arch, layers, slots, max_len, chunk = CELL_STEPS[cell]
            cfg = dataclasses.replace(model_zoo.get_config(arch),
                                      num_layers=layers)
            packed = _on_chip(jax.eval_shape(
                lambda p: model_zoo.pack_for_inference(cfg, p),
                model_zoo.abstract_params(cfg)), one_chip)
            eng = Engine(cfg, packed, max_len=max_len, packed=True)
            num_pages = slots * max_len // CELL_PAGE
            with pytest.MonkeyPatch.context() as mp:
                # the host is a CPU; the steps are traced with the plans
                # the chip they compile for would give them
                chip = backends.device_default
                mp.setattr(backends, "device_default",
                           lambda _platform, **kw: chip("tpu", **kw))
                fold = backends.device_grouped_in_place
                mp.setattr(backends, "device_grouped_in_place",
                           lambda _platform, **kw: fold("tpu", **kw))
                texts = _kv_guard.step_texts(
                    eng, slots=slots, chunk=chunk, page_size=CELL_PAGE,
                    num_pages=num_pages, sharding=one_chip)
            layer = _kv_guard.layer_pool_elements(
                eng, num_pages=num_pages, page_size=CELL_PAGE)
            cache[cell] = (eng, texts, layer)
        return cache[cell]
    return get


# (arch, layers, slots, max_len, prefill chunk) of every serving cell;
# the long-prompt cell is one chip's share of DeepSeek-V2-Lite (stage 1
# of 2, routed experts 0-7 of 64)
CELL_STEPS = {cell: (arch, layers, SLOTS, CELL_MAX_LEN, PREFILL_M)
              for cell, (arch, layers) in CELLS.items()}
CELL_STEPS["deepseek-v2-lite-14l"] = ("deepseek-v2-lite-ep8", 14, 8, 16400,
                                      1024)


@pytest.mark.parametrize("cell", sorted(CELL_STEPS))
def test_paged_steps_index_the_pool_in_place(cell_steps, cell):
    """The compiled prefill-chunk, decode and megastep steps slice, copy
    and write back no layer's pool: the one instruction of a layer pool's
    size besides the slots' view is the scatter into the donated pool.
    Here the GQA cells' pool holds exactly the slots' 4 x 2064 tokens, so
    a decode step's view has a layer pool's elements; the latent pool's
    steps read it in blocks and hold no whole view."""
    _, texts, layer = cell_steps(cell)
    for name, text in texts.items():
        assert "kv_write/scatter" in text, name
        assert _kv_guard.pool_copies(text, layer) == [], name


def test_latent_steps_run_grouped_expert_gemms(cell_steps):
    """The long-prompt cell's steps compile at published widths with the
    held experts as TPU grouped-matmul kernels (two per MoE layer of the
    scan body: gate/up, down) and MLA's scopes, and its prefill step
    fits one chip with the weights and the pool beside it."""
    eng, texts, _ = cell_steps("deepseek-v2-lite-14l")
    for name, text in texts.items():
        assert text.count("%ragged-dot-none") >= 2, name
        assert "moe_route" in text and "mla_" in text, name
    assert "mla_expand" in texts["prefill"]
    assert "mla_absorb" in texts["decode"]


def test_latent_steps_copy_no_expert_bank(cell_steps):
    """The long-prompt cell's compiled prefill-chunk, decode and
    megastep steps slice or copy no stacked expert bank (no layer's
    ``[8, K_pad, N_pad]`` bank copied out of the ``[13, 8, K_pad,
    N_pad]`` stack): the grouped kernel reads the stack folded to
    ``[104, K_pad, N_pad]``, a bitcast of the parameter."""
    eng, texts, _ = cell_steps("deepseek-v2-lite-14l")
    shapes = _weight_guard.stack_shapes(eng.params, grouped=True)
    assert {s[-3:] for s in shapes} == {(8, 2048, 2816), (8, 1408, 2048)}
    for name, text in texts.items():
        assert _weight_guard.weight_copies(text, shapes) == [], name


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_dense_steps_read_the_weight_packs_in_place(cell_steps, cell):
    """The GQA cells' compiled prefill-chunk, decode and megastep steps
    convert, slice or copy no stacked weight pack (no bf16 copy of a
    stack, no layer's slice copied out of it): four panel kernels (QKV,
    W_o, gate/up, down) read them in place, and the benchmark's GEMM
    rule picks every one of them."""
    eng, texts, _ = cell_steps(cell)
    shapes = _weight_guard.stack_shapes(eng.params)
    for name, text in texts.items():
        assert _weight_guard.weight_copies(text, shapes) == [], name
        assert len(_weight_guard.panel_kernels(text)) == 4, name
        assert _weight_guard.kernels_unpicked(text) == set(), name


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_dense_cells_pick_the_same_gemm_ops(cell_steps, cell):
    """The GQA cells' compiled prefill steps give the benchmark's GEMM
    rule (``chipbench/trace_reduce.gemm_op_names``) seven ops: the four
    panel kernels that read the layers' stacked packs in place, and the
    LM head's one-row product, XLA's multiply-reduce fusion over the
    float32 head with the two instructions it fuses (PERF.md)."""
    from chipbench import trace_reduce
    _, texts, _ = cell_steps(cell)
    picks = trace_reduce.gemm_op_names(texts["prefill"])
    assert _weight_guard.panel_kernels(texts["prefill"]) <= picks
    assert len(picks) == 7
