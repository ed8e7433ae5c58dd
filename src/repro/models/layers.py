"""Shared building blocks.  Every projection goes through ``linear`` so the
whole stack can be switched between raw weights (training) and the paper's
pre-packed path (inference) — see models/model_zoo.pack_for_inference."""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro import gemm as _gemm
from repro.core.packing import PackedWeight, StackedLayer
from repro.kernels.panel_gemm import act_fn as _act_fn


def dot_dtype(native):
    """Operand dtype for bf16 einsums with fp32 accumulation.

    The TPU MXU consumes bf16 natively (and upcasting operands costs HBM
    round-trips — §Perf C3); the XLA:CPU thunk runtime cannot EXECUTE
    some bf16×bf16→f32 dots.  Real CPU execution therefore upcasts; the
    dry-run (compile-only, TPU-targeted) forces native via
    REPRO_MXU_DOTS=1, and REPRO_MXU_DOTS=0 forces fp32 everywhere.
    """
    force = os.environ.get("REPRO_MXU_DOTS")
    if force == "1":
        return native
    if force == "0" or jax.default_backend() == "cpu":
        return jnp.float32
    return native


def _pack_and_layer(w) -> tuple:
    """(pack, layer) of a packed weight: a ``StackedLayer``'s stacked pack
    and layer index, else the pack itself and None."""
    if isinstance(w, StackedLayer):
        return w.pack, w.layer
    return w, None


def linear(x: jax.Array, w, bias=None, *, softcap: float | None = None,
           residual=None, out_dtype=None) -> jax.Array:
    """x[..., K] @ w[K, N] (+ fused epilogue).  w may be a raw array, a
    PackedWeight (pre-packed once at model load — paper lever 2), one
    layer of a stacked pack (``StackedLayer``, read in place), or a
    QuantizedPackedWeight (quantized at pack time — the plan picks up
    its format and dispatches the dequant-fused path, repro.quant).

    Packed weights dispatch through the plan/execute API: the plan is
    resolved at trace time (shape-keyed LRU cache, so prefill and decode
    each resolve once) on the backend of the enclosing
    ``gemm.use_backend`` scope (e.g. the serving Engine's).  ``bias`` /
    ``softcap`` / ``residual`` become the plan's ``EpilogueSpec`` —
    applied on the fp32 accumulator inside the kernel's store step, so
    the projection's output leaves the GEMM already finished instead of
    round-tripping through HBM for a follow-up XLA op.  The raw-weight
    path applies the identical fp32 ops (bit-identical for fp32
    operands).
    """
    if isinstance(w, (PackedWeight, StackedLayer)):
        spec = None
        if bias is not None or softcap is not None or residual is not None:
            spec = _gemm.EpilogueSpec(bias=bias is not None,
                                      softcap=softcap,
                                      residual=residual is not None)
        pw, layer = _pack_and_layer(w)
        p = _gemm.plan_for_packed(_gemm.lead_m(x), pw, epilogue=spec)
        return _gemm.execute(p, x, pw, bias=bias, residual=residual,
                             out_dtype=out_dtype, layer=layer)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if softcap is not None:
        y = softcap * jnp.tanh(y * (1.0 / softcap))
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return y.astype(out_dtype or x.dtype)


def fused_linear(x: jax.Array, w) -> tuple:
    """One GEMM pass over a horizontally fused pack (``pack_fused``, or
    one layer of a stacked one): streams x once, returns the per-part
    outputs of the static split map (Q/K/V; MLA's down-projections).
    Two HBM reads of x deleted per call vs three separate projections.
    """
    pw, layer = _pack_and_layer(w)
    p = _gemm.plan_for_packed(_gemm.lead_m(x), pw)
    return _gemm.split_fused(p, _gemm.execute(p, x, pw, layer=layer))


def grouped_linear(x: jax.Array, w, group_sizes: jax.Array, *,
                   glu: str | None = None, out_dtype=None) -> jax.Array:
    """Rows of ``x [M, K]`` sorted by group through a bank of weights:
    the first ``group_sizes[0]`` rows through the bank's weight 0, the
    next through weight 1, ...; rows past the sizes' sum come back zero.
    ``w`` is a grouped pack (``packing.pack_grouped``, or one layer of a
    stacked one), which dispatches through the plan/execute grouped arm,
    or a raw ``[G, K, N]`` bank, or
    for ``glu`` a raw ``(gate, up)`` pair of banks, which run through the
    ``xla`` backend's grouped run; ``glu`` combines ``act(gate) * up`` on
    the fp32 accumulators (the pack's glu epilogue)."""
    out_dtype = out_dtype or x.dtype
    spec = _gemm.EpilogueSpec(glu=glu) if glu else None
    if isinstance(w, (PackedWeight, StackedLayer)):
        pw, layer = _pack_and_layer(w)
        p = _gemm.plan_for_packed(_gemm.lead_m(x), pw, epilogue=spec)
        return _gemm.execute(p, x, pw, group_sizes=group_sizes,
                             out_dtype=out_dtype, layer=layer)
    bank = jnp.concatenate(w, axis=-1) if glu else w
    return _gemm.get_backend("xla").run_grouped(
        x, bank, group_sizes, out_dtype=out_dtype, epilogue=spec)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    s = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(
        jnp.float32)
    return (x * s).astype(dt)


def yarn_correction_range(cfg, dim: int) -> tuple[int, int]:
    """YaRN's ramp between extrapolated and interpolated frequency pairs:
    the (low, high) pair indices, of ``dim // 2``, where the wavelength
    passes ``beta_fast`` and ``beta_slow`` turns over the original
    context (arXiv:2309.00071 §3.2, as DeepSeek-V2 computes it)."""
    def dim_at(turns):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    low = math.floor(dim_at(cfg.yarn_beta_fast))
    high = math.ceil(dim_at(cfg.yarn_beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(cfg, dim: int) -> jax.Array | None:
    """Per-pair inverse frequencies of ``cfg``'s rotary embedding over
    ``dim`` features: None for plain rope (``rope`` computes it), YaRN's
    blend of extrapolated and interpolated frequencies when
    ``cfg.yarn_factor`` is set.  YaRN's cos/sin scale is
    mscale(factor, mscale) / mscale(factor, mscale_all_dim); configs that
    set both equal (DeepSeek-V2) get 1, the only case this supports."""
    if not cfg.yarn_factor:
        return None
    if cfg.yarn_mscale != cfg.yarn_mscale_all_dim:
        raise NotImplementedError("YaRN with a cos/sin scale other than 1")
    half = dim // 2
    extra = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                               / half)
    inter = extra / cfg.yarn_factor
    low, high = yarn_correction_range(cfg, dim)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         freq: jax.Array | None = None):
    """Rotary embedding.  x: [..., S, H, D] (D even), positions: [..., S].
    ``freq`` ([D/2]) overrides the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    half = d // 2
    if freq is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down, act: str = "silu"):
    """Unfused gated FFN (training / raw-weight path).  The activation
    comes from the shared ``kernels.panel_gemm.act_fn`` table so the
    fused epilogue path computes the identical function."""
    a = _act_fn(act)(linear(x, w_gate))
    return linear(a * linear(x, w_up), w_down)


def swiglu_fused(x, w_gate_up, w_down, act: str = "silu", residual=None):
    """Gated FFN over a horizontally fused gate+up pack: ONE kernel pass
    streams x once, carries two accumulators, and combines
    ``act(gate) * up`` on fp32 in the store step — the [.., 2F]
    intermediate never reaches HBM (glu ``EpilogueSpec``).  ``residual``
    rides the down-projection's epilogue (pre-norm blocks), deleting the
    separate residual-add round-trip too.  Either pack may be one layer
    of a stacked pack (``StackedLayer``)."""
    spec = _gemm.EpilogueSpec(glu=act)
    pw, layer = _pack_and_layer(w_gate_up)
    p = _gemm.plan_for_packed(_gemm.lead_m(x), pw, epilogue=spec)
    h = _gemm.execute(p, x, pw, layer=layer)
    return linear(h, w_down, residual=residual)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x * (1.0 / cap))


def embed_tokens(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy; logits [..., V] fp32-softmaxed."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def init_dense(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else shape[0] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
