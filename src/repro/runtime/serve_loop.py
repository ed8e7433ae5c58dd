"""Serving engine: the paper's deployment, generalized.

The paper's result is a *deployment* discipline: pack every constant
weight once at model load, then every prefill/decode call pays only the
compute loop.  ``Engine`` is that discipline as a class:

  * ``__init__`` — the untimed model-load phase: weights are packed
    (transpose/pad/layout, paper §3.2) and placed with their serving
    shardings; prefill and decode are jitted against the packed tree.
  * ``prefill`` / ``decode`` — per-call compute only; no pack, no
    resharding collective in the step HLO (asserted by the dry-run).
  * per-call mode (``packed=False``) keeps raw weights — the
    cblas/BNNSMatMul analogue of the paper's per-call baseline.

Batched requests run through ``serve`` — real continuous batching
(runtime/batching): a static-shape slot pool whose finished rows are
refilled *mid-generation*, a paged KV cache so refills reuse freed
blocks, and chunked prefill admission interleaved with decode steps.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import gemm as gemm_api
from repro.models import model_zoo, transformer
from repro.obs import recorder as _flight
from repro.obs import spans as _spans
from repro.obs.timing import FencedTimer
from repro.parallel import sharding as Sh


@dataclasses.dataclass
class GenStats:
    """Token accounting for ``generate``.

    ``prefill_tokens`` counts prompt tokens *processed*; ``decode_tokens``
    counts tokens *emitted* — ``rows x max_new_tokens`` for ``generate``
    (the prefill-sampled first token included: generate emits
    ``max_new_tokens`` per row, not ``max_new_tokens - 1``).
    ``fused`` records whether the engine ran the horizontally fused
    QKV / gate-up GEMM path (None: raw-weight engine, fusion n/a);
    ``quant`` the engine's quantized weight format (None: fp32).

    GEMM-dispatch observability (the previously-invisible plan churn):
    ``plan_cache`` snapshots ``gemm.plan_cache_info()`` after the run —
    (hits, misses, maxsize, currsize) — and ``vmem_clamped_plans``
    counts cached plans whose blocks the policy shrank to fit the
    kernel VMEM budget.  ``plan_store`` snapshots the engine's
    persistent plan store counters (``gemm.StoreInfo``: store hits /
    misses / autotuned entries / total entries; None when the engine
    runs without a store) — warm-start observability: a second process
    booting from a populated store shows ``hits == plans needed`` and
    zero autotune/gate work.
    """
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    fused: bool | None = None
    quant: str | None = None
    # sparse-ternary pack observability: mean occupied-group fraction
    # across the engine's quantized packs (None: no quantized packs) and
    # how many crossed to the compressed zero-group layout
    quant_density: float | None = None
    quant_sparse_packs: int = 0
    plan_cache: tuple | None = None
    vmem_clamped_plans: int = 0
    plan_store: tuple | None = None

    @property
    def prefill_tps(self):
        return self.prefill_tokens / max(self.prefill_s, 1e-9)

    @property
    def decode_tps(self):
        return self.decode_tokens / max(self.decode_s, 1e-9)


class Engine:
    def __init__(self, cfg, params, *, mesh=None, max_len: int = 2048,
                 packed: bool = True, block_n: int | None = None,
                 block_k: int | None = None, donate_cache: bool = True,
                 backend: str | None = None, fuse: bool = True,
                 quant: str | None = None,
                 keep_fp32=("head", "embed"),
                 plan_store=None):
        """``backend`` pins this engine's GEMM backend (a registry name
        from ``repro.gemm.list_backends()``); None takes the device's
        default per plan (``gemm.backends.device_default``), except on a
        ``mesh`` of several devices, which pins ``xla``: the panel kernel
        is one device's custom call, and XLA cannot partition it over a
        mesh.  The choice is scoped to this engine's traces — two
        engines with different backends coexist in one process, which the
        old ``REPRO_GEMM_IMPL`` process global could not express.

        ``plan_store`` (a ``gemm.PlanStore`` or a path, loaded
        corruption-tolerantly) scopes a PERSISTENT plan store over this
        engine's pack, trace and warmup paths: every plan they resolve
        is looked up in the store first (a populated store makes a
        fresh process start hot — no analytic re-resolution, no
        bit-exactness gate re-runs, measured-autotuned winners adopted)
        and recorded back on a miss.  The caller persists with
        ``engine.plan_store.save()`` (``launch/serve --plan-store``
        does this at exit); ``launch/autotune`` pre-populates one.

        ``fuse`` (default on) packs same-input projection groups
        horizontally at load — Q/K/V and gate+up each become one fused
        GEMM with an in-kernel epilogue — cutting >= 2 GEMM dispatches
        (and as many re-reads of the activations) per transformer block.
        ``fuse=False`` is the A/B escape hatch; it only applies to the
        packed path (raw engines always run unfused).

        ``quant`` ("int8" | "ternary") serves the model on QUANTIZED
        packed weights (repro.quant): every projection quantizes at load
        except the ``keep_fp32`` roles (default: LM head + embeddings),
        GEMMs run the dequant-fused path, and the error ledger
        tolerance-gates each pack.  Requires ``packed=True``.
        """
        if backend is None and mesh is not None and mesh.size > 1:
            backend = "xla"
        self.cfg = cfg
        self.mesh = mesh
        self.max_len = max_len
        self.packed = packed
        self.backend = backend
        self.fused = bool(packed and fuse)
        self.quant = quant
        self.plan_store = gemm_api.as_plan_store(plan_store)
        store = self.plan_store            # closed over by the step defs
        if backend is not None:
            gemm_api.get_backend(backend)       # fail fast on a typo
        if quant is not None and not packed:
            raise ValueError("quant= is a pack-time format; it requires "
                             "packed=True")

        shard_fn = Sh.activation_sharder(mesh) if mesh is not None else None
        from repro.core.packing import PackedWeight
        if packed and any(isinstance(leaf, PackedWeight) for leaf in
                          jax.tree.leaves(params, is_leaf=lambda x:
                                          isinstance(x, PackedWeight))):
            # already packed at load (model_zoo.build_packed): place it
            # as it is, donating the source so no second copy lingers
            if quant is not None or block_n or block_k:
                raise ValueError("params arrive packed: quant= and block "
                                 "overrides are pack-time choices")
            self.params = params
            if mesh is not None:
                self.params = jax.device_put(
                    params, Sh.param_shardings(params, mesh), donate=True)
        elif packed:
            # ---- model load: pack once (lever 2). Untimed by protocol.
            # The pack-time plan resolutions (pack_blocks per weight)
            # run under the engine's plan store, so a populated store
            # hands back its (possibly measured-autotuned) blocks.
            shardings = None
            with gemm_api.use_plan_store(store):
                if mesh is not None:
                    packed_abs = jax.eval_shape(
                        lambda p: model_zoo.pack_for_inference(
                            cfg, p, block_n=block_n, block_k=block_k,
                            fuse=fuse, quant=quant, keep_fp32=keep_fp32),
                        params)
                    shardings = Sh.param_shardings(packed_abs, mesh)
                self.params = model_zoo.pack_for_inference(
                    cfg, params, block_n=block_n, block_k=block_k,
                    shardings=shardings, fuse=fuse, quant=quant,
                    keep_fp32=keep_fp32)
        else:
            self.params = params
            if mesh is not None:
                self.params = jax.device_put(
                    params, Sh.param_shardings(params, mesh))

        # use_backend wraps the BODY, so it is active while jit traces the
        # step and every gemm plan inside resolves to this engine's backend.
        # Decode bodies additionally trace inside gemm.decode_lane(): every
        # plan they resolve takes the decode policy arm (skinny block_m,
        # forced prepack, split-K scored) and is plan-keyed apart from the
        # prefill plans of the same shapes.  Prefill traces never enter the
        # lane, so their plans and numerics are untouched.
        # obs.manifest_scope wraps each jitted body like use_backend
        # does: the body runs at TRACE time, so every gemm.execute the
        # step dispatches registers its plan under the step's manifest
        # key exactly once per compilation — the flight recorder's
        # answer to "which GEMMs does this step run", with zero
        # per-dispatch cost (docs/observability.md).
        def _prefill(params, inputs):
            with gemm_api.use_backend(backend), \
                    gemm_api.use_plan_store(store), \
                    _flight.manifest_scope(
                        f"prefill_m{inputs.shape[0] * inputs.shape[1]}"):
                return transformer.prefill(cfg, params, inputs,
                                           max_len=max_len,
                                           shard_fn=shard_fn)

        def _decode(params, cache, tokens):
            with gemm_api.use_backend(backend), gemm_api.decode_lane(), \
                    gemm_api.use_plan_store(store), \
                    _flight.manifest_scope("decode"):
                return transformer.decode_step(cfg, params, cache, tokens,
                                               shard_fn=shard_fn)

        donate = (1,) if donate_cache else ()
        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, donate_argnums=donate)

        # ---- continuous-batching steps against the paged cache.  One
        # trace each (shapes are static: [1, C] chunks, [slots, 1]
        # decode), so the GEMM plans they resolve are resolved exactly
        # once — the "plans stay hot" property tests/test_serving.py
        # asserts via plan_cache_info().
        # Greedy selection runs INSIDE the jit (same argmax the host-side
        # _pick applies, so tokens stay bit-identical) — each scheduler
        # tick is then a single device dispatch, which is what lets the
        # pool's decode pipeline match generate's device-side loop.
        # The builder is parameterized on the GEMM backend so the
        # scheduler's degradation ladder can ask for a SECOND step set
        # traced against the ``xla`` reference backend (built lazily on
        # first fallback — see ``_paged_steps``); every registered
        # backend passes the same bit-exactness gate, so a fallback
        # dispatch is token-identical to the primary.
        def _build_paged_steps(step_backend):
            def _paged_prefill(params, pages, page_table, lens, tokens,
                               logit_index, *, page_size):
                with gemm_api.use_backend(step_backend), \
                        gemm_api.use_plan_store(store), \
                        _flight.manifest_scope(
                            f"prefill_chunk_m{tokens.shape[1]}"):
                    cache = {"layers": pages, "page_table": page_table,
                             "lens": lens}
                    logits, cache = transformer.prefill_chunk(
                        cfg, params, cache, tokens, page_size=page_size,
                        logit_index=logit_index, shard_fn=shard_fn)
                    tok = jnp.argmax(logits[0]).astype(jnp.int32)
                    return tok, cache["layers"], cache.get("expert_rows")

            def _decode_tick(params, pages, page_table, lens, write_mask,
                             last_tokens, *, page_size):
                """One pool decode tick: the SINGLE definition both the
                per-tick step and the megastep body trace, so a megastep
                of depth D is bit-identical to D per-tick dispatches."""
                cache = {"layers": pages, "page_table": page_table,
                         "lens": lens, "write_mask": write_mask}
                logits, cache = transformer.paged_decode_step(
                    cfg, params, cache, last_tokens[:, None],
                    page_size=page_size, shard_fn=shard_fn)
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # masked rows (idle / still prefilling) keep their token
                new_last = jnp.where(write_mask, toks, last_tokens)
                return new_last, cache["layers"], cache.get("expert_rows")

            def _paged_decode(params, pages, page_table, lens, write_mask,
                              last_tokens, *, page_size):
                with gemm_api.use_backend(step_backend), \
                        gemm_api.decode_lane(), \
                        gemm_api.use_plan_store(store), \
                        _flight.manifest_scope("decode_step"):
                    return _decode_tick(params, pages, page_table, lens,
                                        write_mask, last_tokens,
                                        page_size=page_size)

            def _paged_megastep(params, pages, page_table, lens,
                                write_mask, last_tokens, n_ticks, *,
                                page_size, max_depth):
                """The fused decode megastep: up to ``max_depth`` decode
                ticks — greedy argmax, paged KV write and next-token
                embed each tick — inside ONE jitted ``lax.fori_loop``,
                so the host dispatches (and syncs) once per ``n_ticks``
                tokens per slot instead of once per token.  ``n_ticks``
                is a TRACED operand (the while-loop trip count), so one
                compilation serves every drain depth 1..max_depth.
                Per-slot lengths advance device-side
                (``lens + t * write_mask``); the scheduler pre-allocates
                the pages the D ticks will write.  Returns (last tokens,
                [max_depth, slots] token history — rows past ``n_ticks``
                are zeros the host never reads, pages).
                """
                with gemm_api.use_backend(step_backend), \
                        gemm_api.decode_lane(), \
                        gemm_api.use_plan_store(store), \
                        _flight.manifest_scope("decode_step"):
                    hist0 = jnp.zeros((max_depth, last_tokens.shape[0]),
                                      jnp.int32)
                    step = write_mask.astype(jnp.int32)
                    rows0 = None
                    if cfg.family == "moe":
                        lo, hi = cfg.held_range
                        rows0 = jnp.zeros((cfg.num_layers
                                           - cfg.first_k_dense, hi - lo),
                                          jnp.int32)

                    def body(t, carry):
                        last, pages, hist, rows = carry
                        last, pages, r = _decode_tick(
                            params, pages, page_table, lens + t * step,
                            write_mask, last, page_size=page_size)
                        hist = jax.lax.dynamic_update_index_in_dim(
                            hist, last, t, 0)
                        if r is not None:
                            rows = rows + r
                        return last, pages, hist, rows

                    last, pages, hist, rows = jax.lax.fori_loop(
                        0, n_ticks, body, (last_tokens, pages, hist0, rows0))
                    return last, hist, pages, rows

            return {
                "prefill": jax.jit(_paged_prefill, donate_argnums=donate,
                                   static_argnames=("page_size",)),
                "decode": jax.jit(_paged_decode, donate_argnums=donate,
                                  static_argnames=("page_size",)),
                "megastep": jax.jit(
                    _paged_megastep, donate_argnums=donate,
                    static_argnames=("page_size", "max_depth")),
            }

        self._build_paged = _build_paged_steps
        self._paged = _build_paged_steps(backend)
        self._paged_fb = None           # lazy xla fallback step set
        # the last paged dispatch's rows routed to each held expert per
        # MoE layer ([MoE layers, held] int32, on the device; None for a
        # dense config): the scheduler's counter
        self.expert_rows = None

    # ------------------------------------------------------------- prefill
    def prefill(self, inputs):
        """inputs: [B, S] int32 (or [B, S, d] stub embeddings).
        Returns (last_logits [B, V], cache)."""
        return self._prefill(self.params, inputs)

    def decode(self, cache, tokens):
        return self._decode(self.params, cache, tokens)

    # ----------------------------------------- paged steps (slot pool)
    # The scheduler's dispatch degradation ladder (batching._guarded)
    # keys off this flag: after a retry on the primary backend fails, it
    # re-dispatches once with ``fallback=True``, which routes through a
    # step set traced against the ``xla`` reference backend.  Bit-exact
    # by the backend gate, so survivors of a backend fault keep
    # token-identical outputs.
    supports_fallback = True

    def _paged_steps(self, fallback: bool):
        if not fallback:
            return self._paged
        if self._paged_fb is None:
            # the primary set IS the xla set when this engine already
            # pins xla; otherwise trace a fresh set against it lazily
            # (first fallback dispatch pays the trace/compile, later
            # ones reuse it)
            self._paged_fb = (self._paged if self.backend == "xla"
                              else self._build_paged("xla"))
        return self._paged_fb

    def prefill_chunk(self, pages, page_table, lens, tokens, logit_index,
                      *, page_size: int, fallback: bool = False):
        """One chunked-prefill admission step: write ``tokens`` [1, C]
        into one slot's pages at its current length.  Returns
        (greedy token for chunk row ``logit_index`` — the prompt's last
        real row on the final chunk — as a device scalar, pages).
        ``fallback=True`` dispatches the xla-backend step set.  The
        step's expert-row counter lands in ``self.expert_rows``."""
        tok, pages, self.expert_rows = self._paged_steps(fallback)[
            "prefill"](self.params, pages, page_table, lens, tokens,
                       logit_index, page_size=page_size)
        return tok, pages

    def decode_step(self, pages, page_table, lens, write_mask,
                    last_tokens, *, page_size: int,
                    fallback: bool = False):
        """One decode step for the whole pool: feeds ``last_tokens``
        [slots] back through the model at per-slot lengths, write-masked
        so idle / still-prefilling slots touch nothing.  Returns
        (next last_tokens [slots] — masked rows unchanged, pages)."""
        last, pages, self.expert_rows = self._paged_steps(fallback)[
            "decode"](self.params, pages, page_table, lens, write_mask,
                      last_tokens, page_size=page_size)
        return last, pages

    def decode_megastep(self, pages, page_table, lens, write_mask,
                        last_tokens, n_ticks: int, *, page_size: int,
                        max_depth: int, fallback: bool = False):
        """``n_ticks`` decode ticks for the whole pool in ONE device
        dispatch (jitted ``lax.fori_loop`` — greedy argmax + paged KV
        write + next-token embed per tick).  The caller must have
        pre-allocated each live slot's pages for ``n_ticks`` more
        tokens; ``n_ticks`` is traced (one compile per ``max_depth``),
        and every tick is bit-identical to a ``decode_step`` dispatch.
        Returns (last tokens [slots], token history [max_depth, slots]
        — rows past ``n_ticks`` are zeros, pages)."""
        last, hist, pages, self.expert_rows = self._paged_steps(fallback)[
            "megastep"](self.params, pages, page_table, lens, write_mask,
                        last_tokens, jnp.asarray(n_ticks, jnp.int32),
                        page_size=page_size, max_depth=max_depth)
        return last, hist, pages

    # ------------------------------------------------------- plan warmup
    def warmup_plans(self, *, batch_slots: int, prefill_chunk: int = 32,
                     page_size: int = 16, num_pages: int | None = None,
                     megastep_depth: int = 1) -> dict:
        """Pre-populate the plan cache AND the jit executable cache for
        a serving configuration, so the first tick of the first request
        pays no trace/plan/gate/compile latency.

        Two layers of warmup: (1) the paged serving steps — the
        chunked-prefill step at the ``bucket_m(prefill_chunk)``
        admission width AND at every chunk-tail width ``bucket_m`` gives
        below it (the bucket ladder, then multiples of 128 up to a wider
        chunk: the widths the scheduler's bucketed final/divergent
        chunks emit —
        a prefix-cache hit starts prefill mid-prompt at arbitrary
        offsets, so every tail bucket is reachable), the ``[slots, 1]``
        decode step, and the megastep when ``megastep_depth > 1`` —
        each driven once, which
        resolves EVERY GEMM plan the configured serving geometry
        dispatches (epilogue-carrying plans included, since the real
        layers trace) and compiles the step executables: the first
        serving tick then pays no trace/plan/compile latency, and
        ``plan_cache_info().misses`` stays flat from the first request
        (asserted in tests/test_decode_lane.py).  (2) A best-effort
        decode-lane plan sweep over every packed weight at each
        ``gemm.DECODE_M_BUCKETS`` width, pre-resolving the PLAIN
        (epilogue-free) decode plans — fused-QKV and attention/output
        projections — for pools and ``generate`` batches of other
        bucketed widths <= 8.  Epilogue-carrying plans at those other
        widths (glu gate-up, fused-residual down-projection, softcap
        head) still resolve on their first dispatch there, as does each
        new shape's jit compile.  The pool geometry must match the
        later ``serve`` call (``num_pages=None`` = the dense-equivalent
        default).  Returns ``{step name: compile seconds}`` plus
        ``decode_bucket_plans`` (count pre-resolved) and a
        ``plan_cache`` snapshot.
        """
        if self.cfg.modality != "text":
            raise NotImplementedError("warmup covers the token-serving "
                                      "paged steps")
        from repro.runtime import kv_cache as KV
        chunk = gemm_api.bucket_m(prefill_chunk)
        n_pages = (num_pages if num_pages is not None
                   else batch_slots * (self.max_len // page_size))
        # dummy pool, driven through the REAL call path: AOT
        # lower().compile() does not seed the executables the call path
        # uses, so warmup dispatches each step once on zeros (page
        # tables all -1: every KV write drops, outputs are discarded;
        # the dummy pages are donated away step to step)
        pages = {
            name: jnp.zeros(
                (self.cfg.num_layers, n_pages, page_size, *feat), dtype)
            for name, (feat, dtype) in KV.leaf_specs_for(self.cfg).items()}
        pps = self.max_len // page_size
        i32 = jnp.int32
        timings = {}
        # admission width PLUS every chunk-tail width below it: the
        # scheduler dispatches a prompt's final chunk — and the whole
        # divergent remainder after a prefix-cache hit, which starts
        # mid-prompt at an arbitrary offset — at gemm.bucket_m(rem), so
        # the tail widths the pool can emit are bucket_m(1..chunk): the
        # bucket ladder, then multiples of 128 up to a wider chunk.
        # Driving each once keeps serving from resolving a plan with the
        # prefix cache on, and nothing compiles while serving.
        widths = sorted({gemm_api.bucket_m(r) for r in range(1, chunk)}
                        - {chunk})
        for w in widths + [chunk]:
            t0 = time.perf_counter()
            tok, pages = self.prefill_chunk(
                pages, jnp.full((1, pps), -1, i32), jnp.zeros((1,), i32),
                jnp.zeros((1, w), i32), jnp.asarray(0, i32),
                page_size=page_size)
            jax.block_until_ready(tok)
            key = ("prefill_chunk" if w == chunk
                   else f"prefill_chunk_m{w}")
            timings[key] = time.perf_counter() - t0
        table = jnp.full((batch_slots, pps), -1, i32)
        lens = jnp.zeros((batch_slots,), i32)
        mask = jnp.zeros((batch_slots,), bool)
        last = jnp.zeros((batch_slots,), i32)
        t0 = time.perf_counter()
        last, pages = self.decode_step(pages, table, lens, mask, last,
                                       page_size=page_size)
        jax.block_until_ready(last)
        timings["decode_step"] = time.perf_counter() - t0
        if megastep_depth > 1:
            t0 = time.perf_counter()
            last, _, pages = self.decode_megastep(
                pages, table, lens, mask, last, 1, page_size=page_size,
                max_depth=megastep_depth)
            jax.block_until_ready(last)
            timings["decode_megastep"] = time.perf_counter() - t0
        del pages
        # decode-bucket plan ladder: pre-resolve the decode-lane plan of
        # every packed weight at each bucket width
        from repro.core.packing import PackedWeight
        packs = [leaf for leaf in jax.tree.leaves(
            self.params,
            is_leaf=lambda x: isinstance(x, PackedWeight))
            if isinstance(leaf, PackedWeight)]
        n_plans = 0
        with gemm_api.use_backend(self.backend), \
                gemm_api.use_plan_store(self.plan_store):
            for bucket in gemm_api.DECODE_M_BUCKETS:
                for pw in packs:
                    gemm_api.plan_for_packed(bucket, pw, decode=True)
                    n_plans += 1
        timings["decode_bucket_plans"] = n_plans
        timings["plan_cache"] = gemm_api.plan_cache_info()
        if self.plan_store is not None:
            timings["plan_store"] = self.plan_store.info()
        return timings

    def _quant_pack_stats(self):
        """(mean occupied-group density, sparse pack count) over the
        engine's quantized packs — the ServeStats/GenStats quant area."""
        if not (self.packed and self.quant):
            return None, 0
        from repro.quant.formats import (QuantizedPackedWeight,
                                         SparseTernaryPackedWeight)
        packs = [leaf for leaf in jax.tree.leaves(
            self.params,
            is_leaf=lambda x: isinstance(x, QuantizedPackedWeight))
            if isinstance(leaf, QuantizedPackedWeight)]
        if not packs:
            return None, 0
        dens = [float(getattr(q, "density", 1.0)) for q in packs]
        sparse = sum(1 for q in packs
                     if isinstance(q, SparseTernaryPackedWeight))
        return sum(dens) / len(dens), sparse

    # ------------------------------------------------------------ generate
    def generate(self, prompts, max_new_tokens: int, *,
                 greedy: bool = True, seed: int = 0,
                 stats: GenStats | None = None):
        """Greedy/sampled continuation.  prompts: [B, S0] int32.
        Returns tokens [B, max_new_tokens]."""
        stats = stats if stats is not None else GenStats()
        stats.fused = self.fused if self.packed else None
        stats.quant = self.quant if self.packed else None
        stats.quant_density, stats.quant_sparse_packs = \
            self._quant_pack_stats()
        b, s0 = prompts.shape[0], prompts.shape[1]
        # phase timing through the obs fenced timer: both phases fence
        # (generate's numbers were always execution times — the fence
        # here is the same block_until_ready the bare pairs used to
        # wrap, now attributed explicitly; see docs/observability.md)
        with _spans.span("generate_prefill", step=f"prefill_m{b * s0}",
                         rows=b, tokens=b * s0), \
                FencedTimer(fence=True) as t:
            logits, cache = self.prefill(prompts)
            t.fence(logits)
        stats.prefill_s += t.elapsed_s
        stats.prefill_tokens += b * s0

        key = jax.random.key(seed)
        out = []
        tok = self._pick(logits, key, greedy)
        out.append(tok)
        with _spans.span("generate_decode", step="decode", rows=b,
                         ticks=max_new_tokens - 1), \
                FencedTimer(fence=True) as t:
            for i in range(max_new_tokens - 1):
                key, sub = jax.random.split(key)
                logits, cache = self.decode(cache, tok[:, None])
                tok = self._pick(logits, sub, greedy)
                out.append(tok)
            t.fence(tok)
        stats.decode_s += t.elapsed_s
        stats.decode_tokens += b * max_new_tokens      # emitted per row
        stats.plan_cache = gemm_api.plan_cache_info()
        stats.vmem_clamped_plans = gemm_api.vmem_clamped_count()
        if self.plan_store is not None:
            stats.plan_store = self.plan_store.info()
        return jnp.stack(out, axis=1), stats

    @staticmethod
    def _pick(logits, key, greedy):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    # ------------------------------------------------ continuous batching
    def serve(self, requests: list[np.ndarray], *, batch_slots: int,
              max_new_tokens, prefill_chunk: int = 32,
              page_size: int = 16, num_pages: int | None = None,
              check_invariants: bool = False,
              sync_per_step: bool = False, megastep_depth: int = 1,
              prefix_cache: bool = False,
              watchdog_factor: float | None = None, shutdown=None,
              ttft_budget_s=None, total_budget_s=None):
        """Real continuous batching (greedy): slot refill mid-generation,
        paged KV cache, chunked prefill admission — runtime/batching.

        requests: list of int32 prompt arrays, served at their true
        lengths (no padding to a global prompt_len).  max_new_tokens:
        int or per-request sequence.  ``megastep_depth`` > 1 drains
        decode through the fused megastep (up to D device-side ticks
        per host dispatch).  ``prefix_cache=True`` turns on the
        cross-request prefix cache (runtime/prefix_cache): requests
        whose prompts share a cached prefix skip straight to the
        divergent token, reusing refcounted KV pages (COW-forked at
        the divergence page); ``ServeStats.prefix`` carries the
        hit/evict/COW counters.  Returns (list of generated-token
        arrays in request order — None for requests that ended in a
        non-DONE terminal state, whose ``RequestOutcome`` lives in
        ``stats.outcomes`` — and batching.ServeStats).  Outputs are
        bit-identical to per-request greedy ``generate`` at every
        megastep depth, with the cache on or off.

        Fault-isolation knobs (docs/serving.md "Failure model"):
        ``watchdog_factor`` arms the straggler watchdog over scheduler
        ticks; ``shutdown`` (a ``GracefulShutdown``) drains the run on
        SIGTERM; ``ttft_budget_s`` / ``total_budget_s`` set per-request
        deadlines (scalar or per-request sequence, enforced at tick
        boundaries — missed deadlines end TIMED_OUT, not raised).
        """
        from repro.runtime.batching import ContinuousBatchingScheduler
        sched = ContinuousBatchingScheduler(
            self, batch_slots=batch_slots, prefill_chunk=prefill_chunk,
            page_size=page_size, num_pages=num_pages,
            check_invariants=check_invariants,
            sync_per_step=sync_per_step, megastep_depth=megastep_depth,
            prefix_cache=prefix_cache, watchdog_factor=watchdog_factor,
            shutdown=shutdown)
        outs, stats = sched.run(requests, max_new_tokens,
                                ttft_budget_s=ttft_budget_s,
                                total_budget_s=total_budget_s)
        stats.fused = self.fused if self.packed else None
        stats.quant = self.quant if self.packed else None
        stats.quant_density, stats.quant_sparse_packs = \
            self._quant_pack_stats()
        stats.plan_cache = gemm_api.plan_cache_info()
        stats.vmem_clamped_plans = gemm_api.vmem_clamped_count()
        if self.plan_store is not None:
            stats.plan_store = self.plan_store.info()
        return outs, stats
