"""Smoke run of the serving path on one TPU chip, at published widths.

    python chip_smoke.py [--seed N]

Builds stablelm-3b at its published widths and depth (fp32 weights,
random from ``--seed``, packed at load), serves eight seeded requests
through ``Engine.serve`` on the Pallas panel kernels (continuous
batching, paged KV), and checks the result:

* every request ends DONE with the tokens it asked for;
* the scheduler's degradation ladder never fired: no backend fallback,
  no dispatch retry, no degradation;
* the compiled prefill step holds the Pallas kernel (``tpu_custom_call``);
* the same requests served again without per-step host syncs (fully
  asynchronous dispatch) emit the same tokens;
* one prefill's logits agree, within ``LOGIT_RTOL`` of the largest
  logit, with an XLA-backend prefill of the same packed weights at the
  process's default matmul precision (the one the Pallas step ran at)
  and with one at ``highest`` precision.

It also reports whether the compiled fp32 panel kernel is bit-identical
to ``kernels/ref.gemm_blocked`` at one prefill shape.  Every phase runs
and prints; then any failed check raises, so the exit code is nonzero.
Without a TPU it exits nonzero before it builds anything.  Uses one
chip, even on a host with several.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Bound on max |logit(pallas) - logit(xla)| relative to the largest
# |logit|, for both references.  Activations are bf16, so any change in
# one GEMM's rounding (default precision rounds fp32 operands to bf16 on
# the MXU; ``highest`` does not; two backends accumulate in different
# orders) grows over 32 layers to a floor: on a TPU v5e, XLA prefills of
# these weights at "high" and "highest" precision differ by 2.3e-2 of
# the largest logit, and the Pallas step sits 1.9e-2 from XLA at either
# its own or ``highest`` precision.  The bound sits at about twice that
# floor.  Kernels planted with a dropped K block read far above it on
# the same chip: 1.03 when every non-GLU GEMM skips its last block, 0.35
# when only w_down skips its last 256 of 6912 rows.
LOGIT_RTOL = 5e-2

ARCH = "stablelm-3b"
N_REQUESTS = 8
PROMPT_LENS = (32, 512)          # inclusive range of prompt lengths
MAX_NEW = 16
SLOTS = 4
PREFILL_CHUNK = 128
PAGE_SIZE = 16
MAX_LEN = 1024
BITEXACT_SHAPE = (128, 2560, 7680)   # (M, K, N): the fused QKV at S=128


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    with jax.default_device(dev):
        _smoke(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1}}))
    return 0


def _smoke(dev, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import gemm
    from repro.kernels import panel_gemm as K
    from repro.kernels import ref
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_zoo, transformer
    from repro.runtime import kv_cache as KV
    from repro.runtime.batching import RequestState
    from repro.runtime.serve_loop import Engine

    failed = []

    def _check(ok: bool, what: str) -> None:
        if not ok:
            failed.append(what)
            print(f"CHECK FAILED: {what}")

    print(f"device: {dev.device_kind} ({dev.platform}); compile cache "
          f"{enable_compile_cache()}")
    cfg = model_zoo.get_config(ARCH)
    print(f"model: {cfg.name} at published widths: {cfg.num_layers} "
          f"layers, d_model {cfg.d_model}, {cfg.num_heads} heads x "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.param_dtype} weights, {cfg.compute_dtype} activations; "
          f"no cuts")

    # ---- load: weights built straight into the packed layout
    t0 = time.perf_counter()
    params = model_zoo.build_packed(cfg, seed)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, max_len=MAX_LEN, packed=True,
                 backend="pallas", fuse=True)
    del params
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(eng.params))
    print(f"load+pack: {time.perf_counter() - t0:.2f}s "
          f"({n_bytes / 2**30:.2f} GiB of packed weights)")

    # ---- compile: every serving step the run will dispatch
    t0 = time.perf_counter()
    wt = eng.warmup_plans(batch_slots=SLOTS, prefill_chunk=PREFILL_CHUNK,
                          page_size=PAGE_SIZE)
    steps = {k: round(v, 2) for k, v in wt.items()
             if isinstance(v, float)}
    print(f"compile/warmup: {time.perf_counter() - t0:.2f}s {steps}")

    # ---- serve: seeded requests through the continuous-batching pool
    rng = np.random.default_rng(seed)
    lens = [PROMPT_LENS[0], PROMPT_LENS[1]] + [
        int(n) for n in rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1,
                                     N_REQUESTS - 2)]
    reqs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]
    outs, st = eng.serve(reqs, batch_slots=SLOTS, max_new_tokens=MAX_NEW,
                         prefill_chunk=PREFILL_CHUNK, page_size=PAGE_SIZE,
                         sync_per_step=True)
    states = [st.outcomes[i].state for i in range(N_REQUESTS)]
    print(f"served: {N_REQUESTS} requests (prompts {min(lens)}-"
          f"{max(lens)} tokens, {sum(lens)} in all), {st.decode_tokens} "
          f"tokens in {st.wall_s:.2f}s wall; TTFT p50 "
          f"{st.percentile('ttft_s', 50) * 1e3:.1f} ms; outcomes "
          f"{[s.value for s in states]}")
    print(f"degradation ladder: {st.backend_fallbacks} backend fallbacks, "
          f"{st.dispatch_retries} dispatch retries, degraded "
          f"{st.degraded or 0}")
    _check(all(s is RequestState.DONE for s in states),
           f"outcomes {[s.value for s in states]}")
    _check(all(o is not None and len(o) == MAX_NEW for o in outs),
           f"emitted {[None if o is None else len(o) for o in outs]} "
           f"tokens, wanted {MAX_NEW} each")
    _check(st.decode_tokens == N_REQUESTS * MAX_NEW,
           f"{st.decode_tokens} tokens emitted")
    _check(st.backend_fallbacks == 0 and st.dispatch_retries == 0
           and not st.degraded,
           "the degradation ladder fired: part of the run left the kernel")
    outs_async, st_async = eng.serve(
        reqs, batch_slots=SLOTS, max_new_tokens=MAX_NEW,
        prefill_chunk=PREFILL_CHUNK, page_size=PAGE_SIZE)
    same = all(a is not None and np.array_equal(a, b)
               for a, b in zip(outs_async, outs))
    print(f"served again without per-step syncs: {st_async.decode_tokens} "
          f"tokens in {st_async.wall_s:.2f}s wall, "
          f"{'identical' if same else 'DIFFERENT'} tokens")
    _check(same, "asynchronous dispatch changed the served tokens")

    # ---- the kernel is in the compiled prefill step
    i32 = jnp.int32
    pps = MAX_LEN // PAGE_SIZE
    n_pages = SLOTS * pps
    pages = {name: jax.ShapeDtypeStruct(
                 (cfg.num_layers, n_pages, PAGE_SIZE, *feat), dtype)
             for name, (feat, dtype) in KV.leaf_specs_for(cfg).items()}
    prefill_step = eng._paged_steps(fallback=False)["prefill"]
    hlo = prefill_step.lower(
        eng.params, pages, jax.ShapeDtypeStruct((1, pps), i32),
        jax.ShapeDtypeStruct((1,), i32),
        jax.ShapeDtypeStruct((1, gemm.bucket_m(PREFILL_CHUNK)), i32),
        jax.ShapeDtypeStruct((), i32), page_size=PAGE_SIZE
    ).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    print(f"compiled prefill step: {n_kernels} tpu_custom_call sites")
    _check(n_kernels > 0, "no Pallas kernel in the compiled prefill step")

    # ---- logits: Pallas step vs XLA at default and highest precision
    prompt = jnp.asarray(reqs[1][:PREFILL_CHUNK][None], i32)
    got, cache = eng.prefill(prompt)
    # room for the XLA reference: at default precision it holds a bf16
    # copy of the layer weights (4.7 GiB) while it runs
    del cache

    # A function of its own, traced only under the xla backend: JAX's
    # trace cache is keyed on the function and the matmul precision, not
    # on gemm.use_backend, so re-jitting a function first traced for
    # Pallas would silently re-run the Pallas trace.
    def xla_prefill(params, tokens):
        # the engine's max_len: a different cache length changes the
        # attention chunking, and over 32 layers that alone moves the
        # logits by the bf16 noise floor
        return transformer.prefill(cfg, params, tokens,
                                   max_len=MAX_LEN)[0]

    got = np.asarray(got, np.float32)
    _check(got.shape == (1, cfg.vocab_size), f"logit shape {got.shape}")
    _check(bool(np.isfinite(got).all()), "non-finite Pallas logits")
    # None: no precision context, as the Pallas step ran
    for precision in (None, "highest"):
        with gemm.use_backend("xla"), \
                jax.default_matmul_precision(precision):
            want = np.asarray(jax.jit(xla_prefill)(eng.params, prompt),
                              np.float32)
        diff = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        name = precision or "the default"
        print(f"logits ({prompt.shape[1]}-token prefill) vs xla at {name} "
              f"precision: max|diff| {diff:.4e}, max|logit| {scale:.4e}, "
              f"ratio {diff / scale:.4e} (limit {LOGIT_RTOL:g}); argmax "
              f"pallas {int(got.argmax())} xla {int(want.argmax())}"
              + ("; bit-identical" if diff == 0 else ""))
        _check(diff <= LOGIT_RTOL * scale,
               f"logits differ from xla at {name} precision by "
               f"{diff:.4e} > {LOGIT_RTOL:g} x {scale:.4e}")

    # ---- is the compiled fp32 kernel bit-identical to the blocked oracle?
    m, k, n = BITEXACT_SHAPE
    p = gemm.plan(m, n, k, backend="pallas")
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    y = np.asarray(K.panel_gemm(x, w, block_m=p.block_m,
                                block_n=p.block_n, block_k=p.block_k))
    oracle = jax.jit(lambda a, b: ref.gemm_blocked(a, b, p.block_k))
    report = []
    for prec in ("default", "highest"):
        with jax.default_matmul_precision(prec):
            o = np.asarray(oracle(x, w))
        same = bool(np.array_equal(y.view(np.uint32), o.view(np.uint32)))
        report.append(f"{prec}: {'bit-identical' if same else 'differs'}"
                      f" (max|diff| {float(np.max(np.abs(y - o))):.3e})")
    print(f"fp32 panel kernel {m}x{k}x{n} blocks "
          f"({p.block_m},{p.block_n},{p.block_k}) vs ref.gemm_blocked at "
          f"matmul precision " + "; ".join(report))

    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    print("peak_bytes_in_use: "
          + (f"{peak} ({peak / 2**30:.2f} GiB)" if peak is not None
             else "not reported"))
    if failed:
        raise RuntimeError(f"chip smoke failed {len(failed)} checks: "
                           + "; ".join(failed))


if __name__ == "__main__":
    sys.exit(main())
