"""End-to-end serving driver — the paper's deployment scenario.

Loads a model, packs every projection weight once (untimed model-load
phase, paper §3.2), then serves a queue of mixed-length requests through
continuous batching (``serve``: slot refill mid-generation, paged KV
cache, chunked prefill admission; docs/serving.md), and reports useful
generated tokens/s plus per-request latency percentiles for the pool —
the framework-native analogue of the paper's llama.cpp integration
(§4.7), where the pre-packed path lifted full-forward throughput
291→420 tok/s.  These are host timings of a reduced model, not a
measurement of chip speed (that is chipbench/).

Run: PYTHONPATH=src python examples/serve_batched.py [--arch deepseek-7b]
     [--requests 12] [--prompt-len 64] [--max-new 16] [--batch-slots 4]
"""
import argparse
import time

import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo
from repro.runtime.serve_loop import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b",
                    choices=model_zoo.list_archs())
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--no-fusion", action="store_true",
                    help="serve with horizontal QKV/gate-up fusion OFF "
                         "(A/B the fused GEMM path in place)")
    args = ap.parse_args()

    cfg = model_zoo.reduced_config(model_zoo.get_config(args.arch))
    if cfg.modality != "text":
        raise SystemExit("pick a text arch for the serving demo")
    mesh = make_host_mesh()
    params = model_zoo.build(cfg)
    rng = np.random.default_rng(0)
    requests = [rng.integers(0, cfg.vocab_size,
                             rng.integers(8, args.prompt_len + 1))
                .astype(np.int32) for _ in range(args.requests)]
    # heavy-tailed generation budgets: mostly short, some long
    mns = [int(rng.integers(args.max_new // 2, args.max_new + 1))
           if rng.random() < 0.25 else int(rng.integers(2, 6))
           for _ in range(args.requests)]
    useful = sum(mns)
    max_len = args.prompt_len + args.max_new
    max_len += (-max_len) % args.page_size

    t0 = time.perf_counter()
    eng = Engine(cfg, params, mesh=mesh, max_len=max_len, packed=True,
                 fuse=not args.no_fusion)
    print(f"model load + pack (untimed): {time.perf_counter() - t0:.2f}s  "
          f"[fused GEMMs {'off' if args.no_fusion else 'on'}]")

    # warm the traces (compile is part of model load, not serving)
    eng.serve(requests[:2], batch_slots=args.batch_slots, max_new_tokens=2,
              prefill_chunk=args.prefill_chunk, page_size=args.page_size)

    t0 = time.perf_counter()
    outs, stats = eng.serve(requests, batch_slots=args.batch_slots,
                            max_new_tokens=mns,
                            prefill_chunk=args.prefill_chunk,
                            page_size=args.page_size)
    t_new = time.perf_counter() - t0
    print(f"{'continuous batching':24s} {useful / t_new:8,.0f} useful "
          f"tok/s  ({len(outs)} requests, {useful} tokens, {t_new:.2f}s)")
    qw95 = stats.percentile("queue_wait_s", 95) * 1e3
    tf95 = stats.percentile("ttft_s", 95) * 1e3
    print(f"  queue wait p95 {qw95:.1f} ms | TTFT p95 {tf95:.1f} ms "
          f"(dispatch-side; pass sync_per_step=True for exact latency)")


if __name__ == "__main__":
    main()
