"""Offline (block_n, block_k) sweep — the paper's §3.3 panel-sizing sweep.

The paper sweeps Nc in {64..512} x Kc in {256..2048}, REJECTS any candidate
that is not bit-identical to Accelerate, and deploys the single pair that
wins all twelve shapes.  Same protocol here:

  1. candidates ranked by the napkin-math model in core/scheduler.plan()
     (predicted max(compute, memory) time / occupancy, VMEM-gated);
  2. each surviving candidate is executed (compiled on a TPU, in the
     Pallas interpreter elsewhere) on a reduced shape and must be
     BIT-IDENTICAL to the blocked oracle at its own block_k
     (kernels/ref.gemm_blocked) — any accumulator-carry bug is an
     instant reject;
  3. one (block_n, block_k) pair is deployed uniformly across shapes
     (the paper: "it is not tuned against any one comparison").

The deployed defaults in kernels/panel_gemm.py record the result.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import bitexact, scheduler
from repro.obs import spans as _spans

BLOCK_N_CANDIDATES = (128, 256, 512, 1024)
BLOCK_K_CANDIDATES = (128, 256, 512, 1024, 2048)


@dataclasses.dataclass
class SweepResult:
    block_n: int
    block_k: int
    t_pred: float
    vmem: int
    bit_exact: bool


def sweep(shapes, *, block_m: int = 128, num_cores: int = 1,
          validate: bool = True, reduced: int = 256) -> list[SweepResult]:
    """Rank (block_n, block_k) pairs over a set of (M, N, K) shapes.

    ``shapes``: iterable of (m, n, k).  Returns candidates sorted by total
    predicted time across all shapes (the all-twelve-shapes criterion),
    with non-bit-exact candidates removed when ``validate``.
    """
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.panel_gemm import gate_interpret, panel_gemm

    rng = np.random.default_rng(0)
    out = []
    for bn in BLOCK_N_CANDIDATES:
        for bk in BLOCK_K_CANDIDATES:
            t = 0.0
            ok = True
            for (m, n, k) in shapes:
                p = scheduler.plan(m, n, k, block_m=block_m, block_n=bn,
                                   block_k=bk, num_cores=num_cores)
                if not p.vmem_ok:
                    ok = False
                    break
                t += p.t_pred
            if not ok:
                continue
            exact = True
            if validate:
                m_r = block_m
                k_r, n_r = 2 * bk, bn   # smallest shape with a real K-carry
                x = jnp.asarray(rng.standard_normal((m_r, k_r)),
                                dtype=jnp.float32)
                w = jnp.asarray(rng.standard_normal((k_r, n_r)),
                                dtype=jnp.float32)
                y = panel_gemm(x, w, block_m=block_m, block_n=bn, block_k=bk,
                               interpret=gate_interpret())
                exact = bitexact.bit_identical(
                    np.asarray(y), np.asarray(ref.gemm_blocked(x, w, bk)))
            out.append(SweepResult(bn, bk, t, scheduler.vmem_bytes(
                block_m, bn, bk), exact))
    out = [r for r in out if r.bit_exact]
    out.sort(key=lambda r: r.t_pred)
    return out


def deployed_pair(shapes, **kw) -> tuple[int, int]:
    """The single uniform pair the sweep deploys (paper: Nc=64, Kc=2048)."""
    best = sweep(shapes, **kw)[0]
    return best.block_n, best.block_k


# ====================================================================
# Measured autotune — the per-shape, per-format sweep the persistent
# plan store is populated with.
#
# The paper's sharpest deployment finding is that mis-tuning the single
# column-panel width costs ~2x — an argument for MEASURING candidate
# plans rather than trusting the analytic model above.  Protocol (the
# paper's §4.1 timing discipline):
#
#   1. the analytic ``scheduler.plan`` prediction PRUNES the candidate
#      block triples (and decode split-K counts) to a short list that
#      always includes the analytic winner;
#   2. each candidate is executed for real — jitted, block_until_ready,
#      INTERLEAVED reps so machine drift cancels across candidates,
#      per-candidate median;
#   3. the measured winner must beat the analytic plan by more than the
#      noise tolerance or it is re-measured with more reps
#      (retry-on-noise: re-measure, never fudge), and after the retries
#      the ANALYTIC plan is kept — the mis-tune guard: a plan is never
#      deployed on a measurement that is not above noise;
#   4. the winner must pass the existing bit-exactness gate
#      (``gemm.validate_plan``) before it is committed; a gate-failing
#      candidate is discarded and the next-best stands.
#
# The committed winner lands in the ACTIVE plan store under the
# policy-position key (no block overrides), so a later ``gemm.plan(m,
# n, k, ...)`` — in this process or any warm-started one — adopts it.
# ====================================================================

# A measured advantage below this fraction of the analytic plan's time
# is treated as timer noise: re-measure, and ultimately keep analytic.
NOISE_RTOL = 0.05


@dataclasses.dataclass
class MeasuredPlan:
    """Result of one :func:`measured_autotune` call."""
    plan: "object"               # the deployed GemmPlan (gate-passed)
    t_analytic: float            # measured seconds/call, analytic plan
    t_measured: float            # measured seconds/call, deployed plan
    analytic: bool               # deployed == the analytic choice
    retries: int                 # noise re-measure rounds taken
    candidates: int              # candidates actually timed
    rejected: int                # candidates the bit-exact gate refused
    committed: bool              # landed in the active plan store

    @property
    def speedup(self) -> float:
        """Measured throughput ratio of deployed over analytic (>= 1.0
        by the mis-tune guard, == 1.0 when analytic is kept)."""
        return self.t_analytic / max(self.t_measured, 1e-12)

    def row(self) -> dict:
        p = self.plan
        return {
            "blocks": f"{p.block_m}x{p.block_n}x{p.block_k}",
            "split_k": p.split_k,
            "t_analytic_ms": round(self.t_analytic * 1e3, 5),
            "t_measured_ms": round(self.t_measured * 1e3, 5),
            "tuned_vs_analytic": round(self.speedup, 4),
            "analytic_kept": self.analytic,
            "retries": self.retries,
            "candidates": self.candidates,
            "gate_rejected": self.rejected,
            "committed": self.committed,
        }


def _candidate_plans(p0, m, n, k, *, dtype, backend, num_cores,
                     epilogue, weight_format, decode, max_candidates,
                     density_bucket=-1):
    """Analytic pruning: score block-triple (x decode split-K)
    candidates with the scheduler model, keep the ``max_candidates``
    best plus the analytic winner itself.  Every candidate resolves
    through ``gemm.plan`` with explicit blocks, so the VMEM fit and
    split validation run exactly as they would at dispatch."""
    from repro import gemm
    from repro.core import packing
    from repro.gemm.policy import DECODE_SPLIT_K_CANDIDATES

    bns = sorted({packing.fit_block(n, c) for c in BLOCK_N_CANDIDATES})
    bks = sorted({packing.fit_block(k, c) for c in BLOCK_K_CANDIDATES})
    if density_bucket >= 0:
        # sparse arm: the group-granular walk ignores block_k, and one
        # block_k keeps every candidate's pack (and padded K, hence the
        # synthetic weight's group structure) identical — the sweep's
        # real lever is the column-panel width
        bks = [p0.block_k]
    splits = (DECODE_SPLIT_K_CANDIDATES if (decode and p0.split_k > 1)
              else (p0.split_k,))
    scored = []
    for bn in bns:
        for bk in bks:
            k_pad = max(bk, -(-k // bk) * bk)
            for s in splits:
                if s > 1 and (k_pad % s or (k_pad // s) % bk):
                    continue       # split does not cut this padded K
                p = scheduler.plan(m, n, k, block_m=p0.block_m,
                                   block_n=bn, block_k=bk,
                                   num_cores=num_cores, split_k=s)
                if not p.vmem_ok:
                    continue
                scored.append((p.t_pred, bn, bk, s))
    scored.sort()
    plans, seen = [], set()
    triples = [(p0.block_n, p0.block_k, p0.split_k)]   # analytic first
    triples += [(bn, bk, s) for _, bn, bk, s in scored[:max_candidates]]
    for bn, bk, s in triples:
        try:
            p = gemm.plan(m, n, k, dtype=dtype, backend=backend,
                          num_cores=num_cores, block_m=p0.block_m,
                          block_n=bn, block_k=bk, pack=p0.pack,
                          epilogue=epilogue, weight_format=weight_format,
                          decode=decode, split_k=s,
                          density_bucket=density_bucket)
        except ValueError:
            continue          # split does not cut this K; not a candidate
        tr = (p.block_m, p.block_n, p.block_k, p.split_k)
        if tr in seen:
            continue
        seen.add(tr)
        plans.append(p)
    return plans


def _time_interleaved(runs, *, trials: int, warmup: int) -> list[float]:
    """Median seconds/call per run, interleaved reps (drift cancels)."""
    import time

    import jax

    for fn in runs:
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn())
    ts: list[list[float]] = [[] for _ in runs]
    for _ in range(trials):
        for i, fn in enumerate(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[i].append(time.perf_counter() - t0)
    return [float(np.median(v)) for v in ts]


def measured_autotune(m: int, n: int, k: int, *, dtype=None,
                      backend: str | None = None,
                      weight_format: str = "fp32", epilogue=None,
                      decode: bool = False, num_cores: int | None = None,
                      trials: int = 5, warmup: int = 2,
                      max_retries: int = 3, noise_rtol: float = NOISE_RTOL,
                      max_candidates: int = 4, commit: bool = True,
                      seed: int = 0,
                      density_bucket: int = -1) -> MeasuredPlan:
    """Measure candidate plans for one ``[m,k] @ [k,n]`` dispatch and
    deploy the winner (module docstring has the full protocol).

    The candidate resolutions run under ``gemm.no_plan_store()`` so the
    sweep never reads the store it is populating; with ``commit=True``
    and a store active, the gate-passed winner is committed under the
    policy-position store key (and adopted by this process's in-memory
    plan cache), with its measured time as provenance.

    ``density_bucket >= 0`` sweeps the SPARSE-ternary arm
    (``weight_format='ternary'`` only): the synthetic weight zeroes
    whole GROUP_K K-groups to land in exactly that bucket, packs through
    the compressed layout, and the winner commits under the
    bucket-keyed store position a later ``plan_for_packed`` on a
    same-bucket pack will ask.
    """
    import jax
    import jax.numpy as jnp

    from repro import gemm
    from repro.core import packing
    from repro.gemm import plan_store as _ps
    from repro.gemm import policy as _pol

    dtype = jnp.float32 if dtype is None else dtype
    num_cores = _pol.DEFAULT_NUM_CORES if num_cores is None else num_cores
    with _ps.no_plan_store():
        p0 = gemm.plan(m, n, k, dtype=dtype, backend=backend,
                       num_cores=num_cores, epilogue=epilogue,
                       weight_format=weight_format, decode=decode,
                       density_bucket=density_bucket)
        cands = _candidate_plans(
            p0, m, n, k, dtype=dtype, backend=backend,
            num_cores=num_cores, epilogue=epilogue,
            weight_format=weight_format, decode=decode,
            max_candidates=max_candidates, density_bucket=density_bucket)

    rng = np.random.default_rng(seed)
    quant = weight_format != "fp32"
    sparse = density_bucket >= 0
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w_np = (rng.standard_normal((k, n)) * 0.02).astype(np.float32)
    if sparse:
        # land the synthetic weight in EXACTLY the requested bucket:
        # zero whole GROUP_K K-groups of the candidate-shared padded K
        # (sparse candidates pin one block_k, so one weight serves all)
        from repro.quant.formats import GROUP_K
        k_pad = -(-k // p0.block_k) * p0.block_k
        kg = k_pad // GROUP_K
        pad_zero = kg - (-(-k // GROUP_K))      # all-pad tail groups
        z = max(0, -(-density_bucket * kg // 10) - pad_zero)
        for g in range(z):
            lo, hi = g * GROUP_K, min((g + 1) * GROUP_K, k)
            w_np[lo:hi] = 0.0
        got = int((z + pad_zero) / kg * 10)     # density_bucket_of's math
        if got != density_bucket:
            raise ValueError(
                f"density_bucket={density_bucket} is unreachable for "
                f"K={k} at block_k={p0.block_k} ({kg} groups, {pad_zero} "
                f"already zero from padding -> bucket {got})")
    w = jnp.asarray(w_np)

    def make_run(p):
        # measure the plan's own deployment: a prepack plan pays its
        # pack OUTSIDE the timed region (model-load protocol), a
        # percall plan pays the in-call re-layout it actually costs
        if p.prepack:
            pw = packing.pack(w, block_n=p.block_n, block_k=p.block_k,
                              quant=weight_format if quant else None,
                              sparse=True if sparse else None)
        else:
            pw = w
        if sparse and getattr(pw, "density_bucket", -1) != p.density_bucket:
            raise RuntimeError(
                f"synthetic sparse pack landed in bucket "
                f"{getattr(pw, 'density_bucket', -1)}, plan expects "
                f"{p.density_bucket}")
        run = jax.jit(lambda x, pw: gemm.execute(p, x, pw))
        return lambda: run(x, pw)

    runs = [make_run(p) for p in cands]

    retries = 0
    while True:
        with _spans.span("autotune_measure", m=m, n=n, k=k,
                         candidates=len(runs), round=retries,
                         trials=trials + 2 * retries):
            meds = _time_interleaved(runs, trials=trials + 2 * retries,
                                     warmup=warmup)
        t_analytic = meds[0]                  # analytic plan is cands[0]
        order = sorted(range(len(cands)), key=lambda i: meds[i])
        best = order[0]
        if best == 0:
            break                             # analytic measured best
        adv = (t_analytic - meds[best]) / max(t_analytic, 1e-12)
        if adv >= noise_rtol:
            break                             # a real, above-noise win
        if retries >= max_retries:
            # mis-tune guard: the advantage never cleared the noise
            # floor — keep the analytic plan, never deploy on noise
            order = [0] + [i for i in order if i != 0]
            break
        retries += 1

    # the deployed plan must pass the existing bit-exactness gate;
    # gate-failing candidates are discarded, next-best stands (the
    # analytic plan gates too — an all-reject sweep is an error)
    rejected = 0
    winner = None
    for i in order:
        if gemm.validate_plan(cands[i]):
            winner, t_meas = cands[i], meds[i]
            break
        rejected += 1
    if winner is None:
        raise RuntimeError(
            f"measured autotune: every candidate for {m}x{n}x{k} "
            f"({weight_format}, decode={decode}) failed the "
            f"bit-exactness gate")
    final = dataclasses.replace(winner, validated=True)

    committed = False
    store = _ps.active_plan_store()
    if commit and store is not None:
        skey = _pol.store_key(m, n, k, dtype=dtype, backend=backend,
                              num_cores=num_cores, epilogue=epilogue,
                              weight_format=weight_format, decode=decode,
                              density_bucket=density_bucket)
        store.put(skey, final, t_meas=t_meas, autotuned=True)
        # adopt in-process too: the policy-position cache entry (if the
        # analytic resolution above seeded it) must agree with the store
        ck = _pol._plan_key(m, n, k, dtype=dtype, backend=backend,
                            num_cores=num_cores, epilogue=epilogue,
                            weight_format=weight_format, decode=decode,
                            density_bucket=density_bucket)
        _pol._cache_insert(ck, final)
        committed = True

    return MeasuredPlan(plan=final, t_analytic=meds[0], t_measured=t_meas,
                        analytic=(final.block_n, final.block_k,
                                  final.split_k) == (p0.block_n,
                                                     p0.block_k,
                                                     p0.split_k),
                        retries=retries, candidates=len(cands),
                        rejected=rejected, committed=committed)
