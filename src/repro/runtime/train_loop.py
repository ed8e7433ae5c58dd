"""Training loop: grad-accumulated, sharded, fault-tolerant.

Structure of one compiled step (all inside a single jit, donated state):

  microbatch scan (lax.scan over grad-accum slices)
    └─ value_and_grad of transformer.loss_fn
         └─ scan-over-layers forward (+ remat policy from the config)
  fp32 grad accumulation  →  clip  →  optimizer update

Mixed precision: parameters are kept in ``cfg.param_dtype`` (master) and
cast to ``cfg.compute_dtype`` for the forward/backward.  With bf16
compute this makes every gradient all-reduce/reduce-scatter bf16 on the
wire — the grad-compression lever of DESIGN.md §4 — while accumulation
across microbatches and the update stay fp32.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.optim import optimizers as O
from repro.parallel import sharding as Sh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: dict
    opt_state: dict


def init_state(cfg, tc, *, key=None):
    params = transformer.init_params(cfg, key or jax.random.key(tc.seed))
    opt = make_optimizer(cfg, tc)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt.init(params))


def abstract_state(cfg, tc):
    return jax.eval_shape(lambda: init_state(cfg, tc))


def make_optimizer(cfg, tc):
    lr = O.warmup_cosine(tc.learning_rate, tc.warmup_steps,
                         max(tc.steps, 1))
    return O.make(cfg.optimizer, lr, weight_decay=tc.weight_decay,
                  grad_clip=tc.grad_clip)


def num_microbatches(global_batch: int, batch_shards: int,
                     per_device: int) -> int:
    """Grad-accum slice count: the largest divisor of the per-shard batch
    that brings each slice down to <= per_device rows per shard."""
    per_shard = global_batch // max(batch_shards, 1)
    n = max(per_shard // max(per_device, 1), 1)
    while per_shard % n:
        n -= 1
    return n


def state_shardings(state, mesh):
    """NamedShardings for a TrainState.

    Optimizer state inherits its parameter's spec (FSDP: shards with the
    param).  Adafactor's factored stats drop a trailing dim: ``vr`` keeps
    the spec prefix, ``vc`` keeps prefix + last entry.
    """
    pspecs = Sh.param_specs(state.params, mesh)

    def _mirror(node, spec_node):
        if isinstance(node, dict) and isinstance(spec_node, dict):
            return {k: _mirror(node[k], spec_node[k]) for k in node}
        if isinstance(node, dict):   # factored {"vr","vc"} / {"v"} leaf dict
            ps = tuple(spec_node)
            out = {}
            for k, v in node.items():
                if k == "vc":        # (..., last-dim): prefix + last entry
                    sp = ps[:v.ndim - 1] + ps[-1:] if v.ndim else ()
                else:                # "vr"/"v": spec prefix
                    sp = ps[:v.ndim]
                out[k] = Sh.fit_spec(P(*sp), v.shape, mesh)
            return out
        return Sh.fit_spec(spec_node, node.shape, mesh)

    ospecs = {k: _mirror(sub, pspecs) for k, sub in state.opt_state.items()}
    specs = TrainState(step=P(), params=pspecs, opt_state=ospecs)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(cfg, shape, mesh):
    """Shardings for the {"inputs", "labels"} batch dict."""
    b = shape.global_batch
    tok = NamedSharding(mesh, Sh.batch_spec(b, mesh, extra_dims=1))
    if cfg.modality != "text":
        inp = NamedSharding(mesh, Sh.batch_spec(b, mesh, extra_dims=2))
    else:
        inp = tok
    return {"inputs": inp, "labels": tok}


def _cast_for_compute(params, cdtype):
    """Master→compute cast (matrices only; vectors stay fp32-safe)."""
    return jax.tree.map(
        lambda p: p.astype(cdtype) if p.ndim >= 2 else p, params)


def make_train_step(cfg, tc, mesh, *, donate: bool = True,
                    batch_shardings=None):
    """Build the jitted (state, batch) -> (state, metrics) step."""
    opt = make_optimizer(cfg, tc)
    shard_fn = Sh.activation_sharder(mesh)
    batch_shards = Sh.axis_size(mesh, ("pod", "data"))
    if tc.manual_dp:
        return _make_manual_dp_step(cfg, tc, mesh, opt, donate=donate,
                                    batch_shardings=batch_shardings)

    def loss_fn(params_c, micro):
        return transformer.loss_fn(cfg, params_c, micro, shard_fn=shard_fn)

    pspecs = Sh.param_specs(abstract_state(cfg, tc).params, mesh)

    def _constrain_like_params(tree):
        """Pin gradients to their parameter's sharding (FSDP).

        §Perf iteration 1: without this, the fp32 grad accumulator is
        replicated over the data axis and EVERY microbatch's gradients
        are all-reduced at full width (measured 536 GB/device/step on
        deepseek-7b train_4k).  Constrained, GSPMD reduce-scatters each
        microbatch's grads into data-sharded accumulators — 1/(2·shards)
        the wire bytes — and the unsharded tensors never materialize.
        """
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)),
            tree, pspecs, is_leaf=lambda x: isinstance(x, P))

    def _drop_data_axes(spec: P) -> P:
        drop = {"data", "pod"}

        def keep(entry):
            if entry is None:
                return None
            names = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(n for n in names if n not in drop)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return P(*(keep(e) for e in spec))

    def step_fn(state: TrainState, batch: dict):
        n_micro = num_microbatches(
            batch["labels"].shape[0], batch_shards, tc.microbatch_per_device)
        params_c = _cast_for_compute(state.params, cfg.cdtype)
        if tc.gather_params_once:
            # §Perf iteration 3: materialize the FSDP all-gather ONCE per
            # step instead of once per microbatch — the compute copy is
            # constrained replicated over the data axes, so the gather
            # hoists out of the scan (costs full-d params per device).
            params_c = jax.tree.map(
                lambda p, s: jax.lax.with_sharding_constraint(
                    p, NamedSharding(mesh, _drop_data_axes(s))),
                params_c, pspecs, is_leaf=lambda x: isinstance(x, P))

        def slice_micro(x):
            b = x.shape[0]
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        micro = jax.tree.map(slice_micro, batch)

        def accum(carry, mb):
            g_acc, loss_acc, ce_acc = carry
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params_c, mb)
            if tc.grad_compression == "bf16":
                # bf16 on the wire; fp32 accumulate after the collective
                g = jax.tree.map(lambda x: x.astype(jnp.bfloat16), g)
            if tc.shard_grad_accum:
                g = _constrain_like_params(g)
            g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             g_acc, g)
            return (g, loss_acc + loss, ce_acc + aux["ce"]), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          state.params)
        if tc.shard_grad_accum:
            g0 = _constrain_like_params(g0)
        (grads, loss, ce), _ = jax.lax.scan(
            accum, (g0, jnp.zeros(()), jnp.zeros(())), micro)
        grads = jax.tree.map(lambda g: g / n_micro, grads)
        loss, ce = loss / n_micro, ce / n_micro

        new_params, new_opt, stats = opt.update(
            grads, state.opt_state, state.params, state.step)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt)
        metrics = {"loss": loss, "ce": ce, **stats}
        return new_state, metrics

    abstract = abstract_state(cfg, tc)
    st_sh = state_shardings(abstract, mesh)
    return jax.jit(
        step_fn,
        in_shardings=(st_sh, batch_shardings),
        out_shardings=(st_sh, None),
        donate_argnums=(0,) if donate else (),
    )


def _make_manual_dp_step(cfg, tc, mesh, opt, *, donate: bool = True,
                         batch_shardings=None):
    """§Perf iteration 4: manual data parallelism, auto tensor parallelism.

    The naive GSPMD step syncs gradients at every (microbatch × layer)
    dot boundary — all-reduce wire bytes scale with n_micro (measured
    536 GB/device/step on deepseek-7b train_4k).  Under a shard_map whose
    MANUAL axes are (pod, data) and whose auto axis is model:

      * FSDP params are all-gathered over data ONCE per step (explicit
        `jax.lax.all_gather`, the A3 hoist made structural);
      * every microbatch's backward produces LOCAL grads — no data-axis
        collective inside the scan at all;
      * one `psum_scatter` per param per STEP syncs and re-shards the
        accumulated grads — and because we own the collective, the
        grad_compression="bf16" wire cast finally applies (the A2
        lesson: post-hoc casts can't reach GSPMD-inserted reductions).

    Expected: all-reduce wire ÷ ~n_micro; bf16 halves it again.
    """
    # nothing_saveable remat inside partial-auto shard_map trips an XLA
    # CHECK at 512 partitions ("Invalid binary instruction opcode copy");
    # dots-saveable avoids the pattern and saves less recompute anyway.
    if cfg.remat and cfg.remat_policy != "dots":
        cfg = dataclasses.replace(cfg, remat_policy="dots")

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    pspecs = Sh.param_specs(abstract_state(cfg, tc).params, mesh)
    # inner-region activation constraints may not name manual axes
    shard_fn = Sh.activation_sharder(
        mesh, drop_axes=frozenset(data_axes))

    def _data_dim(spec: P) -> int | None:
        for d, entry in enumerate(spec):
            names = entry if isinstance(entry, tuple) else (entry,)
            if any(n in data_axes for n in names if n):
                return d
        return None

    def _manual_specs(tree_specs):
        def keep(spec):
            d = _data_dim(spec)
            out = [None] * len(spec)
            if d is not None:
                out[d] = "data"      # data only; pod handled for batch
            return P(*out)
        return jax.tree.map(keep, tree_specs,
                            is_leaf=lambda x: isinstance(x, P))

    man_pspecs = _manual_specs(pspecs)

    def loss_fn(params_full, micro):
        return transformer.loss_fn(cfg, params_full, micro,
                                   shard_fn=shard_fn)

    def inner(params_local, batch_local):
        # 1. gather FSDP shards once per step
        def gather(p, spec):
            d = _data_dim(spec)
            if d is None:
                return p
            return jax.lax.all_gather(p, "data", axis=d, tiled=True)
        params_full = jax.tree.map(gather, params_local, pspecs,
                                   is_leaf=lambda x: isinstance(x, P))

        rows = batch_local["labels"].shape[0]
        n_micro = max(rows // max(tc.microbatch_per_device, 1), 1)
        while rows % n_micro:
            n_micro -= 1

        def slice_micro(x):
            return x.reshape(n_micro, rows // n_micro, *x.shape[1:])
        micro = jax.tree.map(slice_micro, batch_local)

        def accum(carry, mb):
            g_acc, loss_acc, ce_acc = carry
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params_full, mb)
            g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             g_acc, g)
            return (g, loss_acc + loss, ce_acc + aux["ce"]), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params_full)
        (grads, loss, ce), _ = jax.lax.scan(
            accum, (g0, jnp.zeros(()), jnp.zeros(())), micro)

        # 2. ONE grad sync per step (mean over data shards), re-sharded
        inv = 1.0 / (n_micro * Sh.axis_size(mesh, data_axes))

        def sync(g, spec):
            if tc.grad_compression == "bf16":
                g = g.astype(jnp.bfloat16)       # wire dtype
            d = _data_dim(spec)
            if d is None:
                g = jax.lax.psum(g, data_axes)
            else:
                g = jax.lax.psum_scatter(g, "data", scatter_dimension=d,
                                         tiled=True)
                if len(data_axes) > 1:           # cross-pod reduction
                    g = jax.lax.psum(g, "pod")
            return g.astype(jnp.float32) * inv
        grads = jax.tree.map(sync, grads, pspecs,
                             is_leaf=lambda x: isinstance(x, P))
        scale = 1.0 / n_micro
        loss = jax.lax.pmean(loss * scale, data_axes)
        ce = jax.lax.pmean(ce * scale, data_axes)
        return grads, loss, ce

    batch_rows_spec = P(data_axes if len(data_axes) > 1 else
                        data_axes[0])

    def batch_spec_for(tree):
        return jax.tree.map(
            lambda x: P(*(batch_rows_spec + (None,) * (x.ndim - 1))),
            tree)

    def step_fn(state: TrainState, batch: dict):
        params_c = _cast_for_compute(state.params, cfg.cdtype)
        inner_sm = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(man_pspecs, batch_spec_for(batch)),
            out_specs=(man_pspecs, P(), P()),
            axis_names=set(data_axes), check_vma=False)
        grads, loss, ce = inner_sm(params_c, batch)
        new_params, new_opt, stats = opt.update(
            grads, state.opt_state, state.params, state.step)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt)
        return new_state, {"loss": loss, "ce": ce, **stats}

    abstract = abstract_state(cfg, tc)
    st_sh = state_shardings(abstract, mesh)
    return jax.jit(step_fn, in_shardings=(st_sh, batch_shardings),
                   out_shardings=(st_sh, None),
                   donate_argnums=(0,) if donate else ())


def train(cfg, tc, mesh, data_iter, *, ckpt_dir: str | None = None,
          log_every: int = 10, shutdown=None, watchdog=None,
          state: TrainState | None = None, start_step: int = 0):
    """Run the loop.  Returns (state, history).

    ``shutdown``: fault_tolerance.GracefulShutdown — checkpoint-and-exit
    on SIGTERM.  ``watchdog``: fault_tolerance.StepWatchdog — straggler
    detection.  Resume: pass ``state``/``start_step`` from
    fault_tolerance.resume_or_init.
    """
    from repro.checkpoint import CheckpointManager
    step_fn = make_train_step(cfg, tc, mesh)
    if state is None:
        state = init_state(cfg, tc)
        state = jax.device_put(state, state_shardings(state, mesh))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    b_sh = None
    history = []
    t_last = time.perf_counter()
    for step, batch in data_iter:
        if step >= tc.steps:
            break
        if b_sh is None and mesh is not None:
            from repro.configs.base import ShapeConfig
            shape = ShapeConfig("run", "train", batch["labels"].shape[1],
                                batch["labels"].shape[0])
            b_sh = batch_shardings(cfg, shape, mesh)
        batch = jax.device_put(batch, b_sh)
        state, metrics = step_fn(state, batch)
        if watchdog is not None:
            now = time.perf_counter()
            watchdog.record(now - t_last)
            t_last = now
        if step % log_every == 0 or step == tc.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, **m})
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"ce {m['ce']:.4f}  gnorm {m['grad_norm']:.3f}  "
                  f"lr {m['lr']:.2e}")
        want_ckpt = mgr and (step + 1) % tc.checkpoint_every == 0
        if shutdown is not None and shutdown.requested:
            print(f"SIGTERM: checkpointing at step {step + 1} and exiting")
            want_ckpt = bool(mgr)
        if want_ckpt:
            mgr.save(step + 1, state, metadata={"step": step + 1})
        if shutdown is not None and shutdown.requested:
            break
    if mgr:
        mgr.wait()
    return state, history
