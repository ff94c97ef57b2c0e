"""Train-step factories.

Two step builders mirror the paper's two ``ExecutionImplementation``s
(Fig. 1), selected by ``RunConfig.comm_type`` exactly like the paper selects
by bitstream name:

* :func:`make_train_step` — the production GSPMD path: one ``jax.jit`` with
  in/out shardings; XLA inserts and schedules all collectives (the
  "native/ICI" path). Supports microbatching (gradient accumulation under
  ``lax.scan``), remat policies, and ZeRO-1 optimizer-state sharding.

* :func:`make_dp_train_step_explicit` — the paper-faithful explicit path:
  the whole step runs inside ``shard_map`` over the data axes with
  *hand-written* gradient reduction from :mod:`repro.comm.collectives`
  (``native`` / ``chain`` ring / ``staged`` host-staged), optionally int8-
  compressed with error feedback. This is the circuit-switched 'network
  kernel' schedule applied to LM training, and is what benchmarks compare.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro import sharding as sh
from repro.comm import compression
from repro.comm.callsites import DP_GRADS
from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType, comm_type
from repro.configs.base import ModelConfig, RunConfig
from repro.models import moe as MOE
from repro.models.model import Model, next_token_loss
from repro.models.parallel import make_attn_impl
from repro.optim.adamw import (AdamWConfig, adamw_init, adamw_update,
                               clip_by_global_norm, make_lr_schedule)


@jax.tree_util.register_pytree_node_class
@dataclass
class TrainState:
    params: Dict
    opt: Dict
    step: jnp.ndarray
    error: Optional[Dict] = None  # compression error-feedback tree

    def tree_flatten(self):
        return (self.params, self.opt, self.step, self.error), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_train_state(model: Model, key, *, compression_on: bool = False) -> TrainState:
    params = model.init(key)
    opt = adamw_init(params)
    err = compression.init_error_tree(params) if compression_on else None
    return TrainState(params=params, opt=opt, step=jnp.zeros((), jnp.int32),
                      error=err)


def state_specs(state: TrainState, rules: sh.MeshRules, mesh: Mesh,
                *, zero1: bool = True) -> TrainState:
    """PartitionSpec pytree matching a TrainState."""
    pspec = sh.param_specs(state.params, rules, mesh)
    ospec = {
        "mu": sh.opt_state_specs(state.params, rules, mesh, zero1=zero1),
        "nu": sh.opt_state_specs(state.params, rules, mesh, zero1=zero1),
        "count": P(),
    }
    espec = None
    if state.error is not None:
        espec = sh.param_specs(state.error, rules, mesh)
    return TrainState(params=pspec, opt=ospec, step=P(), error=espec)


# ---------------------------------------------------------------------------
# production GSPMD step
# ---------------------------------------------------------------------------


def make_train_step_fn(model: Model, run_cfg: RunConfig, mesh: Mesh,
                       *, adamw: Optional[AdamWConfig] = None,
                       total_steps: int = 10_000, fsdp: bool = False) -> Callable:
    """Un-jitted (state, batch) -> (state, metrics); caller picks jit options
    (the dry-run passes explicit in/out shardings and donation)."""
    adamw = adamw or AdamWConfig(lr=run_cfg.learning_rate,
                                 weight_decay=run_cfg.weight_decay,
                                 max_grad_norm=run_cfg.max_grad_norm)
    schedule = make_lr_schedule(adamw.lr, run_cfg.warmup_steps, total_steps)
    rules = sh.rules_for(mesh, fsdp=fsdp)
    shard = sh.make_shard_fn(mesh, rules)
    nmicro = max(run_cfg.microbatches, 1)

    def loss_fn(params, batch):
        logits, _, _ = model.apply(params, batch, shard=shard,
                                   remat=run_cfg.remat)
        return next_token_loss(logits, batch["tokens"])

    grad_fn = jax.value_and_grad(loss_fn)

    def compute_grads(params, batch):
        if nmicro == 1:
            return grad_fn(params, batch)
        # gradient accumulation: scan over microbatches (batch-major split)
        def resplit(x):
            b = x.shape[0]
            assert b % nmicro == 0, (b, nmicro)
            return x.reshape((nmicro, b // nmicro) + x.shape[1:])
        micro = {k: resplit(v) for k, v in batch.items()}

        def body(acc, mb):
            loss, g = grad_fn(params, mb)
            acc_loss, acc_g = acc
            acc_g = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32) / nmicro, acc_g, g)
            return (acc_loss + loss / nmicro, acc_g), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                    micro)
        return loss, grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        loss, grads = compute_grads(state.params, batch)
        grads, gnorm = clip_by_global_norm(grads, adamw.max_grad_norm)
        lr = schedule(state.step)
        new_params, new_opt = adamw_update(grads, state.opt, state.params,
                                           adamw, lr)
        new_state = TrainState(params=new_params, opt=new_opt,
                               step=state.step + 1, error=state.error)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_state, metrics

    return train_step


def make_train_step(model: Model, run_cfg: RunConfig, mesh: Mesh,
                    *, adamw: Optional[AdamWConfig] = None,
                    total_steps: int = 10_000,
                    donate: bool = True, fsdp: bool = False) -> Callable:
    """jit'd (state, batch) -> (state, metrics) with full sharding annotations."""
    train_step = make_train_step_fn(model, run_cfg, mesh, adamw=adamw,
                                    total_steps=total_steps, fsdp=fsdp)
    jit_kwargs = dict(donate_argnums=(0,)) if donate else {}
    return jax.jit(train_step, **jit_kwargs)


def shard_state(state: TrainState, mesh: Mesh, *, zero1: bool = True,
                fsdp: bool = False) -> TrainState:
    """Place a host-initialized TrainState onto the mesh per the rules."""
    rules = sh.rules_for(mesh, fsdp=fsdp)
    specs = state_specs(state, rules, mesh, zero1=zero1)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# paper-faithful explicit-collectives DP step (shard_map over 'x')
# ---------------------------------------------------------------------------

# tuning-table callsite tag for the bucketed gradient reduction: buckets are
# issued back-to-back against the remaining backward compute, so a measured
# ``allreduce@dp.grads`` table entry wins over the isolated-allreduce entry
GRADS_CALLSITE = DP_GRADS


def make_dp_train_step_explicit(model: Model, run_cfg: RunConfig, mesh: Mesh,
                                *, axis: str = "x",
                                adamw: Optional[AdamWConfig] = None,
                                schedule_kind: str = "auto",
                                bucket_bytes: Optional[int] = None,
                                total_steps: int = 10_000) -> Callable:
    """Pure data-parallel step with hand-written gradient reduction.

    The gradient all-reduce routes through
    :meth:`~repro.comm.engine.CollectiveEngine.allreduce_tree`, the bucketed
    overlap path (paper Fig. 5/7's comm/compute overlap applied to the
    backward pass): leaves are packed into ~``bucket_bytes`` buckets and each
    bucket is reduced independently, so XLA can overlap early buckets'
    collectives with the remaining backward compute. ``run_cfg.comm_type``
    picks ICI_DIRECT vs HOST_STAGED, ``schedule_kind`` names the registered
    reduction schedule (``native`` / ``chain`` ring / ``rs_ag`` fused ring /
    ``ring2d`` / ``staged``) — the default ``"auto"`` resolves per bucket
    through the cost model (:mod:`repro.comm.autotune`).
    ``bucket_bytes=None`` derives the bucket size from the DP-axis topology
    and hardware link numbers (pipeline depth x per-hop latency-bandwidth
    product) instead of a fixed constant. Every bucket's reduction is tagged
    ``dp.grads``, so a measured tuning-table entry for the bucketed-gradient
    pattern overrides the isolated-allreduce entry per callsite.

    ``run_cfg.grad_compression`` turns on the int8 error-feedback reduction
    (beyond-paper): that path reduces *leaf-wise* — per-leaf error state
    cannot be bucketed without re-blocking the quantizer — so
    ``bucket_bytes`` does not apply, but the wire payload still rides the
    engine's ring schedules via ``compressed_psum(engine=...)``.
    """
    adamw = adamw or AdamWConfig(lr=run_cfg.learning_rate,
                                 weight_decay=run_cfg.weight_decay,
                                 max_grad_norm=run_cfg.max_grad_norm)
    schedule = make_lr_schedule(adamw.lr, run_cfg.warmup_steps, total_steps)
    engine = CollectiveEngine.for_mesh(mesh, comm_type(run_cfg.comm_type),
                                       schedule_kind)
    compress = run_cfg.grad_compression == "int8_ef"
    ndev = mesh.shape[axis]

    def loss_fn(params, batch):
        logits, _, _ = model.apply(params, batch, remat=run_cfg.remat)
        return next_token_loss(logits, batch["tokens"])

    def step_body(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        # mean over DP ranks, via the selected schedule
        if compress:
            flat_g, treedef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(state.error)
            red, errs = [], []
            for g, e in zip(flat_g, flat_e):
                r, ne = compression.compressed_psum(
                    g.astype(jnp.float32) / ndev, axis, e, engine=engine)
                red.append(r)
                errs.append(ne)
            grads = jax.tree.unflatten(treedef, red)
            new_error = jax.tree.unflatten(treedef, errs)
        else:
            grads = engine.allreduce_tree(
                jax.tree.map(lambda g: g.astype(jnp.float32) / ndev, grads),
                axis, bucket_bytes=bucket_bytes, callsite=GRADS_CALLSITE)
            new_error = state.error
        loss = engine.allreduce(loss / ndev, axis)

        grads, gnorm = clip_by_global_norm(grads, adamw.max_grad_norm)
        lr = schedule(state.step)
        new_params, new_opt = adamw_update(grads, state.opt, state.params,
                                           adamw, lr)
        new_state = TrainState(params=new_params, opt=new_opt,
                               step=state.step + 1, error=new_error)
        return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    def spec_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def wrapped(state, batch):
        st_spec = TrainState(
            params=spec_like(state.params, P()),
            opt={"mu": spec_like(state.opt["mu"], P()),
                 "nu": spec_like(state.opt["nu"], P()),
                 "count": P()},
            step=P(),
            error=spec_like(state.error, P()) if state.error is not None else None,
        )
        batch_spec = {k: P(axis) for k in batch}
        metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
        fn = shard_map(
            step_body, mesh=mesh,
            in_specs=(st_spec, batch_spec),
            out_specs=(st_spec, metrics_spec),
            check_vma=False)
        return fn(state, batch)

    return jax.jit(wrapped, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# whole-model explicit step: full forward+backward inside one shard_map
# ---------------------------------------------------------------------------


def whole_model_param_specs(params: Dict, axis: str = "x") -> Dict:
    """PartitionSpecs for the explicit whole-model step: everything
    replicated except MoE expert weights, which are sharded over ``axis``
    (the leading dim after the super-block scan dim —
    :func:`repro.models.moe.moe_param_specs` with ``scanned=True``)."""
    specs = jax.tree.map(lambda _: P(), params)
    for kp, blk in params["blocks"].items():
        if "moe" in blk:
            specs["blocks"][kp]["moe"] = MOE.moe_param_specs(
                blk["moe"], axis, scanned=True)
    return specs


_IS_SPEC = lambda x: isinstance(x, P)  # noqa: E731 — P() flattens to nothing otherwise


def make_whole_model_train_step_explicit(
        model: Model, run_cfg: RunConfig, mesh: Mesh, *, axis: str = "x",
        attn_mode: str = "tp", adamw: Optional[AdamWConfig] = None,
        schedule_kind: str = "auto", nchunks=1,
        bucket_bytes: Optional[int] = None,
        total_steps: int = 10_000) -> Callable:
    """Whole-model engine-routed step: the full forward+backward runs
    inside ONE ``shard_map`` over ``axis``, every wire hop an explicit
    :class:`~repro.comm.engine.CollectiveEngine` call under a registered
    callsite tag (see :mod:`repro.comm.callsites`):

    * attention activations are exchanged per layer via the ``attn_mode``
      hook from :mod:`repro.models.parallel` — head-parallel (``tp``,
      ``@tp.qkv``/``@tp.out``) or sequence-parallel ring attention (``sp``,
      ``@sp.qkv``/``@sp.kv``/``@sp.out``);
    * MoE dispatch/combine keep ``@moe.dispatch``/``@moe.combine`` with
      experts sharded across ranks in the *param tree* (``nchunks``
      pipelines the capacity strips exactly as in the single-layer path);
    * data-parallel gradient buckets keep ``allreduce @ dp.grads``.

    Gradient semantics: the residual stream is batch-sharded, so the local
    backward already yields *complete* gradients for expert-sharded leaves
    (the dispatch/combine transposes aggregate the other ranks' terms) —
    those are only rescaled by 1/ndev, never reduced — while replicated
    leaves take the bucketed ``allreduce_tree``. The global-norm clip
    mirrors :func:`repro.optim.adamw.clip_by_global_norm` but reduces the
    expert-shard sum-of-squares across ranks first, so the clip scale (and
    the reported ``grad_norm``) equals the GSPMD value.

    Differences vs GSPMD (:func:`make_train_step`) are pure reassociation:
    loss, gradients, and updated params match on the same mesh to float32
    tolerance for every registered schedule and chunk count
    (tests/dist/test_transformer.py).
    """
    cfg = model.cfg
    if cfg.is_encoder_decoder:
        raise ValueError("whole-model explicit step supports decoder-only "
                         "models (encoder-decoder has no explicit path)")
    if run_cfg.grad_compression != "none":
        raise ValueError(
            "whole-model explicit step does not support grad_compression="
            f"{run_cfg.grad_compression!r}: the int8 error-feedback path "
            "reduces leaf-wise and cannot skip the expert-sharded leaves")
    adamw = adamw or AdamWConfig(lr=run_cfg.learning_rate,
                                 weight_decay=run_cfg.weight_decay,
                                 max_grad_norm=run_cfg.max_grad_norm)
    schedule = make_lr_schedule(adamw.lr, run_cfg.warmup_steps, total_steps)
    engine = CollectiveEngine.for_mesh(mesh, comm_type(run_cfg.comm_type),
                                       schedule_kind)
    ndev = mesh.shape[axis]
    # schedule=None: the hooks inherit the engine-wide resolution (auto via
    # the cost model, or the engine's explicit schedule_kind)
    attn_impl = make_attn_impl(attn_mode, cfg, mesh, axis=axis, engine=engine)
    moe_impl = None
    if cfg.has_moe:
        moe_impl = MOE.make_moe_impl(cfg, mesh, axis=axis, engine=engine,
                                     nchunks=nchunks)

    def loss_fn(params, batch):
        logits, _, _ = model.apply(params, batch, remat=run_cfg.remat,
                                   attn_impl=attn_impl, moe_impl=moe_impl)
        return next_token_loss(logits, batch["tokens"])

    def step_body(state: TrainState, batch, *, param_spec):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)

        # Mean over DP ranks. Expert-sharded leaves already carry the full
        # cross-rank sum (the collective transposes of dispatch/combine
        # aggregate every rank's contribution), so they are only rescaled;
        # replicated leaves ride the bucketed reduction.
        g_leaves, treedef = jax.tree.flatten(grads)
        s_leaves = jax.tree.flatten(param_spec, is_leaf=_IS_SPEC)[0]
        scaled = [g.astype(jnp.float32) / ndev for g in g_leaves]
        rep = {str(i): g for i, (g, s) in enumerate(zip(scaled, s_leaves))
               if s == P()}
        rep = engine.allreduce_tree(rep, axis, bucket_bytes=bucket_bytes,
                                    callsite=GRADS_CALLSITE)
        merged = [rep[str(i)] if str(i) in rep else g
                  for i, g in enumerate(scaled)]
        loss = engine.allreduce(loss / ndev, axis)

        # Global-norm clip, sharding-aware: expert-shard sumsq needs a
        # cross-rank psum; replicated leaves are identical post-allreduce,
        # so their sumsq is local. Same formula as clip_by_global_norm.
        rep_sq = sum(jnp.sum(jnp.square(g)) for g, s in
                     zip(merged, s_leaves) if s == P())
        shard_sq = sum(jnp.sum(jnp.square(g)) for g, s in
                      zip(merged, s_leaves) if s != P())
        if not isinstance(shard_sq, int):  # any expert-sharded leaves?
            rep_sq = rep_sq + engine.allreduce(shard_sq, axis)
        gnorm = jnp.sqrt(rep_sq)
        scale = jnp.minimum(1.0, adamw.max_grad_norm /
                            jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.unflatten(treedef, [g * scale for g in merged])

        lr = schedule(state.step)
        new_params, new_opt = adamw_update(grads, state.opt, state.params,
                                           adamw, lr)
        new_state = TrainState(params=new_params, opt=new_opt,
                               step=state.step + 1, error=state.error)
        return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    def wrapped(state, batch):
        pspec = whole_model_param_specs(state.params, axis)
        st_spec = TrainState(
            params=pspec,
            opt={"mu": jax.tree.map(lambda s: s, pspec, is_leaf=_IS_SPEC),
                 "nu": jax.tree.map(lambda s: s, pspec, is_leaf=_IS_SPEC),
                 "count": P()},
            step=P(),
            error=None,
        )
        batch_spec = {k: P(axis) for k in batch}
        metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
        fn = shard_map(
            partial(step_body, param_spec=pspec), mesh=mesh,
            in_specs=(st_spec, batch_spec),
            out_specs=(st_spec, metrics_spec),
            check_vma=False)
        return fn(state, batch)

    return jax.jit(wrapped, donate_argnums=(0,))
