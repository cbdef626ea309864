"""train_step / serve_step builders with explicit shardings.

``build_train_step`` returns a jitted function

    (params, opt_state, batch, monitor) -> (params, opt_state, metrics, monitor)

with in/out shardings derived from :mod:`repro.train.sharding`, donated
params/opt buffers, optional microbatch gradient accumulation (lax.scan so
weights stay resident and grads reduce once), and QO telemetry folded in.

``build_serve_steps`` returns (prefill_fn, decode_fn) for serving shapes.

All builders also return the lowered-input ShapeDtypeStructs so the
dry-run can ``.lower().compile()`` without touching real data.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.models.layers import compute_dtype
from repro.optim import adamw
from repro.train import sharding as SH
from repro.train import monitor as MON


def input_specs(cfg, shape, *, abstract=True):
    """ShapeDtypeStruct stand-ins for every model input of a shape config.

    For train: {tokens, labels}; encdec adds enc_in; vlm adds loss_mask.
    For decode: (token, pos); prefill like train without labels.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = compute_dtype()
    out: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        if shape.kind == "train":
            out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
            if cfg.family == "vlm":
                # early fusion: image-token positions are masked from the loss
                out["loss_mask"] = jax.ShapeDtypeStruct((B, S), jnp.float32)
        if cfg.family == "encdec":
            out["enc_in"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), f32)
    else:  # decode
        out["token"] = jax.ShapeDtypeStruct((B,), i32)
    return out


def abstract_params(cfg):
    return jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))


def abstract_state(cfg, opt_cfg: adamw.AdamWConfig):
    pshapes = abstract_params(cfg)
    oshapes = jax.eval_shape(adamw.init_state, pshapes)
    return pshapes, oshapes


def build_train_step(cfg, shape, mesh, opt_cfg=None, *, microbatch: int = 0,
                     remat=True, kv_chunk=512, with_monitor=True,
                     donate=True, seq_parallel=False,
                     sharding_style="contraction"):
    """Returns (step_fn, in_shardings, out_shardings, arg_shapes).

    seq_parallel: pin the residual stream sequence-sharded over the model
    axis (Megatron-SP).  Row-parallel all-reduces of (tokens, d) outputs
    become reduce-scatter + all-gather pairs — ~TP-fold fewer collective
    bytes on the residual (§Perf hillclimb)."""
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    pshapes = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, pshapes, mesh, style=sharding_style)
    ospecs = SH.opt_specs(pspecs)
    bfield = SH.batch_specs(cfg, shape.kind, shape.global_batch, mesh)
    batch_shapes = input_specs(cfg, shape)
    bspecs = {k: bfield(k) for k in batch_shapes}
    mon_specs = MON.monitor_specs() if with_monitor else None
    fsdp, tp = SH.mesh_axes(mesh)
    seq_ax = tp if (seq_parallel and shape.seq_len % mesh.shape[tp] == 0) else None
    act_spec = P(fsdp, seq_ax, None)  # (batch, seq, d) residual pin

    def loss_fn(params, batch):
        loss, metrics = M.lm_loss(params, cfg, batch, remat=remat,
                                  kv_chunk=kv_chunk, act_spec=act_spec)
        return loss, metrics

    def step(params, opt_state, batch, monitor):
        if microbatch and microbatch > 1:
            nm = microbatch
            B = batch["tokens"].shape[0]
            assert B % nm == 0

            def mb(carry, mbatch):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mbatch)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            resh = jax.tree.map(
                lambda t: jnp.moveaxis(
                    t.reshape((nm, B // nm) + t.shape[1:]), 0, 0), batch)
            zero_g = jax.tree.map(jnp.zeros_like, params)
            (grads, loss), _ = jax.lax.scan(mb, (zero_g, jnp.float32(0.0)), resh)
            grads = jax.tree.map(lambda g: g / nm, grads)
            loss = loss / nm
            metrics = {"xent": loss, "aux": jnp.float32(0.0)}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        params2, opt_state2, opt_metrics = adamw.apply(
            opt_cfg, params, opt_state, grads)
        # NaN-step skip: keep old params if the update is not finite
        finite = jnp.isfinite(loss) & jnp.isfinite(opt_metrics["grad_norm"])
        params2 = jax.tree.map(
            lambda new, old: jnp.where(finite, new, old), params2, params)
        opt_state2 = jax.tree.map(
            lambda new, old: jnp.where(finite, new, old), opt_state2, opt_state)

        metrics = dict(metrics, **opt_metrics, loss=loss,
                       skipped=(~finite).astype(jnp.float32))
        if monitor is not None:
            monitor = MON.observe(monitor, loss=loss,
                                  grad_norm=opt_metrics["grad_norm"])
        return params2, opt_state2, metrics, monitor

    in_sh = (SH.to_shardings(mesh, pspecs), SH.to_shardings(mesh, ospecs),
             SH.to_shardings(mesh, bspecs),
             SH.to_shardings(mesh, mon_specs) if with_monitor else None)
    out_sh = (in_sh[0], in_sh[1], None, in_sh[3])
    donate_args = (0, 1) if donate else ()
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=donate_args)
    oshapes = jax.eval_shape(adamw.init_state, pshapes)
    mshape = jax.eval_shape(MON.init_monitor) if with_monitor else None
    return fn, in_sh, out_sh, (pshapes, oshapes, batch_shapes, mshape)


def build_serve_steps(cfg, shape, mesh, *, kv_chunk=512):
    """Returns (prefill_fn, decode_fn, shapes) with explicit shardings.

    decode shapes lower ``serve_step`` = one token against a seq_len cache.
    """
    pshapes = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, pshapes, mesh)
    B, S = shape.global_batch, shape.seq_len
    cache_shapes = jax.eval_shape(lambda: M.init_cache(cfg, B, S))
    cspecs = SH.cache_specs(cfg, B, mesh, cache_shapes)
    bfield = SH.batch_specs(cfg, shape.kind, B, mesh)

    p_sh = SH.to_shardings(mesh, pspecs)
    c_sh = SH.to_shardings(mesh, cspecs)
    fsdp, _ = SH.mesh_axes(mesh)
    fsdp_n = 1
    for a in fsdp:
        fsdp_n *= mesh.shape[a]
    bshard = fsdp if B % fsdp_n == 0 else None
    act_spec = P(bshard, None, None)

    # ---- prefill over the full prompt ----
    prefill_shapes = input_specs(
        cfg, type(shape)(shape.name, S, B, "prefill"))
    pf_bspecs = {k: bfield(k) if k != "enc_in" else P(None, None, None)
                 for k in prefill_shapes}
    pf_bspecs = {k: bfield(k) for k in prefill_shapes}

    def prefill_fn(params, batch, cache):
        return M.prefill(params, cfg, batch, cache, kv_chunk=kv_chunk,
                         act_spec=act_spec)

    prefill_jit = jax.jit(
        prefill_fn,
        in_shardings=(p_sh, SH.to_shardings(mesh, pf_bspecs), c_sh),
        out_shardings=(c_sh, None),
        donate_argnums=(2,))

    # ---- single-token decode ----
    def decode_fn(params, token, cache, pos):
        return M.decode_step(params, cfg, token, cache, pos,
                             kv_chunk=kv_chunk, act_spec=act_spec)

    # token sharding left to the partitioner (it follows the cache batch
    # axis); pinning it would reject host-produced argmax tokens in tests
    decode_jit = jax.jit(
        decode_fn,
        in_shardings=(p_sh, None, c_sh, None),
        out_shardings=(None, c_sh),
        donate_argnums=(2,))

    decode_shapes = {
        "token": jax.ShapeDtypeStruct((B,), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    return prefill_jit, decode_jit, (pshapes, cache_shapes,
                                     prefill_shapes, decode_shapes)
