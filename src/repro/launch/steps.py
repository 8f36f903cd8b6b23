"""Step builders: sharded train / prefill / decode step functions + their
input specs — shared by the dry-run, the trainer and the server.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeCfg
from ..distributed.collectives import (accumulate_microbatches,
                                       error_feedback_apply)
from ..distributed.sharding import (ModelSharding, ShardCfg, batch_spec,
                                    tree_cache_specs, tree_param_specs)
from ..models import lm
from ..optim import AdamWCfg, OptState, apply_updates, init_opt_state

BF16 = jnp.bfloat16


def as_shardings(tree, mesh: Mesh):
    """PartitionSpecs in ``tree`` -> ``NamedSharding``s on ``mesh``, so
    ``jax.jit``'s in/out_shardings need no ambient mesh."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        tree, is_leaf=lambda s: isinstance(s, P))


# -------------------------------------------------------------- policies

def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.axis_names]))


def pick_microbatches(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
                      act_budget: int = 128 << 20) -> int:
    """Accumulation factor: keep per-microbatch live activations (bf16,
    d_model-width, with remat) under ``act_budget`` per device."""
    local_b = max(1, shape.global_batch // dp_size(mesh))
    width = max(cfg.d_model,
                cfg.ssm.expand * cfg.d_model if cfg.ssm else cfg.d_model)
    per_item = shape.seq_len * width * 2 * 4  # x4: residuals + mixer buffers
    n = 1
    while local_b % (2 * n) == 0 and (local_b // n) * per_item > act_budget:
        n *= 2
    return n


def shard_cfg_for(cfg: ArchConfig, shape: ShapeCfg) -> ShardCfg:
    """Default sharding strategy per (arch, shape) cell."""
    return ShardCfg(
        fsdp=True, tp=True,
        seq_shard_cache=(shape.name == "long_500k"),
        # GQA decode: kv-heads rarely divide the 16-way TP axis — shard the
        # cache sequence over 'model' instead of replicating (§Perf cell B:
        # 310x collective, 14x memory)
        cache_seq_model=(shape.kind == "decode"),
    )


# ------------------------------------------------------------ input specs

def input_specs(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
                sc: Optional[ShardCfg] = None):
    """ShapeDtypeStruct stand-ins + shardings for every step input.

    Returns (abstract_inputs: dict, shardings: dict) keyed per argument of
    the corresponding step function."""
    sc = sc or shard_cfg_for(cfg, shape)
    dp = batch_spec(mesh)
    B, S = shape.global_batch, shape.seq_len
    bspec = dp if B % dp_size(mesh) == 0 else P()

    def tok(b, s):
        return jax.ShapeDtypeStruct((b, s), jnp.int32)

    ctx_len = 0
    if cfg.cross_len:
        ctx_len = cfg.cross_len
    dec_len = S
    if cfg.is_encdec:
        ctx_len, dec_len = S, min(448, S)   # frames drive the long dim

    out: Dict[str, Any] = {}
    shardings: Dict[str, Any] = {}
    if shape.kind == "train":
        batch = {"tokens": tok(B, dec_len), "targets": tok(B, dec_len)}
        bsh = {"tokens": bspec, "targets": bspec}
        if ctx_len:
            batch["ctx"] = jax.ShapeDtypeStruct((B, ctx_len, cfg.d_model),
                                                BF16)
            bsh["ctx"] = P(bspec[0] if len(bspec) else None, None, None)
        out["batch"] = batch
        shardings["batch"] = bsh
    elif shape.kind == "prefill":
        out["tokens"] = tok(B, dec_len)
        shardings["tokens"] = bspec
        if ctx_len:
            out["ctx"] = jax.ShapeDtypeStruct((B, ctx_len, cfg.d_model), BF16)
            shardings["ctx"] = P(bspec[0] if len(bspec) else None, None, None)
    else:  # decode
        out["token"] = tok(B, 1)
        shardings["token"] = bspec
        cap = min(448 + 128, S) if cfg.is_encdec else S + 128
        cache_shape = jax.eval_shape(
            lambda: lm.init_cache(cfg, B, cap, ctx_len=ctx_len))
        out["cache"] = cache_shape
        shardings["cache"] = tree_cache_specs(cfg, sc, cache_shape, mesh)
    return out, shardings


def abstract_state(cfg: ArchConfig, opt_cfg: AdamWCfg):
    """Abstract (params, opt) pytree — no allocation."""
    params = jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
    opt = jax.eval_shape(functools.partial(init_opt_state, cfg=opt_cfg),
                         params)
    return {"params": params, "opt": opt}


def _extend_fsdp_to_pod(spec: P, shape, mesh: Mesh) -> P:
    """ZeRO-1 across pods: optimizer-state dims sharded by 'data' extend to
    ('data', 'pod') when divisible — m/v never cross the pod boundary except
    in the once-per-step update, so the extra sharding is DCN-free at use."""
    if "pod" not in mesh.axis_names:
        return spec
    total = mesh.shape["data"] * mesh.shape["pod"]
    parts = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
        if ax == "data" and dim % total == 0:
            parts.append(("data", "pod"))
        else:
            parts.append(ax)
    return P(*parts)


def _opt_specs(cfg, sc, tree_shape, mesh):
    """Param-spec tree for optimizer states, FSDP extended across 'pod'.
    (PartitionSpec is a tuple subclass, so map over flattened lists —
    jax.tree.map would descend into the specs themselves.)"""
    flat, treedef = jax.tree_util.tree_flatten(tree_shape)
    specs_flat = treedef.flatten_up_to(
        tree_param_specs(cfg, sc, tree_shape, mesh))
    out = [_extend_fsdp_to_pod(sp, leaf.shape, mesh)
           for leaf, sp in zip(flat, specs_flat)]
    return jax.tree_util.tree_unflatten(treedef, out)


def state_specs(cfg: ArchConfig, sc: ShardCfg, state_shape, mesh: Mesh):
    pspecs = tree_param_specs(cfg, sc, state_shape["params"], mesh)
    opt = state_shape["opt"]
    mu = _opt_specs(cfg, sc, opt.mu, mesh)
    nu = _opt_specs(cfg, sc, opt.nu, mesh)
    return {"params": pspecs, "opt": OptState(P(), mu, nu)}


# ------------------------------------------------------------ step fns

def make_train_step(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
                    sc: Optional[ShardCfg] = None,
                    opt_cfg: Optional[AdamWCfg] = None,
                    n_micro: Optional[int] = None,
                    attn_block: int = 1024):
    """Returns (train_step, state_shardings, batch_shardings).

    train_step(state, batch) -> (state, metrics); donates state."""
    sc = sc or shard_cfg_for(cfg, shape)
    opt_cfg = opt_cfg or AdamWCfg()
    n_micro = n_micro or pick_microbatches(cfg, shape, mesh)
    params_shape = jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
    shd = ModelSharding(cfg, sc, mesh, params_shape)
    dp = batch_spec(mesh)

    def loss(params, mb):
        return lm.loss_fn(cfg, params, mb, attn_block=attn_block, remat=True,
                          shd=shd)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        # hoist the big FSDP gathers (embed / lm_head) out of the
        # microbatch loop — inside it they re-gather every iteration
        params_use = dict(params)
        params_use["embed"] = shd.embed(params["embed"])
        params_use["lm_head"] = shd.head(params["lm_head"])
        if n_micro > 1:
            def resh(x):
                b = x.shape[0]
                x = x.reshape((n_micro, b // n_micro) + x.shape[1:])
                # keep the microbatch slices DP-sharded (reshape across the
                # batch dim otherwise triggers an all-gather)
                return jax.lax.with_sharding_constraint(
                    x, P(*((None, dp[0] if len(dp) == 1 else dp)
                           + (None,) * (x.ndim - 2))))
            mbs = jax.tree.map(resh, batch)
            loss_val, grads = accumulate_microbatches(
                loss, params_use, mbs,
                grad_specs=tree_param_specs(cfg, sc, params_shape, mesh))
        else:
            loss_val, grads = jax.value_and_grad(loss)(params_use, batch)
        if sc.grad_compress_bf16:
            grads = jax.tree.map(lambda g: g.astype(BF16), grads)
        new_params, new_opt, metrics = apply_updates(params, grads, opt,
                                                     opt_cfg)
        metrics["loss"] = loss_val
        return {"params": new_params, "opt": new_opt}, metrics

    st_shape = abstract_state(cfg, opt_cfg)
    st_specs = state_specs(cfg, sc, st_shape, mesh)
    _, in_sh = input_specs(cfg, shape, mesh, sc)
    jitted = jax.jit(
        train_step,
        in_shardings=as_shardings((st_specs, in_sh["batch"]), mesh),
        out_shardings=as_shardings((st_specs, P()), mesh),
        donate_argnums=(0,),
    )
    return jitted, st_specs, in_sh


def make_prefill_step(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
                      sc: Optional[ShardCfg] = None,
                      attn_block: int = 1024):
    sc = sc or shard_cfg_for(cfg, shape)
    B, S = shape.global_batch, shape.seq_len
    dec_len = min(448, S) if cfg.is_encdec else S
    cap = dec_len + 128 if not cfg.sliding_window else dec_len

    params_shape = jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
    shd = ModelSharding(cfg, sc, mesh, params_shape)

    def prefill_step(params, tokens, ctx=None):
        logits, cache = lm.prefill(cfg, params, tokens, ctx,
                                   seq_cap=cap, attn_block=attn_block,
                                   shd=shd)
        return logits, cache

    pspecs = tree_param_specs(cfg, sc, params_shape, mesh)
    abs_in, in_sh = input_specs(cfg, shape, mesh, sc)
    args = (pspecs, in_sh["tokens"]) + \
        ((in_sh["ctx"],) if "ctx" in in_sh else ())
    cache_shape = jax.eval_shape(
        prefill_step, params_shape, abs_in["tokens"],
        *([abs_in["ctx"]] if "ctx" in abs_in else []))[1]
    cache_specs = tree_cache_specs(cfg, sc, cache_shape, mesh)
    jitted = jax.jit(prefill_step, in_shardings=as_shardings(args, mesh),
                     out_shardings=as_shardings((P(), cache_specs), mesh))
    return jitted, pspecs, in_sh


def make_decode_step(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
                     sc: Optional[ShardCfg] = None):
    sc = sc or shard_cfg_for(cfg, shape)
    params_shape = jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
    shd = ModelSharding(cfg, sc, mesh, params_shape)

    def decode(params, token, cache):
        return lm.decode_step(cfg, params, token, cache, shd=shd)

    pspecs = tree_param_specs(cfg, sc, params_shape, mesh)
    abs_in, in_sh = input_specs(cfg, shape, mesh, sc)
    jitted = jax.jit(
        decode,
        in_shardings=as_shardings((pspecs, in_sh["token"], in_sh["cache"]),
                                  mesh),
        out_shardings=as_shardings((P(), in_sh["cache"]), mesh),
        donate_argnums=(2,),
    )
    return jitted, pspecs, in_sh, abs_in
