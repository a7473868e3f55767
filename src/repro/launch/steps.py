"""Jitted step builders: train_step (pjit baseline), the eigen-compressed
hybrid train/refresh steps (paper technique, role R2), and serve steps.

Two compiled functions implement eigen compression (production-style, like
multi-program MaxText):
  * ``eigen_train_step``  — every step: project local grads onto the shared
    basis, psum the (r x n) coordinates, low-rank Adam, error feedback.
  * ``eigen_refresh_step`` — every K steps: recompute per-shard gradient
    bases and combine them with Algorithm 1/2 across the data axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import data_axes
from repro.launch.sharding import (
    batch_shardings,
    batch_spec,
    cache_shardings,
    param_shardings,
    replicated,
    rules_for,
    spec_for_axes,
)
from repro.models.config import ModelConfig
from repro.models.registry import build
from repro.models.sharding_ctx import activation_sharding, no_activation_sharding
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim import eigen_compress as EC


# ----------------------------------------------------------- baseline step --
def make_train_step(cfg: ModelConfig, mesh, *, adamw_cfg: AdamWConfig, schedule):
    """Pure-pjit train step: XLA inserts the DP grad all-reduce / FSDP
    collectives from the in/out shardings."""
    api = build(cfg)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(api.loss, has_aux=True)(
            params, batch
        )
        lr = schedule(opt_state["step"])
        new_params, new_opt, om = adamw_update(
            grads, opt_state, params, lr=lr, cfg=adamw_cfg
        )
        out_metrics = {"loss": loss, "lr": lr, **metrics, **om}
        return new_params, new_opt, out_metrics

    return train_step


def shardings_for_train(cfg: ModelConfig, mesh, values_like, axes, batch_like):
    """(param_shardings, opt_shardings, batch_shardings, metric_shardings)."""
    ps = param_shardings(values_like, axes, mesh, cfg)
    opt_like = jax.eval_shape(adamw_init, values_like)
    os_ = {
        "m": ps,
        "v": ps,
        "step": replicated(mesh),
    }
    bs = batch_shardings(batch_like, mesh)
    return ps, os_, bs


def jit_train_step(cfg, mesh, values_like, axes, batch_like, *, adamw_cfg, schedule):
    fn = make_train_step(cfg, mesh, adamw_cfg=adamw_cfg, schedule=schedule)
    ps, os_, bs = shardings_for_train(cfg, mesh, values_like, axes, batch_like)
    ms = jax.tree.map(
        lambda _: replicated(mesh),
        jax.eval_shape(
            fn,
            values_like,
            jax.eval_shape(adamw_init, values_like),
            batch_like,
        )[2],
    )
    jitted = jax.jit(
        fn,
        in_shardings=(ps, os_, bs),
        out_shardings=(ps, os_, ms),
        donate_argnums=(0, 1),
    )
    return _with_activation_ctx(jitted, mesh), (ps, os_, bs)


# -------------------------------------------------------------- serve steps --
def make_prefill_step(cfg: ModelConfig, mesh):
    api = build(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, batch)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh):
    api = build(cfg)

    def decode_step(params, tokens, cache, pos):
        return api.decode_step(params, tokens, cache, pos)

    return decode_step


def jit_decode_step(cfg, mesh, values_like, axes, cache_like):
    fn = make_decode_step(cfg, mesh)
    ps = param_shardings(values_like, axes, mesh, cfg)
    cs = cache_shardings(cache_like, cfg, mesh)
    batch = jax.tree.leaves(cache_like)[0].shape[1]
    tok_s = NamedSharding(mesh, batch_spec(mesh, 2, leading_dim=batch))
    logit_s = NamedSharding(mesh, batch_spec(mesh, 2, leading_dim=batch))
    pos_s = replicated(mesh)
    jitted = jax.jit(
        fn,
        in_shardings=(ps, tok_s, cs, pos_s),
        out_shardings=(logit_s, cs),
        donate_argnums=(2,),
    )
    return _with_activation_ctx(jitted, mesh), (ps, tok_s, cs, pos_s)


def _with_activation_ctx(jitted, mesh):
    """Wrap a jitted step so tracing (first call / .lower) happens under the
    activation-sharding context (constrain_batch pins batch shardings)."""
    from repro.launch.mesh import data_axes

    class _Wrapped:
        def __init__(self, fn):
            self._fn = fn

        def __call__(self, *a, **k):
            with activation_sharding(mesh, data_axes(mesh)):
                return self._fn(*a, **k)

        def lower(self, *a, **k):
            with activation_sharding(mesh, data_axes(mesh)):
                return self._fn.lower(*a, **k)

    return _Wrapped(jitted)


# ----------------------------------------------- eigen-compressed training --
def compressed_paths(values_like, axes, ecfg: EC.EigenCompressConfig):
    """Select leaves for compression + their matmul-view reshapes.

    2-D/3-D matmul weights compress directly; 4-D attention weights
    (L, embed, heads, head_dim) / (L, heads, head_dim, embed) compress
    through a 3-D view that merges the head dims (axes-metadata driven).
    Diagonal / vector params (SSM cores, norms) are excluded by ndim.
    Returns {path: matmul_view_shape or None}."""
    flat = jax.tree_util.tree_flatten_with_path(values_like)[0]
    ax_flat = (
        {jax.tree_util.keystr(k): a
         for k, a in jax.tree_util.tree_flatten_with_path(
             axes, is_leaf=lambda x: isinstance(x, tuple))[0]}
        if axes is not None else {}
    )
    out = {}
    for k, v in flat:
        path = jax.tree_util.keystr(k)
        shape = v.shape
        view = None
        if v.ndim == 4 and path in ax_flat:
            a = ax_flat[path]
            if a[-3:] == ("embed", "heads", "head_dim"):
                view = (shape[0], shape[1], shape[2] * shape[3])
            elif a[-3:] == ("heads", "head_dim", "embed"):
                view = (shape[0], shape[1] * shape[2], shape[3])
            else:
                continue
            d, n = view[-2], view[-1]
        elif v.ndim in (2, 3):
            d, n = shape[-2], shape[-1]
        else:
            continue
        if d >= ecfg.min_dim and n >= ecfg.rank and d >= ecfg.rank:
            out[path] = view
    return out


def eigen_opt_init(
    values, ecfg: EC.EigenCompressConfig, n_data_shards: int, axes=None
):
    """Optimizer state: full Adam for uncompressed leaves, low-rank state
    (+ per-shard error feedback with a leading shard axis) for compressed."""
    flat = jax.tree_util.tree_flatten_with_path(values)[0]
    comp = compressed_paths(values, axes, ecfg)
    full_m, full_v, eigen = {}, {}, {}
    for k, v in flat:
        path = jax.tree_util.keystr(k)
        if path in comp:
            view = comp[path]
            vv = v if view is None else jax.ShapeDtypeStruct(view, v.dtype)
            st = EC.init_state(vv, ecfg)
            st["err"] = jnp.zeros(
                (n_data_shards,) + tuple(vv.shape), jnp.float32
            )
            eigen[path] = st
        else:
            full_m[path] = jnp.zeros_like(v, dtype=jnp.float32)
            full_v[path] = jnp.zeros_like(v, dtype=jnp.float32)
    return {
        "full_m": full_m,
        "full_v": full_v,
        "eigen": eigen,
        "step": jnp.zeros((), jnp.int32),
    }


def _flatdict(tree):
    return {
        jax.tree_util.keystr(k): v
        for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _unflatten_like(d: Dict[str, Any], like):
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [d[jax.tree_util.keystr(k)] for k, _ in flat]
    )


def make_eigen_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    adamw_cfg: AdamWConfig,
    schedule,
    ecfg: EC.EigenCompressConfig,
    views: Optional[Dict[str, tuple]] = None,
    bf16_psum: bool = False,
):
    """Hybrid manual(data)/auto(model) train step with compressed DP psum.

    Collectives per step: psum(r x n) per compressed leaf (vs d x n for the
    baseline), full psum for uncompressed leaves, psum(1) for the loss.
    """
    api = build(cfg)
    dax = data_axes(mesh)
    axis = dax if len(dax) > 1 else dax[0]

    def per_shard(params, opt_state, batch):
        with no_activation_sharding():
            return _per_shard_impl(params, opt_state, batch)

    def _per_shard_impl(params, opt_state, batch):
        m_shards = jax.lax.psum(jnp.ones((), jnp.float32), axis)
        (loss, metrics), grads = jax.value_and_grad(api.loss, has_aux=True)(
            params, batch
        )
        loss = jax.lax.psum(loss, axis) / m_shards
        step = opt_state["step"] + 1
        lr = schedule(opt_state["step"])
        b1, b2, eps, wd = (
            adamw_cfg.b1,
            adamw_cfg.b2,
            adamw_cfg.eps,
            adamw_cfg.weight_decay,
        )
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        gdict = _flatdict(grads)
        pdict = _flatdict(params)
        new_p, new_fm, new_fv, new_eigen = {}, {}, {}, {}

        for path, g in gdict.items():
            p = pdict[path]
            if path in opt_state["eigen"]:
                view = (views or {}).get(path)
                if view is not None:
                    g = g.reshape(view)  # 4-D attention grads -> matmul view
                st = dict(opt_state["eigen"][path])
                st_local = dict(st)
                st_local["err"] = st["err"][0]  # manual shard slice
                g_hat, g_low = EC.compress_and_reduce(g, st_local, axis_name=axis)
                m_new = b1 * st["m"] + (1 - b1) * g_low
                v_new = b2 * st["v"] + (1 - b2) * g_low * g_low
                delta_low = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
                if g.ndim == 2:
                    delta = st["basis"] @ delta_low
                else:
                    delta = jnp.einsum("ldr,lrn->ldn", st["basis"], delta_low)
                if view is not None:
                    delta = delta.reshape(p.shape)
                if wd > 0:
                    delta = delta + wd * p.astype(jnp.float32)
                new_p[path] = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
                err = EC.new_error(g, st_local, ecfg)
                st["m"], st["v"] = m_new, v_new
                st["err"] = err[None]
                new_eigen[path] = st
            else:
                if bf16_psum:
                    # §Perf C: halve the uncompressed DP-psum bytes.
                    gf = jax.lax.psum(
                        g.astype(jnp.bfloat16), axis
                    ).astype(jnp.float32) / m_shards
                else:
                    gf = jax.lax.psum(g.astype(jnp.float32), axis) / m_shards
                m_new = b1 * opt_state["full_m"][path] + (1 - b1) * gf
                v_new = b2 * opt_state["full_v"][path] + (1 - b2) * gf * gf
                delta = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
                if wd > 0 and p.ndim >= 2:
                    delta = delta + wd * p.astype(jnp.float32)
                new_p[path] = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
                new_fm[path], new_fv[path] = m_new, v_new

        new_params = _unflatten_like(new_p, params)
        new_opt = {
            "full_m": new_fm,
            "full_v": new_fv,
            "eigen": new_eigen,
            "step": step,
        }
        return new_params, new_opt, {"loss": loss, "lr": lr, "aux": metrics["aux"]}

    return per_shard, axis


def make_eigen_refresh_step(
    cfg: ModelConfig,
    mesh,
    *,
    ecfg: EC.EigenCompressConfig,
    views: Optional[Dict[str, tuple]] = None,
):
    """Recompute per-shard gradient eigenbases and Procrustes-average them
    (Algorithm 1/2) into the shared projection basis.  Adam's low-rank
    moments are rotated into the new basis via the alignment of new-to-old
    (the same Procrustes primitive, beyond-paper use)."""
    api = build(cfg)
    dax = data_axes(mesh)
    axis = dax if len(dax) > 1 else dax[0]

    def per_shard(params, opt_state, batch, key):
        with no_activation_sharding():
            return _per_shard_impl(params, opt_state, batch, key)

    def _per_shard_impl(params, opt_state, batch, key):
        _, grads = jax.value_and_grad(lambda v: api.loss(v, batch)[0])(params)
        gdict = _flatdict(grads)
        new_eigen = {}
        keys = jax.random.split(key, max(len(opt_state["eigen"]), 1))
        for i, (path, st) in enumerate(sorted(opt_state["eigen"].items())):
            g = gdict[path]
            view = (views or {}).get(path)
            if view is not None:
                g = g.reshape(view)
            st = dict(st)
            basis_new = EC.refresh_basis(
                g,
                st["basis"],
                st["initialized"],
                axis_name=axis,
                cfg=ecfg,
                key=keys[i],
            )
            # Rotate low-rank moments into the new basis: R = P_new^T P_old.
            if g.ndim == 2:
                rot = basis_new.T @ st["basis"]
                st["m"] = rot @ st["m"]
                st["v"] = jnp.abs(rot) ** 2 @ st["v"]  # variance transport approx
            else:
                rot = jnp.einsum("ldr,lds->lrs", basis_new, st["basis"])
                st["m"] = jnp.einsum("lrs,lsn->lrn", rot, st["m"])
                st["v"] = jnp.einsum("lrs,lsn->lrn", jnp.abs(rot) ** 2, st["v"])
            st["basis"] = basis_new
            st["initialized"] = jnp.ones((), jnp.bool_)
            new_eigen[path] = st
        new_opt = dict(opt_state)
        new_opt["eigen"] = new_eigen
        return new_opt

    return per_shard, axis


def jit_eigen_steps(
    cfg, mesh, values_like, axes, batch_like, *, adamw_cfg, schedule, ecfg
):
    """Wrap the per-shard bodies in shard_map (manual data axes, auto model)
    and jit with shardings.  Params must NOT be FSDP-sharded over 'data'
    (compression replaces FSDP's reduce-scatter; enforced here)."""
    import dataclasses

    cfg_nofsdp = dataclasses.replace(cfg, fsdp=False) if cfg.fsdp else cfg
    dax = data_axes(mesh)
    n_data = 1
    for a in dax:
        n_data *= mesh.shape[a]

    ps = param_shardings(values_like, axes, mesh, cfg_nofsdp)
    views = compressed_paths(values_like, axes, ecfg)
    views = {k: v for k, v in views.items() if v is not None}
    opt_like = jax.eval_shape(
        lambda v: eigen_opt_init(v, ecfg, n_data, axes), values_like
    )

    # Build opt shardings: err leaves shard their leading axis over data.
    flat = jax.tree_util.tree_flatten_with_path(opt_like)[0]
    os_dict = {}
    for k, v in flat:
        path = jax.tree_util.keystr(k)
        if "'err'" in path:
            os_dict[path] = NamedSharding(
                mesh, P(dax if len(dax) > 1 else dax[0], *(None,) * (v.ndim - 1))
            )
        else:
            os_dict[path] = replicated(mesh)
    os_ = _unflatten_like(os_dict, opt_like)
    bs = batch_shardings(batch_like, mesh)

    train_body, axis = make_eigen_train_step(
        cfg_nofsdp, mesh, adamw_cfg=adamw_cfg, schedule=schedule, ecfg=ecfg,
        views=views, bf16_psum=getattr(ecfg, "bf16_psum", False),
    )
    refresh_body, _ = make_eigen_refresh_step(
        cfg_nofsdp, mesh, ecfg=ecfg, views=views
    )

    ps_specs = jax.tree.map(lambda s: _manual_only_spec(s, dax), ps)
    os_specs = jax.tree.map(lambda s: _manual_only_spec(s, dax), os_)
    bs_specs = jax.tree.map(lambda s: _manual_only_spec(s, dax), bs)
    scalar_spec = P()

    train_sm = jax.shard_map(
        train_body,
        mesh=mesh,
        in_specs=(ps_specs, os_specs, bs_specs),
        out_specs=(ps_specs, os_specs, {"loss": P(), "lr": P(), "aux": P()}),
        axis_names=set(dax),
        check_vma=False,
    )
    refresh_sm = jax.shard_map(
        refresh_body,
        mesh=mesh,
        in_specs=(ps_specs, os_specs, bs_specs, scalar_spec),
        out_specs=os_specs,
        axis_names=set(dax),
        check_vma=False,
    )
    ms = {"loss": replicated(mesh), "lr": replicated(mesh), "aux": replicated(mesh)}
    train_jit = jax.jit(
        train_sm,
        in_shardings=(ps, os_, bs),
        out_shardings=(ps, os_, ms),
        donate_argnums=(0, 1),
    )
    refresh_jit = jax.jit(
        refresh_sm,
        in_shardings=(ps, os_, bs, replicated(mesh)),
        out_shardings=os_,
        donate_argnums=(1,),
    )
    return train_jit, refresh_jit, (ps, os_, bs)


def _manual_only_spec(sharding: NamedSharding, dax) -> P:
    """Project a NamedSharding's spec onto the MANUAL (data) axes only —
    shard_map in/out specs may not mention auto axes."""
    entries = []
    for e in sharding.spec:
        if e is None:
            entries.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in dax)
            entries.append(kept if kept else None)
        else:
            entries.append(e if e in dax else None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)
