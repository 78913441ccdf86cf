"""Sharded ShapeDtypeStruct builders for the dry-run.

Everything here produces abstract inputs only — no device allocation.  The
trees mirror the runtime structures exactly (TrainState / DecodeState /
batch dicts) with NamedShardings attached, so ``jit(fn).lower(*sds)`` proves
the real distribution config.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.configs import shapes as shape_lib
from repro.distributed import context as dctx
from repro.models import backbone, common
from repro.models.common import Spec
from repro.train import trainer

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sds(shape, dtype, mesh, pspec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(dctx.auto_axes(mesh), pspec))


def _replicated(sds_tree, mesh):
    return jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, mesh, P()), sds_tree)


# ------------------------------------------------------------------ params
def param_sds(run: RunConfig, mesh, dtype=None):
    """Sharded param SDS tree (resolved under the active rule table)."""
    mcfg = run.model
    dtype = dtype or DTYPES[run.train.param_dtype]
    specs = backbone.model_specs(mcfg)

    def one(s: Spec):
        return _sds(s.shape, dtype, mesh, s.pspec())

    return jax.tree.map(one, specs, is_leaf=common.is_spec)


def _fp32_like(tree, mesh):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, jnp.float32, sharding=s.sharding), tree)


def _factored_sds(run: RunConfig, mesh):
    """Adafactor v_row/v_col SDS with axis-consistent shardings."""
    specs = backbone.model_specs(run.model)

    def row(s: Spec):
        if len(s.shape) >= 2:
            return _sds(s.shape[:-1], jnp.float32, mesh,
                        dctx.pspec_for(s.shape[:-1], s.axes[:-1]))
        return _sds(s.shape, jnp.float32, mesh, s.pspec())

    def col(s: Spec):
        if len(s.shape) >= 2:
            shp = s.shape[:-2] + s.shape[-1:]
            axes = s.axes[:-2] + s.axes[-1:]
            return _sds(shp, jnp.float32, mesh, dctx.pspec_for(shp, axes))
        return _sds((), jnp.float32, mesh, P())

    return (jax.tree.map(row, specs, is_leaf=common.is_spec),
            jax.tree.map(col, specs, is_leaf=common.is_spec))


def train_state_sds(run: RunConfig, mesh) -> trainer.TrainState:
    tcfg = run.train
    params = param_sds(run, mesh)
    master = _fp32_like(params, mesh) if (
        tcfg.optimizer == "adamw" and tcfg.master_weights
        and DTYPES[tcfg.param_dtype] != jnp.float32) else None
    if tcfg.optimizer == "adamw":
        from repro.train.optim import AdamWState
        opt = AdamWState(mu=_fp32_like(params, mesh),
                         nu=_fp32_like(params, mesh))
    else:
        from repro.train.optim import AdafactorState
        vr, vc = _factored_sds(run, mesh)
        opt = AdafactorState(v_row=vr, v_col=vc)
    sync = None
    if tcfg.thinned_sync:
        from repro.train.compression import SyncState
        sync = SyncState(err=_fp32_like(params, mesh))
    return trainer.TrainState(
        step=_sds((), jnp.int32, mesh, P()),
        params=params, master=master, opt=opt, sync=sync)


# ------------------------------------------------------------------- batch
def batch_sds(run: RunConfig, shape: shape_lib.ShapeSpec, mesh) -> dict:
    mcfg = run.model
    specs = shape_lib.input_specs(mcfg, shape)
    axes = shape_lib.batch_axes(mcfg, shape)
    out = {}
    for k, s in specs.items():
        names = backbone.parse_axes(axes[k])
        out[k] = _sds(s.shape, s.dtype, mesh,
                      dctx.pspec_for(s.shape, names))
    return out


def rng_sds(mesh):
    return _sds((2,), jnp.uint32, mesh, P())


# ------------------------------------------------------------------ decode
def decode_state_sds(run: RunConfig, mesh, shape: shape_lib.ShapeSpec,
                     dtype=jnp.bfloat16) -> backbone.DecodeState:
    mcfg = run.model
    B = shape.global_batch
    sds = jax.eval_shape(
        lambda: backbone.init_decode_state(mcfg, B, shape.seq_len, dtype))
    axes = backbone.decode_state_axes(mcfg)
    return jax.tree.map(
        lambda s, a: _sds(s.shape, s.dtype, mesh,
                          dctx.pspec_for(s.shape, backbone.parse_axes(a))),
        sds, axes)
