"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The baseline `ffn.moe` relies on XLA SPMD to partition a sort-based scatter
into the [E, cap, D] dispatch buffer; the dry-run showed SPMD resolves that
scatter as *partial all-reduces of the whole buffer* (kimi train_4k: 194 TB
of all-reduce per chip per step — ~400x the compute time).  This module is
the production EP formulation: tokens are exchanged between expert shards
with an explicit `lax.all_to_all` over the 'model' axis inside a
`shard_map` — what Mixtral/DeepSeek-scale systems actually run.

Ownership layout (inside shard_map over the full mesh):
  * the flattened token stream [T, D] is sharded over the data axes; within
    a data row it is chunked over 'model' — chip m owns contiguous chunk m
    and routes only its own tokens (no duplicated decisions, no psum on the
    return path: the output block IS the owner's chunk).
  * expert weights are sharded over 'model' (E_loc = E/M experts per chip);
    their FSDP data-dim shard is re-gathered by jit at entry (2.1 GB/layer
    for kimi — 500x less wire than the SPMD-scatter baseline).
  * dispatch: [M, E_loc, cap, D] buffers, one block per peer, fixed-size
    all_to_all out and back.

Used for train/prefill (T divisible by the mesh); decode keeps the dense
ffn.moe path (tiny T; its cost there is weight residency, fixed by the
serve sharding rules).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common, ffn


def _route(xf, router_w, num_experts: int, E_pad: int, top_k: int,
           router_dtype=jnp.float32):
    logits = jnp.einsum("td,de->te", xf.astype(router_dtype),
                        router_w.astype(router_dtype))
    if E_pad > num_experts:
        logits = jnp.where(jnp.arange(E_pad) >= num_experts, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eid = jax.lax.top_k(probs, top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((E_pad,)).at[eid.reshape(-1)].add(1.0) / eid.size
    aux = num_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return gate_w, eid, aux, z


def _owned_chunk_moe(xc, router_w, w_gate, w_up, w_down, *,
                     num_experts: int, top_k: int, cap: int,
                     model_axis: str, M: int):
    """EP body for one chip's owned chunk.  xc: [tc, D]; w_*: [E_loc,D,F]."""
    tc, D = xc.shape
    E_loc = w_gate.shape[0]
    E_pad = E_loc * M

    gate_w, eid, aux, z = _route(xc, router_w, num_experts, E_pad, top_k)

    flat_e = eid.reshape(-1)                                  # [tc*k]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    idx = jnp.arange(tc * top_k)
    is_start = jnp.concatenate([jnp.array([True]),
                                sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank = idx - seg_start                                    # slot in expert
    keep = rank < cap
    src_token = order // top_k

    # dispatch buffer grouped by destination shard: [M, E_loc, cap, D]
    dest_shard = sorted_e // E_loc
    dest_slot = (sorted_e % E_loc) * cap + rank
    dest = jnp.where(keep, dest_shard * (E_loc * cap) + dest_slot,
                     M * E_loc * cap)
    buf = jnp.zeros((M * E_loc * cap, D), xc.dtype).at[dest].set(
        xc[src_token], mode="drop").reshape(M, E_loc, cap, D)

    # ---- EP exchange out: experts receive their tokens from every peer --
    recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                              concat_axis=0)                  # [M,E_loc,cap,D]

    xe = jnp.moveaxis(recv, 0, 1).reshape(E_loc, M * cap, D)
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(xe.dtype))
    h = common.swiglu(g, u)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(xe.dtype))

    # ---- EP exchange back: results return to the owning peers ----------
    back = jnp.moveaxis(ye.reshape(E_loc, M, cap, D), 1, 0)
    ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                             concat_axis=0).reshape(M * E_loc * cap, D)

    contrib = jnp.where(keep[:, None],
                        ret[jnp.minimum(dest, M * E_loc * cap - 1)],
                        0).astype(xc.dtype)
    w_flat = gate_w.reshape(-1)[order]
    y = jnp.zeros((tc, D), xc.dtype).at[src_token].add(
        contrib * w_flat[:, None].astype(xc.dtype))

    drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return y, aux, z, drop


def moe_ep(p: dict, x: jax.Array, *, num_experts: int, top_k: int,
           capacity_factor: float = 1.25, mesh=None) -> Tuple[jax.Array, dict]:
    """Drop-in replacement for ffn.moe with explicit EP all-to-all.

    Falls back to ffn.moe without a mesh / 'model' axis / divisible token
    count.  Parameter tree identical to ffn.moe_specs.
    """
    from repro.distributed import context as dctx
    mesh = mesh or dctx.get_mesh()
    B, S, D = x.shape
    T = B * S
    if mesh is None or "model" not in mesh.axis_names:
        return ffn.moe(p, x, num_experts=num_experts, top_k=top_k,
                       capacity_factor=capacity_factor)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    M = sizes["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    n_data = math.prod(sizes[a] for a in data_axes) if data_axes else 1
    E_pad = p["router"].shape[1]
    # Ownership = (batch block over data, SEQUENCE chunk over model): the
    # [B, S, D] layout passes the shard_map boundary unchanged — a flat
    # [T, D] reshape across mixed tile assignments made XLA fall back to
    # full-tensor rematerialization (30 GB f32 per transition, measured).
    if B % n_data or S % M or E_pad % M:
        return ffn.moe(p, x, num_experts=num_experts, top_k=top_k,
                       capacity_factor=capacity_factor)

    tc = (B // n_data) * (S // M)             # tokens owned per chip
    cap = int(math.ceil(tc * top_k / E_pad * capacity_factor))
    cap = max(8, -(-cap // 8) * 8)

    def body(xb, router_w, w_gate, w_up, w_down):
        b_loc, s_loc, _ = xb.shape
        y, aux, z, drop = _owned_chunk_moe(
            xb.reshape(b_loc * s_loc, D), router_w, w_gate, w_up, w_down,
            num_experts=num_experts, top_k=top_k, cap=cap,
            model_axis="model", M=M)
        names = data_axes + ("model",)
        return (y.reshape(b_loc, s_loc, D), jax.lax.pmean(aux, names),
                jax.lax.pmean(z, names), jax.lax.pmean(drop, names))

    tok_spec = P(data_axes if data_axes else None, "model", None)
    y, aux, z, drop = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(), P("model"), P("model"), P("model")),
        out_specs=(tok_spec, P(), P(), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    y = common.shard(y, "batch", "seq", None)
    if "shared" in p:
        sg = jax.nn.sigmoid(jnp.einsum(
            "bsd,dz->bsz", x.astype(jnp.float32),
            p["shared_gate"].astype(jnp.float32)))
        y = y + ffn.mlp(p["shared"], x) * sg.astype(x.dtype)

    metrics = {"moe_aux_loss": aux, "moe_z_loss": z, "moe_drop_frac": drop}
    return y, metrics
