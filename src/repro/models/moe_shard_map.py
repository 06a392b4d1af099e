"""shard_map MoE dispatch: per-shard local capacity + expert all-to-all.

Why this exists: the pjit/dense dispatch (models/moe.py) scatters tokens into
the grouped buffer with data-dependent indices over a *global* flat axis —
XLA's SPMD partitioner cannot shard that scatter/gather and falls back to
all-gathering the (T·k, d_model) dispatch tensors (measured: 34 GB/device at
jamba's 1M-token prefill). Here every device dispatches only its own tokens
(local cumsum → local scatter into an (E, C_local) slice), then one
``all_to_all`` over the model axis exchanges expert ownership for token
ownership — the textbook EP exchange, and the only collective in the path.

Semantics difference vs the dense path: capacity is **per data×SP shard**
(C_local = ceil(T_local·k·cf/E)) rather than global — per-shard capacity is
what large MoE systems actually deploy (it bounds the a2a payload
deterministically). With axis sizes of 1 the two paths agree exactly (tested).

Applicability: EP only (n_experts divisible by the model axis); grok-1
(8 experts) keeps the dense expert-TP path.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig


def _local_dispatch(xf, probs, k: int, c_loc: int, e: int):
    """Local capacity dispatch over this shard's tokens.
    xf: (T_loc, D); probs: (T_loc, E) → (grouped (E, C_loc, D), slot, keep, gates)."""
    t, d = xf.shape
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    flat_e = expert_idx.reshape(t * k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos_in_e = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)
    keep = pos_in_e < c_loc
    slot = jnp.where(keep, flat_e * c_loc + pos_in_e, 0)
    x_rep = jnp.broadcast_to(xf[:, None, :], (t, k, d)).reshape(t * k, d)
    x_rep = x_rep * keep[:, None].astype(xf.dtype)
    grouped = jnp.zeros((e * c_loc, d), xf.dtype).at[slot].add(x_rep)
    return grouped.reshape(e, c_loc, d), slot, keep, gate_vals


def moe_apply_shard_map(
    params: dict,
    x: jax.Array,  # (B, S, D) sharded (batch→data axes, seq→model [SP])
    cfg: ModelConfig,
    mesh,
    rules,
) -> Tuple[jax.Array, jax.Array]:
    e, k = cfg.n_experts, cfg.top_k
    model_n = mesh.shape["model"]
    assert e % model_n == 0, "shard_map MoE requires EP divisibility"
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_axes = rules.get("batch") or data_axes
    seq_axes = rules.get("seq_res")
    sp = model_n if seq_axes else 1

    b, s, d = x.shape
    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]
    if b % n_data != 0 or s % sp != 0:
        batch_axes, n_data = (), 1  # fall back to replicated-batch blocks
    t_loc = (b // n_data) * (s // sp)
    c_loc = max(k, int(math.ceil(t_loc * k * cfg.capacity_factor / e)))

    x_spec = P(batch_axes if batch_axes else None, "model" if seq_axes else None, None)
    w_in_spec = P("model", None, None)  # (E, D, F) EP
    w_out_spec = P("model", None, None)  # (E, F, D)

    def block(xb, router, wi_g, wi_u, wo):
        bl, sl, _ = xb.shape
        xf = xb.reshape(bl * sl, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        grouped, slot, keep, gates = _local_dispatch(xf, probs, k, c_loc, e)

        # aux loss (Switch): local fractions, averaged over every shard
        me = probs.mean(axis=0)
        ce_cnt = jnp.zeros((e,), jnp.float32).at[slot // c_loc].add(
            keep.astype(jnp.float32)
        ) / (bl * sl * k)
        aux = e * jnp.sum(me * ce_cnt)
        axes = tuple(batch_axes) + (("model",) if seq_axes else ())
        if axes:
            aux = jax.lax.pmean(aux, axes)

        # EP exchange: expert ownership ↔ token ownership over 'model'
        grouped = jax.lax.all_to_all(
            grouped, "model", split_axis=0, concat_axis=1, tiled=True
        )  # (E_loc, C_loc·model_n, D)

        gate = jnp.einsum("ecd,edf->ecf", grouped, wi_g)
        up = jnp.einsum("ecd,edf->ecf", grouped, wi_u)
        if cfg.mlp_kind == "geglu":
            act = jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(xb.dtype)
        else:
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(xb.dtype)
        h = jnp.einsum("ecf,efd->ecd", act * up, wo)  # (E_loc, C_loc·model_n, D)

        h = jax.lax.all_to_all(
            h, "model", split_axis=1, concat_axis=0, tiled=True
        )  # (E, C_loc, D)

        y_rep = h.reshape(e * c_loc, d)[slot] * (
            gates.reshape(-1, 1) * keep[:, None]
        ).astype(h.dtype)
        y = y_rep.reshape(bl * sl, k, d).sum(axis=1)
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_in_spec, w_in_spec, w_out_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])
    return y, aux
