"""Mixture-of-Experts: router + two expert-compute paths.

  * ``moe_dense_oracle`` -- every expert over every token, weighted by the
    sparse gate matrix. Exact (no capacity drops); used as the correctness
    oracle in tests and for tiny smoke configs.
  * ``moe_capacity``    -- gather -> batched-einsum -> scatter-add with a
    fixed per-expert capacity. Exact FLOPs x capacity slack, fully static
    shapes, and shard-friendly: with experts sharded over the ``model`` mesh
    axis each shard evaluates only its local expert slice (``expert_offset``
    / ``n_local``), and the surrounding TP all-reduce combines shards. No
    quadratic one-hot dispatch (DESIGN.md §5).

Params layout (stacked per layer by the transformer builder):
  router: [d, E]
  experts: {"w_gate": [E, d, f], "w_up": [E, d, f], "w_down": [E, f, d]}
  shared: gated-MLP params (optional)
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import InitCtx, act_fn, dense_init


def init_moe(ctx: InitCtx, d: int, n_experts: int, moe_d_ff: int,
             shared_d_ff: int = 0) -> dict:
    e, f = n_experts, moe_d_ff
    p = {
        "router": dense_init(ctx, (d, e)),
        "experts": {
            "w_gate": dense_init(ctx, (e, d, f)),
            "w_up": dense_init(ctx, (e, d, f)),
            "w_down": dense_init(ctx, (e, f, d), scale=1.0 / math.sqrt(f)),
        },
    }
    if shared_d_ff:
        from .layers import init_gated_mlp
        p["shared"] = init_gated_mlp(ctx, d, shared_d_ff)
    return p


def route(router_w: jax.Array, x: jax.Array, topk: int,
          norm_topk: bool, n_valid: Optional[int] = None) -> Tuple:
    """x: [N, d] -> (weights [N,k] f32, ids [N,k] i32, probs [N,E] f32).

    ``n_valid`` masks padded dummy experts (qwen2-moe pads 60 -> 64 for the
    16-way EP shard; dummies never receive tokens)."""
    logits = jnp.einsum("nd,de->ne", x, router_w).astype(jnp.float32)
    if n_valid is not None and n_valid < logits.shape[-1]:
        pad_mask = jnp.arange(logits.shape[-1]) >= n_valid
        logits = jnp.where(pad_mask[None], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, topk)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32), probs


def load_balance_loss(probs: jax.Array, ids: jax.Array, n_valid: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e (over valid experts;
    padded dummies never route so they contribute 0)."""
    e_total = probs.shape[-1]
    onehot = jax.nn.one_hot(ids, e_total, dtype=jnp.float32)     # [N,k,E]
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)                 # fraction routed
    p = jnp.mean(probs, axis=0)
    return n_valid * jnp.sum(f * p)


def moe_dense_oracle(params: dict, x: jax.Array, topk: int,
                     norm_topk: bool = False, act: str = "silu",
                     n_valid: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """[B,S,d] -> ([B,S,d], aux_loss). Computes every expert densely."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    weights, ids, probs = route(params["router"], xf, topk, norm_topk, n_valid)
    e = params["experts"]["w_gate"].shape[0]
    gates = jnp.zeros((xf.shape[0], e), jnp.float32)
    gates = gates.at[jnp.arange(xf.shape[0])[:, None], ids].add(weights)
    g = jnp.einsum("nd,edf->nef", xf, params["experts"]["w_gate"])
    u = jnp.einsum("nd,edf->nef", xf, params["experts"]["w_up"])
    h = act_fn(act)(g) * u
    y = jnp.einsum("nef,efd->ned", h, params["experts"]["w_down"])
    out = jnp.einsum("ned,ne->nd", y, gates.astype(y.dtype))
    aux = load_balance_loss(probs, ids, e if n_valid is None else n_valid)
    return out.reshape(b, s, d), aux


def dispatch_indices(ids: jax.Array, weights: jax.Array, capacity: int,
                     expert_offset: int, n_local: int) -> Tuple:
    """Slot assignment for capacity-based dispatch over a local expert slice.

    ids/weights: [N, k]. Returns (slot_pair [E_loc*C] i32 index into the
    flattened (N*k) pair axis, slot_w [E_loc*C] f32, valid [E_loc*C] bool).
    Tokens beyond an expert's capacity are dropped (standard capacity MoE);
    pairs routed outside [offset, offset+n_local) scatter out-of-bounds and
    are dropped by ``mode="drop"``.
    """
    nk = ids.shape[0] * ids.shape[1]
    ids_f = ids.reshape(-1)                               # [N*k]
    w_f = weights.reshape(-1)
    local = ids_f - expert_offset                         # [N*k]
    sel = (local[:, None] == jnp.arange(n_local)[None])   # [N*k, E_loc]
    rank = jnp.cumsum(sel, axis=0) * sel                  # 1-based rank
    keep = sel & (rank <= capacity)
    oob = n_local * capacity
    flat_pos = jnp.min(jnp.where(keep, local[:, None] * capacity + rank - 1,
                                 oob), axis=1)            # one expert per pair
    pair_idx = jnp.arange(nk, dtype=jnp.int32)
    slot_pair = jnp.zeros((oob,), jnp.int32).at[flat_pos].set(
        pair_idx, mode="drop")
    slot_w = jnp.zeros((oob,), jnp.float32).at[flat_pos].set(w_f, mode="drop")
    valid = jnp.zeros((oob,), bool).at[flat_pos].set(True, mode="drop")
    return slot_pair, slot_w, valid


def moe_capacity(params: dict, x: jax.Array, topk: int, *,
                 capacity_factor: float = 1.25, norm_topk: bool = False,
                 act: str = "silu", n_valid: Optional[int] = None,
                 expert_offset: int = 0, n_local: Optional[int] = None,
                 precomputed_route: Optional[Tuple] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """[B,S,d] -> ([B,S,d], aux). Computes the local expert slice
    [offset, offset+n_local); with EP sharding, shards psum their outputs."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    el = params["experts"]["w_gate"].shape[0]     # local expert count
    n_local = n_local or el
    assert el == n_local, "expert param slice must match n_local"
    if precomputed_route is not None:
        weights, ids, probs = precomputed_route
    else:
        weights, ids, probs = route(params["router"], xf, topk, norm_topk, n_valid)
    e_total = probs.shape[-1]
    e_valid = n_valid or e_total
    capacity = max(1, math.ceil(n * topk * capacity_factor / e_valid))
    slot_pair, slot_w, valid = dispatch_indices(
        ids, weights, capacity, expert_offset, n_local)
    tok = slot_pair // topk
    gathered = xf[tok] * valid[:, None].astype(xf.dtype)          # [E_loc*C, d]
    gt = gathered.reshape(el, capacity, d)
    g = jnp.einsum("ecd,edf->ecf", gt, params["experts"]["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", gt, params["experts"]["w_up"])
    h = act_fn(act)(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["experts"]["w_down"])
    y = y.reshape(el * capacity, d) * slot_w[:, None].astype(y.dtype)
    out = jnp.zeros_like(xf).at[tok].add(y, mode="drop")
    aux = load_balance_loss(probs, ids, e_valid)
    return out.reshape(b, s, d), aux


def _expert_compute(experts: dict, xf: jax.Array, slot_pair, slot_w, valid,
                    capacity: int, act: str, topk: int) -> jax.Array:
    """Gather -> batched expert einsum -> weighted scatter-add. [N,d]->[N,d]."""
    d = xf.shape[-1]
    el = experts["w_gate"].shape[0]
    tok = slot_pair // topk
    gathered = xf[tok] * valid[:, None].astype(xf.dtype)
    gt = gathered.reshape(el, capacity, d)
    g = jnp.einsum("ecd,edf->ecf", gt, experts["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", gt, experts["w_up"])
    h = act_fn(act)(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, experts["w_down"])
    y = y.reshape(el * capacity, d) * slot_w[:, None].astype(y.dtype)
    return jnp.zeros_like(xf).at[tok].add(y, mode="drop")


def moe_ep_shardmap(params: dict, x: jax.Array, *, topk: int, mesh,
                    dp_axes, tp_axis: str = "model",
                    capacity_factor: float = 1.25, norm_topk: bool = False,
                    act: str = "silu", n_valid: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel routed experts via shard_map (DESIGN.md §5).

    x is replicated over the ``model`` axis (the TP all-reduce of the
    preceding attention already guarantees this); each model shard routes
    its local tokens, evaluates only its local expert slice, and a psum
    over ``model`` combines — the same all-reduce a dense TP MLP needs, so
    EP adds no extra collective.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    e_padded = params["experts"]["w_gate"].shape[0]
    tp = mesh.shape[tp_axis]
    assert e_padded % tp == 0, (e_padded, tp)
    e_loc = e_padded // tp
    e_valid = n_valid or e_padded
    x_spec = P(dp_axes, None, None)
    dp_size = 1
    for a in ((dp_axes,) if isinstance(dp_axes, str) else (dp_axes or ())):
        dp_size *= mesh.shape[a]

    def local_fn(router_w, experts, xl):
        b, s, d = xl.shape
        xf = xl.reshape(-1, d)
        n = xf.shape[0]
        offset = jax.lax.axis_index(tp_axis) * e_loc
        weights, ids, probs = route(router_w, xf, topk, norm_topk, n_valid)
        capacity = max(1, math.ceil(n * topk * capacity_factor / e_valid))
        slot_pair, slot_w, valid = dispatch_indices(ids, weights, capacity,
                                                    offset, e_loc)
        out = _expert_compute(experts, xf, slot_pair, slot_w, valid,
                              capacity, act, topk)
        out = jax.lax.psum(out, tp_axis)
        aux = load_balance_loss(probs, ids, e_valid)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return out.reshape(b, s, d), aux

    expert_specs = jax.tree.map(lambda _: P(tp_axis, None, None),
                                params["experts"])
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(P(), expert_specs, x_spec),
                   out_specs=(x_spec, P()),
                   check_vma=False)
    return fn(params["router"], params["experts"], x)
