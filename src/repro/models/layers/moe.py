"""Top-k MoE with capacity-based dispatch (expert-parallel over 'model').

Dispatch works per top-k SLOT (a Python loop of K ≤ 8 iterations), never
materializing a (K*T, D) buffer:

  slot k: scatter its (T, D) tokens into the (E, cap, D) expert buffer
  experts: one batched einsum over (E sharded, cap, D)
  combine: slot k gathers its (T, D) outputs and ACCUMULATES — token-aligned
           add, no scatter at all.

Positions-in-expert come from a k-major masked cumsum (slot 0 wins capacity
ties over slot 1, etc.).  E is padded to a multiple of the model-axis width
(granite-moe's 40 -> 48) with router logits pinned to -inf on pads;
overflow drops the assignment and the gate renormalizes.

`constrain` (optional) pins token-major intermediates to the batch axes —
without it XLA replicated the dispatch chain at 512 devices (42 GiB/device
observed); with it the whole dispatch is ~(T/n_batch_devices) local.

The expert-capacity overflow selection is the same top-k primitive as the
PQ tournament — on TPU both lower to the bitonic_topk kernel
(DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec  # noqa: F401  (shard_map specs)


@dataclasses.dataclass(frozen=True)
class MoEDims:
    n_experts: int  # real experts
    n_experts_pad: int  # padded to model-axis multiple
    top_k: int
    capacity_factor: float = 1.25


def moe_block(
    x: jnp.ndarray,  # (B, S, D)
    router_w: jnp.ndarray,  # (D, E_pad)
    w_gate: jnp.ndarray,  # (E_pad, D, F)
    w_up: jnp.ndarray,  # (E_pad, D, F)
    w_down: jnp.ndarray,  # (E_pad, F, D)
    dims: MoEDims,
    constrain_tokens: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    constrain_experts: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,D), aux_loss ()) — aux is the standard
    load-balancing loss (Switch §2.2)."""
    B, S, D = x.shape
    T = B * S
    E, K = dims.n_experts_pad, dims.top_k
    ct = constrain_tokens or (lambda a: a)
    ce = constrain_experts or (lambda a: a)
    xt = ct(x.reshape(T, D))

    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T, E)
    e_iota = jnp.arange(E, dtype=jnp.int32)
    logits = jnp.where(e_iota[None, :] < dims.n_experts, logits, -1e30)
    logits = ct(logits)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, sel = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    sel, gate_vals = ct(sel), ct(gate_vals)

    # Load-balancing aux loss over REAL experts.
    me = jnp.mean(probs[:, : dims.n_experts], axis=0)
    occ = jnp.zeros((E,), jnp.float32)
    for k in range(K):
        occ = occ + jnp.mean(jax.nn.one_hot(sel[:, k], E, dtype=jnp.float32), axis=0)
    aux = dims.n_experts * jnp.sum(me * occ[: dims.n_experts])

    cap = int(max(1, (T * K / E) * dims.capacity_factor))
    cap = min(cap, T)

    # Positions-in-expert, k-major (slot 0 first): per-slot masked cumsum
    # plus offsets of all previous slots.
    base = jnp.zeros((E,), jnp.int32)  # tokens already placed per expert
    buf = jnp.zeros((E, cap, D), x.dtype)
    slot_pos = []
    for k in range(K):
        onehot = jax.nn.one_hot(sel[:, k], E, dtype=jnp.int32)  # (T, E)
        onehot = ct(onehot)
        within = jnp.cumsum(onehot, axis=0) - onehot  # exclusive
        pos_k = jnp.sum(within * onehot, axis=1) + base[sel[:, k]]  # (T,)
        base = base + jnp.sum(onehot, axis=0)
        keep = pos_k < cap
        e_safe = jnp.where(keep, sel[:, k], E)
        p_safe = jnp.where(keep, pos_k, 0)
        buf = buf.at[e_safe, p_safe].set(xt, mode="drop")
        slot_pos.append((e_safe, p_safe, keep))

    buf = ce(buf)
    # Expert compute (E sharded over 'model' by the param specs).
    g = ce(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = ce(jnp.einsum("ecd,edf->ecf", buf, w_up))
    h = jax.nn.silu(g) * u
    out_buf = ce(jnp.einsum("ecf,efd->ecd", h, w_down))  # (E, cap, D)

    # Combine: per-slot token-aligned gather + weighted accumulate.
    out = jnp.zeros((T, D), jnp.float32)
    for k, (e_safe, p_safe, keep) in enumerate(slot_pos):
        gathered = ct(out_buf[e_safe, p_safe].astype(jnp.float32))  # (T, D)
        w = gate_vals[:, k].astype(jnp.float32) * keep
        out = out + gathered * w[:, None]
    return ct(out).reshape(B, S, D).astype(x.dtype), aux


def moe_block_ep(
    x: jnp.ndarray,  # (B, S, D) — batch-sharded, REPLICATED over 'model'
    router_w: jnp.ndarray,  # (D, E_pad) replicated
    w_gate: jnp.ndarray,  # (E_pad@model, D, F)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # (E_pad@model, F, D)
    dims: MoEDims,
    mesh,
    batch_axes: Tuple[str, ...],
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE as shard_map — the TPU-native dispatch for a
    replicated-activation layout.

    Because the residual stream is replicated across the model axis, every
    model-column already HAS every token: dispatch requires NO communication
    at all.  Each column routes its local-batch tokens to the experts it
    owns, runs them, and the per-column partial outputs all-reduce over the
    model axis (the row-parallel pattern, same as the dense FFN's w_down).

    Observed at 512 devices vs. the naive scatter formulation: per-device
    FLOPs drop 16x (experts actually shard) and dispatch collectives drop
    from ~1.8 TB to one (B_loc, S, D) psum per layer.

    Capacity note: the slot budget is per (column, batch-row) —
    cap_loc = T_loc * K / E_pad * cf — so overflow drops are decided
    locally (documented divergence from the global-capacity formulation).
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = dims.n_experts_pad, dims.top_k
    n_cols = mesh.shape[model_axis]
    assert E % n_cols == 0, (E, n_cols)
    E_loc = E // n_cols

    def body(xb, rw, wg, wu, wd):
        Bl, Sl, _ = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, D)
        logits = xt.astype(jnp.float32) @ rw.astype(jnp.float32)
        e_iota = jnp.arange(E, dtype=jnp.int32)
        logits = jnp.where(e_iota[None, :] < dims.n_experts, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, sel = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )

        me_frac = jnp.mean(probs[:, : dims.n_experts], axis=0)
        occ = jnp.zeros((E,), jnp.float32)
        for k in range(K):
            occ = occ + jnp.mean(
                jax.nn.one_hot(sel[:, k], E, dtype=jnp.float32), axis=0
            )
        aux = dims.n_experts * jnp.sum(me_frac * occ[: dims.n_experts])
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)  # replicate across batch rows

        col = jax.lax.axis_index(model_axis)
        cap = int(max(1, (T * K / E) * dims.capacity_factor))
        cap = min(cap, T)

        buf = jnp.zeros((E_loc, cap, D), xb.dtype)
        slot_meta = []
        base = jnp.zeros((E_loc,), jnp.int32)
        for k in range(K):
            ek = sel[:, k]
            is_local = (ek // E_loc) == col
            le = jnp.where(is_local, ek % E_loc, E_loc)
            onehot = jax.nn.one_hot(le, E_loc, dtype=jnp.int32)  # (T, E_loc)
            within = jnp.cumsum(onehot, axis=0) - onehot
            pos = jnp.sum(within * onehot, axis=1) + jnp.where(
                is_local, base[jnp.minimum(le, E_loc - 1)], 0
            )
            base = base + jnp.sum(onehot, axis=0)
            keep = is_local & (pos < cap)
            e_safe = jnp.where(keep, le, E_loc)
            p_safe = jnp.where(keep, pos, 0)
            buf = buf.at[e_safe, p_safe].set(xt, mode="drop")
            slot_meta.append((e_safe, p_safe, keep))

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)  # (E_loc, cap, D)

        out = jnp.zeros((T, D), jnp.float32)
        for k, (e_safe, p_safe, keep) in enumerate(slot_meta):
            gathered = out_buf.at[e_safe, p_safe].get(
                mode="fill", fill_value=0.0
            ).astype(jnp.float32)
            w = gate_vals[:, k].astype(jnp.float32) * keep
            out = out + gathered * w[:, None]
        out = jax.lax.psum(out, model_axis)  # row-parallel combine
        return out.reshape(Bl, Sl, D).astype(xb.dtype), aux

    from jax import shard_map

    bspec = batch_axes if batch_axes else None
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    return fn(x, router_w, w_gate, w_up, w_down)
