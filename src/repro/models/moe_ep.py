"""Expert-parallel MoE via shard_map + all_to_all — the structural fix for
the collective-bound MoE training rows (§Perf iteration 3's refuted GSPMD
attempt, done properly).

Tokens are manual-sharded over (data, model); experts over model.  Each
device routes its local tokens to the expert-owner peers along the
``model`` axis with ``all_to_all`` (the canonical EP schedule), computes
its E/M experts, and returns results the same way.  Capacity is enforced
per (source device, destination peer) and per local expert — exactly what
real EP systems do.  Cross-device traffic per layer is
O(local_tokens × top_k × d) instead of the global (E·C, d) buffer
all-reduces GSPMD emits for the gather-based formulation.

Enabled with REPRO_MOE_EP=1 under an active mesh with data+model axes
(single-pod path; the pod axis stays on the GSPMD formulation).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig

Params = Dict[str, Any]


def _round8(n: int) -> int:
    return max(8, -(-n // 8) * 8)


def ep_applicable(m: MoEConfig, n_tokens: int, mesh) -> bool:
    if mesh is None or "data" not in mesh.axis_names \
            or "model" not in mesh.axis_names:
        return False
    D = mesh.shape["data"]
    M = mesh.shape["model"]
    return (n_tokens % (D * M) == 0 and m.n_experts % M == 0
            and n_tokens // (D * M) > 0)


def ep_applicable_seq(m: MoEConfig, B: int, T: int, mesh) -> bool:
    if not ep_applicable(m, B * T, mesh):
        return False
    return T % mesh.shape["model"] == 0 and B % mesh.shape["data"] == 0


def moe_apply_ep(p: Params, m: MoEConfig, x: jnp.ndarray, mesh
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) global.  Returns (y, aux) like moe_apply."""
    B, T, d = x.shape
    N = B * T
    E, k = m.n_experts, m.top_k
    D = mesh.shape["data"]
    M = mesh.shape["model"]
    E_loc = E // M
    N_loc = N // (D * M)
    # capacity per (source device, destination peer)
    C_send = _round8(math.ceil(N_loc * k / M * m.capacity_factor))
    # capacity per local expert (receives from M peers)
    C_exp = _round8(math.ceil(M * C_send / E_loc * m.capacity_factor))

    def body(xb, rw, wg, wu, wd):
        # xb: (N_loc, d) local tokens
        logits = xb.astype(jnp.float32) @ rw                  # (N_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, eidx = jax.lax.top_k(probs, k)                # (N_loc, k)
        gate_w = gate_w / jnp.maximum(
            jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)
        # load-balance aux (global mean via pmean)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], E), axis=0)
        aux = m.router_aux_weight * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(jax.lax.pmean(aux, "data"), "model")

        flat_e = eidx.reshape(-1)                             # (Nk,)
        dest = flat_e // E_loc                                # owner peer
        ohd = jax.nn.one_hot(dest, M, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(ohd, axis=0) - ohd) * ohd, axis=-1)
        keep = pos < C_send
        send_slot = jnp.where(keep, dest * C_send + pos, M * C_send)
        tok = jnp.repeat(jnp.arange(N_loc), k)

        send_x = jnp.zeros((M * C_send, d), xb.dtype
                           ).at[send_slot].set(xb[tok], mode="drop")
        send_el = jnp.full((M * C_send,), -1, jnp.int32
                           ).at[send_slot].set(
            (flat_e % E_loc).astype(jnp.int32), mode="drop")

        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=True)
        recv_el = jax.lax.all_to_all(send_el, "model", 0, 0, tiled=True)

        # group received tokens by local expert
        valid = recv_el >= 0
        el = jnp.clip(recv_el, 0, E_loc - 1)
        ohe = jax.nn.one_hot(el, E_loc, dtype=jnp.int32) * valid[:, None]
        pos_e = jnp.sum((jnp.cumsum(ohe, axis=0) - ohe) * ohe, axis=-1)
        keep2 = valid & (pos_e < C_exp)
        buf_slot = jnp.where(keep2, el * C_exp + pos_e, E_loc * C_exp)
        buf = jnp.zeros((E_loc * C_exp, d), xb.dtype
                        ).at[buf_slot].set(recv_x, mode="drop")
        buf = buf.reshape(E_loc, C_exp, d)

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", g * u, wd)
        out_flat = out.reshape(E_loc * C_exp, d)

        back = out_flat[jnp.minimum(buf_slot, E_loc * C_exp - 1)] \
            * keep2[:, None].astype(xb.dtype)
        send_back = jax.lax.all_to_all(back, "model", 0, 0, tiled=True)

        contrib = send_back[jnp.minimum(send_slot, M * C_send - 1)] \
            * keep[:, None].astype(xb.dtype)
        contrib = contrib * gate_w.reshape(-1)[:, None].astype(xb.dtype)
        y = jnp.zeros((N_loc, d), xb.dtype).at[tok].add(contrib)
        return y, aux

    wg = p["w_gate"].astype(x.dtype)
    wu = p["w_up"].astype(x.dtype)
    wd = p["w_down"].astype(x.dtype)
    def body4(xb4, rw, wg, wu, wd):
        # xb4: (B_loc, 1, T//M, d) — explicit (batch, model-slice) layout so
        # the boundary reshard is a local slice, not GSPMD's replication
        # fallback
        B_loc = xb4.shape[0]
        y, aux = body(xb4.reshape(-1, d), rw, wg, wu, wd)
        return y.reshape(B_loc, 1, -1, d), aux

    sm = jax.shard_map(
        body4, mesh=mesh,
        in_specs=(P("data", "model", None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P("data", "model", None, None), P()),
        check_vma=False)
    x4 = x.reshape(B, M, T // M, d)
    y, aux = sm(x4, p["router"]["w"], wg, wu, wd)
    return y.reshape(B, T, d), aux
