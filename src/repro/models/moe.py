"""Mixture-of-Experts: top-k routing, capacity, expert parallelism.

Two execution paths:

  * `_moe_local`     -- single-program dispatch (sort + scatter); used with no
                        active mesh (CPU tests, small runs).  Also the oracle
                        for the sharded path.
  * `_moe_sharded`   -- GShard-style explicit-collective dispatch inside
                        shard_map: tokens are scattered into per-(source,
                        expert) capacity slices locally, exchanged with ONE
                        all-to-all over the model axis (experts sharded, 8
                        per shard at E=128, tp=16), grouped-matmul'ed, and
                        returned with the reverse all-to-all.  Expert weights
                        are ZeRO-sharded over (pod, data) and all-gathered
                        per layer inside the block.

    Rationale (EXPERIMENTS.md §Perf): routing through plain jnp ops under
    GSPMD turned the dispatch into replicated gathers -- the dry-run showed a
    4,670 s collective term for qwen3-moe train_4k.  The explicit a2a
    schedule is the paper-independent baseline any MoE system uses.

Dispatch is sort-based rather than the one-hot einsum (T*E*C*D MACs would
dwarf useful compute at E=128 and wreck the MODEL_FLOPS/HLO_FLOPs ratio).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import with_logical_constraint as wlc
from repro.sharding.specs import current_mesh
from .common import dense_init


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int  # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert_ff: int = 0  # 0 = none
    bf16_gather: bool = False  # §Perf: bf16 expert-weight ZeRO gathers


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ks[0], (D, E), dtype=jnp.float32),
        "e_gate": dense_init(ks[1], (E, D, F), in_axis=1, dtype=dtype),
        "e_up": dense_init(ks[2], (E, D, F), in_axis=1, dtype=dtype),
        "e_down": dense_init(ks[3], (E, F, D), in_axis=1, dtype=dtype),
    }
    if cfg.shared_expert_ff:
        from .ffn import init_mlp

        p["shared"] = init_mlp(ks[4], D, cfg.shared_expert_ff, dtype=dtype)
    return p


def _route(xt, router, cfg: MoEConfig):
    """xt: (T, D) -> gates (T, K), expert ids (T, K), aux-loss pieces."""
    logits = xt.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(eidx[:, 0], cfg.n_experts, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return gate_vals, eidx, frac_tokens, frac_probs


def _fill_slots(eidx, gates, cap: int, E: int):
    """Sort assignments by expert; rank-within-expert capacity dropping.
    Returns (slot_e, slot_r, src_token, gate) for T*K assignments."""
    K = eidx.shape[1]
    T = eidx.shape[0]
    flat_e = eidx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * K, dtype=jnp.int32) - starts[se]
    keep = rank < cap
    slot_e = jnp.where(keep, se, E - 1)
    slot_r = jnp.where(keep, rank, cap - 1)
    sg = jnp.where(keep, sg, 0.0)
    return slot_e, slot_r, st, sg


def _expert_mlp(buf, wg, wu, wd):
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


# ---------------------------------------------------------------------------
# local (single-program) path
# ---------------------------------------------------------------------------


def _moe_local(p, x, cfg: MoEConfig):
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    cap = int(max(K, T * K * cfg.capacity_factor / E))
    xt = x.reshape(T, D)
    gates, eidx, frac_t, frac_p = _route(xt, p["router"], cfg)
    aux = E * jnp.sum(frac_t * frac_p)
    slot_e, slot_r, st, sg = _fill_slots(eidx, gates, cap, E)
    keep = sg > 0.0
    buf = jnp.zeros((E, cap, D), x.dtype)
    buf = buf.at[slot_e, slot_r].add(jnp.where(keep[:, None], xt[st], 0.0))
    eo = _expert_mlp(buf, p["e_gate"], p["e_up"], p["e_down"])
    contrib = eo[slot_e, slot_r] * sg[:, None].astype(eo.dtype)
    out = jnp.zeros((T, D), eo.dtype).at[st].add(contrib)
    return out.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# sharded (shard_map, explicit all-to-all) path
# ---------------------------------------------------------------------------


def _moe_sharded(p, x, cfg: MoEConfig, mesh, bf16_gather: bool = False):
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    names = mesh.axis_names
    bd = tuple(a for a in ("pod", "data") if a in names)
    ep = "model"
    n_ep = mesh.shape[ep]
    assert E % n_ep == 0, f"E={E} not divisible by model axis {n_ep}"
    seq_shardable = S % n_ep == 0 and S > 1
    x_spec = P(bd, ep if seq_shardable else None, None)

    T_l = (B // math.prod(mesh.shape[a] for a in bd)) * (
        S // (n_ep if seq_shardable else 1)
    )
    cap_se = int(max(1, math.ceil(T_l * K * cfg.capacity_factor / E)))
    E_l = E // n_ep

    def block(x_l, router_l, wg_l, wu_l, wd_l):
        b_l, s_l, _ = x_l.shape
        xt = x_l.reshape(b_l * s_l, D)
        router = jax.lax.all_gather(router_l, bd, axis=0, tiled=True)
        gates, eidx, frac_t, frac_p = _route(xt, router, cfg)
        # average the *fractions* across shards first (matches the global
        # single-program aux loss), then combine
        frac_t = jax.lax.pmean(jax.lax.pmean(frac_t, ep), bd)
        frac_p = jax.lax.pmean(jax.lax.pmean(frac_p, ep), bd)
        aux = E * jnp.sum(frac_t * frac_p)

        slot_e, slot_r, st, sg = _fill_slots(eidx, gates, cap_se, E)
        keep = sg > 0.0
        buf = jnp.zeros((E, cap_se, D), x_l.dtype)
        buf = buf.at[slot_e, slot_r].add(jnp.where(keep[:, None], xt[st], 0.0))

        # ONE all-to-all over the expert-parallel axis: every shard keeps its
        # E_l experts and receives all sources' capacity slices for them.
        recv = jax.lax.all_to_all(buf, ep, split_axis=0, concat_axis=1, tiled=True)
        # recv: (E_l, n_ep * cap_se, D)

        # ZeRO: gather the data-sharded dim of the local expert weights.
        # §Perf (qwen3-moe it.1): optionally cast to bf16 BEFORE the gather
        # (the matmul runs in bf16 anyway) -- halves the per-layer gather
        # bytes vs gathering fp32 masters.
        if bf16_gather:
            wg_l = wg_l.astype(jnp.bfloat16)
            wu_l = wu_l.astype(jnp.bfloat16)
            wd_l = wd_l.astype(jnp.bfloat16)
        wg = jax.lax.all_gather(wg_l, bd, axis=1, tiled=True)  # (E_l, D, F)
        wu = jax.lax.all_gather(wu_l, bd, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd_l, bd, axis=2, tiled=True)  # (E_l, F, D)
        eo = _expert_mlp(recv.astype(wg.dtype), wg, wu, wd)

        back = jax.lax.all_to_all(
            eo.astype(x_l.dtype), ep, split_axis=1, concat_axis=0, tiled=True
        )  # (E, cap_se, D)
        contrib = back[slot_e, slot_r] * sg[:, None].astype(back.dtype)
        out = jnp.zeros((b_l * s_l, D), back.dtype).at[st].add(contrib)
        return out.reshape(b_l, s_l, D).astype(x_l.dtype), aux

    fn = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(bd, None),  # router (D, E): ZeRO over bd
            P(ep, bd, None),  # e_gate (E, D, F)
            P(ep, bd, None),  # e_up
            P(ep, None, bd),  # e_down (E, F, D)
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["e_gate"], p["e_up"], p["e_down"])


def moe_block(p, x, cfg: MoEConfig):
    """x: (B, S, D) -> (out, aux_loss)."""
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        out, aux = _moe_sharded(p, x, cfg, mesh, bf16_gather=cfg.bf16_gather)
    else:
        out, aux = _moe_local(p, x, cfg)
    if "shared" in p:
        from .ffn import mlp_block

        out = out + mlp_block(p["shared"], x)
    return wlc(out, "batch", "seq", None), aux
