"""BalancedMoE: mixture-of-experts layer whose overflow handling *is* the
paper's dynamic load balancing (core/balance.py).  Experts are the workers,
tokens the tasks, expert capacity the XQueue size, and EP device groups the
NUMA zones.  Returns the paper's counter set as metrics."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import balance
from repro.kernels import ops
from repro.models import layers


def moe_init(key, cfg: ModelConfig):
    m = cfg.moe
    D, F = cfg.d_model, m.d_expert_ff
    ks = jax.random.split(key, 5)

    def experts(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[1] ** -0.5).astype(cfg.pdtype)

    p = {"router": layers._dense_init(ks[0], (D, m.n_experts), jnp.float32),
         "wg": experts(ks[1], (m.n_experts, D, F)),
         "wu": experts(ks[2], (m.n_experts, D, F)),
         "wd": (jax.random.normal(ks[3], (m.n_experts, F, D), jnp.float32)
                * F ** -0.5).astype(cfg.pdtype)}
    if m.n_shared:
        p["shared"] = layers.mlp_init(ks[4], cfg, F * m.n_shared)
    return p


def capacity_for(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    cap = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(8, (cap + 7) // 8 * 8)


def moe_apply(p, x, cfg: ModelConfig, *, ep_groups: int, rng,
              dp_groups: int = 1):
    """x: (B, S, D).  Returns (out, aux) where aux carries the router
    load-balance loss and the paper-style DLB counters.

    `dp_groups` = data-parallel shard count: capacity and dispatch buffers
    are per (shard, expert) — tokens never leave their data shard, only the
    expert dimension is remote (EP all-to-all).  Per-device buffer is then
    (E/ep, C_shard, D) instead of (E/ep, C_global, D)."""
    import math
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    G = math.gcd(dp_groups, B)     # token groups follow the batch sharding
    t = T // G
    xt = x.reshape(T, D)
    cap = capacity_for(cfg, t)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    ep_groups = math.gcd(ep_groups, m.n_experts)   # groups must divide experts
    groups = balance.default_expert_groups(m.n_experts, ep_groups)
    use_sm = (m.shard_routing and layers._AXIS_HINTS["on"]
              and layers._AXIS_HINTS["mesh"] is not None and G > 1)
    if use_sm:
        buf, ve, pos, weight, probs, stats = _route_dispatch_shard_map(
            xt, logits, cfg, cap, groups, rng, G)
        r_expert_for_aux = None
    else:
        token_group = jnp.arange(T, dtype=jnp.int32) // t
        r = balance.route(logits, m.top_k, cap, groups, strategy=m.strategy,
                          p_local=m.p_local, key=rng,
                          token_group=token_group, n_token_groups=G)
        # dispatch into flat (G*E, C, D) virtual-expert buffers
        ve = jnp.where(r.expert >= 0,
                       token_group[:, None] * m.n_experts + r.expert, -1)
        buf = ops.moe_dispatch(xt, ve, r.pos, n_experts=G * m.n_experts,
                               capacity=cap)
        buf = buf.reshape(G, m.n_experts, cap, D)
        pos, weight, probs = r.pos, r.weight, r.probs
        stats = r.stats
        r_expert_for_aux = r.expert
    buf = layers.hint(buf, "dp", "tp", None, None)
    act = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wg"]))
    h = layers.hint(act * jnp.einsum("gecd,edf->gecf", buf, p["wu"]),
                    "dp", "tp", None, None)
    y = layers.hint(jnp.einsum("gecf,efd->gecd", h, p["wd"]),
                    "dp", "tp", None, None)
    if use_sm:
        out = _combine_shard_map(y, ve, pos, weight, cfg, T)
        exp_for_lb = jnp.where(ve >= 0, ve % m.n_experts, -1)
    else:
        out = ops.moe_combine(y.reshape(G * m.n_experts, cap, D), ve, pos,
                              weight, n_tokens=T)
        exp_for_lb = r_expert_for_aux
    out = out.reshape(B, S, D)
    if m.n_shared:
        out = out + layers.mlp_apply(p["shared"], x, cfg)
    aux = {"lb_loss": balance.load_balance_loss(probs, exp_for_lb, m.top_k)}
    aux.update({k: v.astype(jnp.float32) for k, v in stats.items()})
    return out, aux


def _route_dispatch_shard_map(xt, logits, cfg: ModelConfig, cap, groups,
                              rng, G):
    """Beyond-paper optimization (EXPERIMENTS.md #Perf): routing sorts,
    ranking, and the dispatch scatter run *inside shard_map over the data
    axes*, so every shard sorts only its own T/G tokens and the scatter is
    device-local — the jit global-view formulation replicates the (T*k)-sized
    argsorts on every device and lowers the sharded scatter to all-gathers.
    Only the expert dimension leaves the shard afterwards (EP)."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E = m.n_experts
    mesh = layers._AXIS_HINTS["mesh"]
    dp = layers._AXIS_HINTS["dp"]
    dp = dp if isinstance(dp, tuple) else (dp,)
    T, D = xt.shape

    def local_fn(xt_l, logits_l):
        shard = jnp.int32(0)
        for ax in dp:
            shard = shard * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        key = jax.random.fold_in(rng, shard)
        r = balance.route(logits_l, m.top_k, cap, groups,
                          strategy=m.strategy, p_local=m.p_local, key=key)
        buf = ops.moe_dispatch(xt_l, r.expert, r.pos, n_experts=E,
                               capacity=cap)
        ve = jnp.where(r.expert >= 0, shard * E + r.expert, -1)
        stats = {k: jax.lax.psum(v, dp) for k, v in r.stats.items()}
        return (buf[None], ve[None], r.pos[None], r.weight[None],
                r.probs[None], stats)

    specs_in = (P(dp, None), P(dp, None))
    specs_out = (P(dp, None, None, None), P(dp, None, None),
                 P(dp, None, None), P(dp, None, None), P(dp, None, None),
                 {k: P() for k in ("ntasks_static", "ntasks_stolen_local",
                                   "ntasks_stolen_remote", "ntasks_dropped",
                                   "max_load")})
    buf, ve, pos, weight, probs, stats = jax.shard_map(
        local_fn, mesh=mesh, in_specs=specs_in, out_specs=specs_out,
        check_vma=False)(xt, logits)
    # global views: (G,E,C,D) buffers; (T,k) routing tables; (T,E) probs
    k = m.top_k
    return (buf, ve.reshape(T, k), pos.reshape(T, k),
            weight.reshape(T, k), probs.reshape(T, -1), stats)


def _combine_shard_map(y, ve, pos, weight, cfg: ModelConfig, T):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E = m.n_experts
    mesh = layers._AXIS_HINTS["mesh"]
    dp = layers._AXIS_HINTS["dp"]
    dp = dp if isinstance(dp, tuple) else (dp,)
    k = m.top_k

    def local_fn(y_l, ve_l, pos_l, w_l):
        # back to local expert ids (tokens never left their shard)
        e_l = jnp.where(ve_l[0] >= 0, ve_l[0] % E, -1)
        out = ops.moe_combine(y_l[0], e_l, pos_l[0], w_l[0],
                              n_tokens=e_l.shape[0])
        return out[None]

    G = layers._AXIS_HINTS["dp_size"]

    def regroup(a):      # (T, k) -> (G, T/G, k): shard_map splits dim 0
        return a.reshape(G, T // G, k)

    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, None),
                  P(dp, None, None), P(dp, None, None)),
        out_specs=P(dp, None, None), check_vma=False)(
        y, regroup(ve), regroup(pos), regroup(weight))
    return out.reshape(T, -1)
