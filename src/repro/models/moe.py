"""Mixture-of-Experts FFN with capacity-bucketed scatter dispatch.

This is the paper's visit-exchange pattern applied to experts (DESIGN.md
§4): tokens are routed to a fixed-capacity per-expert bucket (static
shapes), processed as dense per-expert matmuls, and combined back weighted
by router gates. The position-within-expert prefix-count plays the role of
the visit slot assignment in core/exchange.py, and dropped tokens (over
capacity) are the analog of bucket overflow — counted and reported.

Sharding (baseline): experts use TP-within-expert — w_* shard the `mlp`
dim over 'model', so the collective profile matches the dense FFN (one
all-reduce after the down-projection) and any expert count works on any
mesh. An expert-parallel variant (experts sharded over 'model' with an
all_to_all dispatch) is the §Perf hillclimb for moonshot's 64 experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def capacity(cfg, num_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.experts_per_token * num_tokens
            / max(cfg.num_experts, 1))
    return max((c + 7) // 8 * 8, 8)


def moe_ffn(x, p, cfg, rules=None):
    """x: (B, S, D) or (T, D). Returns same shape + aux dict."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E, K = cfg.num_experts, cfg.experts_per_token
    C = capacity(cfg, T)

    router_logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    gate_v, gate_i = jax.lax.top_k(router_logits, K)  # (T, K)
    gates = jax.nn.softmax(gate_v, axis=-1).astype(x.dtype)

    flat_e = gate_i.reshape(-1)  # (T*K,) token-major
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    # Position-within-expert via log-depth associative scan. A plain
    # jnp.cumsum lowers to reduce-window whose *counted* cost is O(n^2)
    # (and is serial on long axes); associative_scan is O(n log n) work,
    # log depth — measured 40x on the moonshot train cell (§Perf).
    cum = jax.lax.associative_scan(jnp.add, onehot, axis=0)
    pos_in_e = jnp.take_along_axis(cum - 1, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < C
    pos_c = jnp.minimum(pos_in_e, C - 1)

    # Over-capacity tokens are zeroed and land on slot (e, C-1); the
    # zeroed payload makes collisions harmless (no sentinel row — keeps
    # the buffer 2-D scatter GSPMD-friendly).
    x_rep = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E, C, D), x.dtype)
    if rules is not None:
        buf = rules.constraint(buf, "expert", "expert_cap", "embed")
    h = buf.at[flat_e, pos_c].add(x_rep)
    if rules is not None:
        h = rules.constraint(h, "expert", "expert_cap", "embed")

    g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    act = jax.nn.silu(g) * u
    if rules is not None:
        act = rules.constraint(act, "expert", "expert_cap", "mlp")
    y = jnp.einsum("ecf,efd->ecd", act, p["w_down"])  # (E, C, D)

    out_tok = y[flat_e, pos_c] * (gates.reshape(-1)[:, None]
                                  * keep[:, None].astype(y.dtype))
    out = out_tok.reshape(T, K, D).sum(axis=1)

    aux = {
        "dropped_fraction": 1.0 - keep.mean(),
        "router_z": jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2),
        # load-balance loss (Switch-style): E * sum_e f_e * p_e
        "load_balance": _load_balance_loss(router_logits, gate_i, E),
    }
    return out.reshape(orig_shape), aux


def moe_ffn_dispatch(x, p, cfg, rules=None):
    """MoE with the dispatch strategy selected by cfg.moe_dispatch.

    'pjit': the global scatter above — GSPMD decides the collectives
    (baseline; measured collective-bound on the 64-expert moonshot cell).
    'shard_map': the paper's pattern done properly — dispatch is LOCAL to
    each data shard (exactly like the per-worker visit buckets in
    core/exchange.py), expert weights stay sharded over 'model'
    (TP-within-expert) under GSPMD auto mode. The only inter-chip traffic
    is the model-axis all-reduce of the down-projection — the same
    collective profile as a dense FFN.
    """
    if rules is None or cfg.moe_dispatch != "shard_map":
        return moe_ffn(x, p, cfg, rules)
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    manual = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not manual:
        return moe_ffn(x, p, cfg, rules)
    bspec = P(manual)

    def inner(xl, pl):
        out, aux = moe_ffn(xl, pl, cfg, None)
        aux = {k: _jax.lax.pmean(v, manual) for k, v in aux.items()}
        return out, aux

    return _jax.shard_map(
        inner, mesh=mesh, in_specs=(bspec, P()), out_specs=(bspec, P()),
        axis_names=set(manual), check_vma=False,
    )(x, p)


def _load_balance_loss(router_logits, gate_i, E):
    probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_i[:, 0], E, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)
