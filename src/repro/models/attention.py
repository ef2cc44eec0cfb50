"""Attention: GQA with optional qk-norm, QKV biases, sliding/local windows,
RoPE, and a unified KV cache (linear or rolling ring buffer for SWA).

Shapes: H query heads grouped over M kv heads (G = H // M). Attention math
is written grouped — (B, S, M, G, Dh) — so kv-head sharding composes with
GQA without materializing repeated K/V.

Cache contract (decode): ``cache`` is a dict with k/v of shape
(B, M, T, Dh) where T = allocated slots (full length, or the window for
SWA archs). Slot for absolute position p is ``p % T`` (identical for the
linear case since p < T). Keys are stored *post-RoPE at absolute
positions*, so relative attention holds in the ring buffer. Slot validity
for query position `pos`: slot i holds absolute position
``pos - ((pos - i) mod T)``; valid iff that is >= 0 (and automatically
within the window by construction).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models import unroll as unroll_lib

NEG_INF = -1e30


def qkv_project(x, p, cfg, rules, positions):
    """x: (B, S, D) -> q (B,S,M,G,Dh), k,v (B,S,M,Dh), roped."""
    H, M, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // M
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dmk->bsmk", x, p["wk"])
    v = jnp.einsum("bsd,dmk->bsmk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    B, S = x.shape[:2]
    q = q.reshape(B, S, M, G, Dh)
    return q, k, v


def attend(q, k, v, mask, cfg, rules=None):
    """q: (B,Sq,M,G,Dh); k,v: (B,Sk,M,Dh); mask broadcastable to
    (B,M,G,Sq,Sk). Returns (B,Sq,H,Dh)."""
    scale = cfg.resolved_head_dim**-0.5
    logits = jnp.einsum("bsmgk,btmk->bmgst", q, k) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bmgst,btmk->bsmgk", probs, v)
    B, Sq = out.shape[0], out.shape[1]
    return out.reshape(B, Sq, cfg.num_heads, cfg.resolved_head_dim)


def causal_window_mask(sq: int, sk_offset: int, sk: int, window: Optional[int]):
    """(Sq, Sk) mask; query i is at absolute position sk_offset + i."""
    qpos = sk_offset + jnp.arange(sq, dtype=jnp.int32)[:, None]
    kpos = jnp.arange(sk, dtype=jnp.int32)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m


def attend_chunked(q, k, v, cfg, *, causal=True, window=None, chunk=1024,
                   unroll=False):
    """Online-softmax attention over KV chunks (flash-attention algorithm
    in pure XLA — the jnp oracle for kernels/flash_attention).

    Never materializes (Sq, Sk) — peak intermediate is (Sq, chunk). For
    causal masks, chunks strictly above the diagonal contribute nothing but
    are still computed (static shapes); the Pallas kernel skips them.

    q: (B,Sq,M,G,Dh); k,v: (B,Sk,M,Dh). Returns (B,Sq,H,Dh).
    """
    B, Sq, M, G, Dh = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    assert Sk % chunk == 0, (Sk, chunk)
    nch = Sk // chunk
    scale = cfg.resolved_head_dim**-0.5
    q = q * scale

    kc = k.reshape(B, nch, chunk, M, Dh)
    vc = v.reshape(B, nch, chunk, M, Dh)
    qpos = jnp.arange(Sq, dtype=jnp.int32)[:, None]

    def body(carry, xs):
        m, l, acc = carry
        j, kj, vj = xs
        logits = jnp.einsum("bsmgk,btmk->bmgst", q, kj).astype(jnp.float32)
        kpos = j * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask = kpos <= qpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bmgst,btmk->bsmgk", p.astype(q.dtype), vj)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None].astype(acc.dtype) + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, M, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, M, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, M, G, Dh), q.dtype)
    xs = (jnp.arange(nch, dtype=jnp.int32), kc.transpose(1, 0, 2, 3, 4),
          vc.transpose(1, 0, 2, 3, 4))
    if unroll or unroll_lib.enabled():
        carry = (m0, l0, acc0)
        for j in range(nch):
            carry, _ = body(carry, (jnp.asarray(j), kc[:, j], vc[:, j]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), xs)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None].astype(acc.dtype)
    return out.reshape(B, Sq, cfg.num_heads, cfg.resolved_head_dim)


def flash_sharded(q, k, v, cfg, rules, *, causal=True, window=None):
    """Pallas flash-attention under a full shard_map: the (B, M, G) planes
    shard over the data axes, the model axis is replicated (attention at
    these shapes is data-parallel). HBM traffic = Q+K+V+O (the kernel's
    VMEM contract). Forward-only — used for prefill/decode, not train.

    Falls back to the chunked XLA path when there is no mesh or the plane
    count doesn't divide the data axes."""
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd

    B, Sq, M, G, Dh = q.shape
    Sk = k.shape[1]
    blk = max(min(512, Sq, Sk), 128)
    if rules is None or not hasattr(rules, "mesh"):
        return attend_chunked(q, k, v, cfg, causal=causal, window=window,
                              chunk=cfg.attn_chunk)
    mesh = rules.mesh
    manual = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import numpy as _np

    dsize = int(_np.prod([mesh.shape[a] for a in manual])) if manual else 1
    if not manual or (B * M * G) % dsize or Sq % blk or Sk % blk:
        return attend_chunked(q, k, v, cfg, causal=causal, window=window,
                              chunk=cfg.attn_chunk)
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * M * G, Sq, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * M * G, Sk, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * M * G, Sk, Dh)
    from jax.sharding import PartitionSpec as P

    spec = P(manual)

    def inner(ql, kl, vl):
        return flash_attention_bhsd(
            ql, kl, vl, causal=causal, window=window, blk_q=blk, blk_k=blk,
            interpret=True,
        )

    out = jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(qf, kf, vf)
    return out.reshape(B, M, G, Sq, Dh).transpose(0, 3, 1, 2, 4).reshape(
        B, Sq, M * G, Dh
    )


def self_attention(x, p, cfg, rules, *, window=None, causal=True, pos_offset=0,
                   unroll=False):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    B, S, _ = x.shape
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = qkv_project(x, p, cfg, rules, positions)
    if rules is not None:
        q = rules.constraint(q, "batch", "q_seq", "kv_heads", None, "head_dim")
        k = rules.constraint(k, "batch", "seq", "kv_heads", "head_dim")
        v = rules.constraint(v, "batch", "seq", "kv_heads", "head_dim")
    if cfg.attn_impl == "flash":
        out = flash_sharded(q, k, v, cfg, rules, causal=causal, window=window)
    elif cfg.attn_impl == "chunked":
        out = attend_chunked(
            q, k, v, cfg, causal=causal, window=window,
            chunk=cfg.attn_chunk, unroll=unroll,
        )
    else:
        if causal:
            mask = causal_window_mask(S, 0, S, window)[None, None, None]
        else:
            mask = jnp.ones((1, 1, 1, S, S), bool)
        out = attend(q, k, v, mask, cfg, rules)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, (k, v)


def init_cache_entry(cfg, batch: int, alloc: int, dtype=jnp.bfloat16):
    M, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, M, alloc, Dh), dtype),
        "v": jnp.zeros((batch, M, alloc, Dh), dtype),
    }


def cache_entry_struct(cfg, batch: int, alloc: int, dtype=jnp.bfloat16):
    M, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct((batch, M, alloc, Dh), dtype)
    return {"k": s, "v": s}


def cache_axes():
    return ("batch", "kv_heads", "cache_seq", "head_dim")


def decode_attention(x, p, cache, pos, cfg, rules, *, window=None):
    """Single-token decode. x: (B, 1, D); pos: scalar absolute position.
    Returns (out (B,1,D), updated cache)."""
    B = x.shape[0]
    positions = jnp.full((1, 1), pos, jnp.int32)
    q, k_new, v_new = qkv_project(x, p, cfg, rules, positions)
    T = cache["k"].shape[2]
    slot = (pos % T).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype).transpose(0, 2, 1, 3), slot, 2
    )
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype).transpose(0, 2, 1, 3), slot, 2
    )
    # Slot validity (see module docstring).
    i = jnp.arange(T, dtype=jnp.int32)
    slot_pos = pos - ((pos - i) % T)
    valid = slot_pos >= 0
    if window is not None:
        valid = valid & (slot_pos > pos - window)
    mask = valid[None, None, None, None, :]  # (1,1,1,1,T)
    kk = k.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, T, M, Dh) view
    vv = v.transpose(0, 2, 1, 3).astype(q.dtype)
    out = attend(q, kk, vv, mask, cfg, rules)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, {"k": k, "v": v}
