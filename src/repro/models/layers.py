"""Transformer building blocks: RMSNorm, RoPE, GQA attention (flash-scan),
MLA attention, SwiGLU MLP, and expert-parallel MoE.

Conventions
-----------
* every ``init_*`` returns ``(params, specs)`` — two parallel pytrees; specs
  use LOGICAL axis names resolved by ``repro.sharding.env`` ("tp" = model,
  "fsdp" = data, "dp" = (pod, data), None = replicated);
* compute runs in bf16, params are stored f32 (cast at use);
* head counts are padded up to the tensor-parallel degree at init time
  (``pad_heads``) — the padding overhead is accounted in the roofline's
  MODEL_FLOPS/HLO_FLOPS ratio (DESIGN.md §5);
* attention over long sequences uses a lax.scan flash pattern (online
  softmax over KV blocks) so no [S, S] score tensor is ever materialised.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import MlaConfig, ModelConfig, MoeConfig
from ..sharding.env import get_env, shard

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


def _init(key, shape, scale=None):
    scale = scale if scale is not None else 0.02
    return (jax.random.normal(key, shape, PARAM_DTYPE) * scale)


def pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_heads(h: int, kv: int, tp: int) -> tuple[int, int]:
    """Pad (q-heads, kv-heads) so q-heads shard over tp and group evenly."""
    h_pad = pad_to(h, tp)
    if kv >= h_pad:
        return h_pad, h_pad
    kv_pad = kv
    while h_pad % kv_pad != 0:
        kv_pad += 1
    return h_pad, kv_pad


# ---------------------------------------------------------------------------
# Norm + RoPE
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_freqs(dh: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., S, dh] (dh even), positions [S] or broadcastable."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [S, dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key: jax.Array, cross: bool = False):
    env = get_env()
    tp = env.tp_size()
    h, kv = pad_heads(cfg.n_heads, cfg.n_kv, tp)
    dh, d = cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "wq": _init(ks[0], (d, h, dh)),
        "wk": _init(ks[1], (d, kv, dh)),
        "wv": _init(ks[2], (d, kv, dh)),
        "wo": _init(ks[3], (h, dh, d), scale=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    s: dict[str, Any] = {
        "wq": ("fsdp", "tp", None),
        "wk": ("fsdp", None, None),   # kv heads replicated across tp
        "wv": ("fsdp", None, None),
        "wo": ("tp", None, "fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), PARAM_DTYPE)
        p["bk"] = jnp.zeros((kv, dh), PARAM_DTYPE)
        p["bv"] = jnp.zeros((kv, dh), PARAM_DTYPE)
        s["bq"], s["bk"], s["bv"] = ("tp", None), (None, None), (None, None)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), PARAM_DTYPE)
        p["k_norm"] = jnp.ones((dh,), PARAM_DTYPE)
        s["q_norm"], s["k_norm"] = (None,), (None,)
    return p, s


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool, q_offset: jax.Array | int = 0,
                    block: int = 1024) -> jax.Array:
    """Online-softmax attention. q [B,H,Sq,dh]; k/v [B,KV,Sk,dh]; returns
    [B,H,Sq,dh]. Never materialises the [Sq,Sk] score matrix — scans KV
    blocks carrying the running (max, sum, acc)."""
    from .perf import get_perf
    if get_perf().flash_custom_vjp and q_offset == 0:
        from .flash_vjp import flash_fa2
        return flash_fa2(q, k, v, causal, block if k.shape[2] % block == 0
                         else k.shape[2])

    b, hq, sq, dh = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]                                # may differ from dh (MLA)
    g = hq // kvh
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, kvh, g, sq, dh)

    n_blk = max(sk // block, 1)
    block = sk // n_blk
    kb = k.astype(jnp.float32).reshape(b, kvh, n_blk, block, dh)
    vb = v.astype(jnp.float32).reshape(b, kvh, n_blk, block, dv)
    kb = jnp.moveaxis(kb, 2, 0)                     # [n, B, KV, blk, dh]
    vb = jnp.moveaxis(vb, 2, 0)

    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)

    from .perf import get_perf
    pv_bf16 = get_perf().pv_bf16
    additive_mask = get_perf().additive_mask

    def step(carry, xs):
        m, l, acc, blk_i = carry
        kblk, vblk = xs
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qf, kblk)     # [B,KV,G,Sq,blk]
        if causal:
            k_pos = blk_i * block + jnp.arange(block)
            if additive_mask:
                # §Perf: [Sq,blk] additive bias broadcast fuses into the dot
                # epilogue; no [B,H,Sq,blk] select tensor is materialised
                bias = jnp.where(q_pos[:, None] >= k_pos[None, :],
                                 0.0, -jnp.inf).astype(s.dtype)
                s = s + bias[None, None, None]
            else:
                mask = q_pos[:, None] >= k_pos[None, :]    # [Sq, blk]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        if pv_bf16:    # §Perf: halve probs HBM traffic; accum stays f32
            pv = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(jnp.bfloat16),
                            vblk.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bkgqc,bkcd->bkgqd", p, vblk)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc, blk_i + 1), None

    m0 = jnp.full((b, kvh, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) cache.

    q [B,H,dh]; k_cache/v_cache [B,S,KV,dh]; length: valid prefix length.
    Softmax reductions over the sharded S axis lower to psums (the
    cross-chip flash-decoding split-K pattern — DESIGN.md §6).
    """
    b, hq, dh = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = hq // kvh
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, kvh, g, dh)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf, kf)        # [B,KV,G,S]
    valid = jnp.arange(s)[None, None, None, :] < length
    logits = jnp.where(valid, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30), vf)
    return out.reshape(b, hq, dh).astype(q.dtype)


def attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
              positions: jax.Array, causal: bool = True,
              cache: tuple[jax.Array, jax.Array] | None = None,
              cache_len: jax.Array | None = None,
              kv_input: jax.Array | None = None,
              use_rope: bool = True):
    """GQA attention, all modes.

    train/prefill: x [B,S,D] -> (out [B,S,D], new_kv)
    decode:        x [B,1,D] + cache -> (out, updated cache slice at cache_len)
    cross-attn:    kv_input [B,S_enc,D] (whisper decoder), cache unused.
    """
    b, sq, d = x.shape
    xc = x.astype(COMPUTE_DTYPE)
    kv_src = (kv_input if kv_input is not None else x).astype(COMPUTE_DTYPE)

    q = jnp.einsum("bsd,dhk->bhsk", xc, p["wq"].astype(COMPUTE_DTYPE))
    k = jnp.einsum("bsd,dhk->bhsk", kv_src, p["wk"].astype(COMPUTE_DTYPE))
    v = jnp.einsum("bsd,dhk->bhsk", kv_src, p["wv"].astype(COMPUTE_DTYPE))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(COMPUTE_DTYPE)[None, :, None, :]
        k = k + p["bk"].astype(COMPUTE_DTYPE)[None, :, None, :]
        v = v + p["bv"].astype(COMPUTE_DTYPE)[None, :, None, :]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if use_rope:
        kv_positions = positions if kv_input is None else jnp.arange(k.shape[2])
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)

    if cache is not None:
        k_cache, v_cache = cache
        # write new k/v at cache_len (sq == 1 decode step)
        k_new = jnp.moveaxis(k, 1, 2)                     # [B,Sq,KV,dh]
        v_new = jnp.moveaxis(v, 1, 2)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, cache_len, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, cache_len, 0, 0))
        out = decode_attention(q[:, :, 0, :], k_cache, v_cache,
                               cache_len + 1)
        out = out[:, :, None, :]                          # [B,H,1,dh]
        new_cache = (k_cache, v_cache)
    else:
        out = flash_attention(q, k, v, causal=causal)
        new_cache = (jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))

    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(COMPUTE_DTYPE))
    return y.astype(x.dtype), new_cache


def attention_fixed_kv(cfg: ModelConfig, p: dict, x: jax.Array,
                       k_cache: jax.Array, v_cache: jax.Array) -> jax.Array:
    """Cross-attention against precomputed K/V (whisper decode): x [B,1,D],
    caches [B,S_enc,KV,dh]. No RoPE, no cache update."""
    xc = x.astype(COMPUTE_DTYPE)
    q = jnp.einsum("bsd,dhk->bhsk", xc, p["wq"].astype(COMPUTE_DTYPE))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(COMPUTE_DTYPE)[None, :, None, :]
    s_enc = k_cache.shape[1]
    out = decode_attention(q[:, :, 0, :], k_cache, v_cache,
                           jnp.int32(s_enc))
    y = jnp.einsum("bhsk,hkd->bsd", out[:, :, None, :],
                   p["wo"].astype(COMPUTE_DTYPE))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key: jax.Array):
    m = cfg.mla
    assert m is not None
    env = get_env()
    tp = env.tp_size()
    h = pad_to(cfg.n_heads, tp)
    d = cfg.d_model
    dn, dr, dv = m.nope_head_dim, m.rope_head_dim, m.v_head_dim
    q_in = m.q_lora or d
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": _init(ks[0], (d, m.kv_lora)),            # compress KV
        "w_kr": _init(ks[1], (d, dr)),                    # decoupled rope key
        "w_uk": _init(ks[2], (m.kv_lora, h, dn)),         # up-proj keys
        "w_uv": _init(ks[3], (m.kv_lora, h, dv)),         # up-proj values
        "w_uq": _init(ks[4], (q_in, h, dn + dr)),         # queries
        "wo": _init(ks[5], (h, dv, d), scale=0.02 / math.sqrt(2 * cfg.n_layers)),
        "kv_norm": jnp.ones((m.kv_lora,), PARAM_DTYPE),
    }
    s = {
        "w_dkv": ("fsdp", None),
        "w_kr": ("fsdp", None),
        "w_uk": (None, "tp", None),
        "w_uv": (None, "tp", None),
        "w_uq": ("fsdp", "tp", None),
        "wo": ("tp", None, "fsdp"),
        "kv_norm": (None,),
    }
    if m.q_lora:
        p["w_dq"] = _init(ks[6], (d, m.q_lora))
        p["q_norm"] = jnp.ones((m.q_lora,), PARAM_DTYPE)
        s["w_dq"] = ("fsdp", None)
        s["q_norm"] = (None,)
    return p, s


def mla_attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
                  positions: jax.Array,
                  cache: tuple[jax.Array, jax.Array] | None = None,
                  cache_len: jax.Array | None = None):
    """Multi-head Latent Attention.

    Cache holds only (c_kv [B,S,kv_lora], k_rope [B,S,dr]) — the compressed
    latent — and decode uses the absorbed form (w_uk folded into the query,
    w_uv folded into the output projection), so per-step decode reads
    O(S·kv_lora) bytes instead of O(S·H·dh).
    """
    m = cfg.mla
    b, sq, d = x.shape
    xc = x.astype(COMPUTE_DTYPE)
    h = p["w_uq"].shape[1]
    dn, dr = m.nope_head_dim, m.rope_head_dim

    if m.q_lora:
        q_in = rms_norm(xc @ p["w_dq"].astype(COMPUTE_DTYPE), p["q_norm"],
                        cfg.rms_eps)
    else:
        q_in = xc
    q = jnp.einsum("bsd,dhk->bhsk", q_in, p["w_uq"].astype(COMPUTE_DTYPE))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = rms_norm(xc @ p["w_dkv"].astype(COMPUTE_DTYPE), p["kv_norm"],
                    cfg.rms_eps)                           # [B,S,kv_lora]
    k_rope = apply_rope(xc @ p["w_kr"].astype(COMPUTE_DTYPE),
                        positions, cfg.rope_theta)         # [B,S,dr]

    if cache is not None:
        ckv_cache, kr_cache = cache
        ckv_cache = jax.lax.dynamic_update_slice(
            ckv_cache, c_kv.astype(ckv_cache.dtype), (0, cache_len, 0))
        kr_cache = jax.lax.dynamic_update_slice(
            kr_cache, k_rope.astype(kr_cache.dtype), (0, cache_len, 0))
        s_len = ckv_cache.shape[1]
        # absorbed decode: fold w_uk into the query -> score in latent space
        q_c = jnp.einsum("bhsk,lhk->bhsl", q_nope.astype(jnp.float32),
                         p["w_uk"].astype(jnp.float32))    # [B,H,1,kv_lora]
        scale = 1.0 / math.sqrt(dn + dr)
        lat = ckv_cache.astype(jnp.float32)                # [B,S,L]
        krc = kr_cache.astype(jnp.float32)                 # [B,S,dr]
        logits = (jnp.einsum("bhsl,btl->bhst", q_c, lat)
                  + jnp.einsum("bhsk,btk->bhst",
                               q_rope.astype(jnp.float32), krc)) * scale
        valid = jnp.arange(s_len)[None, None, None, :] < (cache_len + sq)
        logits = jnp.where(valid, logits, -jnp.inf)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        pr = jnp.exp(logits - mx)
        pr = pr / jnp.maximum(jnp.sum(pr, axis=-1, keepdims=True), 1e-30)
        o_lat = jnp.einsum("bhst,btl->bhsl", pr, lat)      # [B,H,1,L]
        out = jnp.einsum("bhsl,lhv->bhsv", o_lat,
                         p["w_uv"].astype(jnp.float32))    # absorbed w_uv
        new_cache = (ckv_cache, kr_cache)
    else:
        # train/prefill: materialise per-head keys/values, flash-scan
        k_nope = jnp.einsum("bsl,lhk->bhsk", c_kv, p["w_uk"].astype(COMPUTE_DTYPE))
        vfull = jnp.einsum("bsl,lhv->bhsv", c_kv, p["w_uv"].astype(COMPUTE_DTYPE))
        kr = jnp.broadcast_to(k_rope[:, None, :, :], (b, h, sq, dr))
        k = jnp.concatenate([k_nope, kr.astype(k_nope.dtype)], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(qq, k, vfull, causal=True)
        new_cache = (c_kv, k_rope)

    y = jnp.einsum("bhsv,hvd->bsd", out.astype(COMPUTE_DTYPE),
                   p["wo"].astype(COMPUTE_DTYPE))
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key: jax.Array, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_gate": _init(ks[0], (d, f)),
         "w_up": _init(ks[1], (d, f)),
         "w_down": _init(ks[2], (f, d), scale=0.02 / math.sqrt(2 * cfg.n_layers))}
    s = {"w_gate": ("fsdp", "tp"), "w_up": ("fsdp", "tp"),
         "w_down": ("tp", "fsdp")}
    return p, s


def mlp(p: dict, x: jax.Array) -> jax.Array:
    xc = x.astype(COMPUTE_DTYPE)
    g = jax.nn.silu(xc @ p["w_gate"].astype(COMPUTE_DTYPE))
    u = xc @ p["w_up"].astype(COMPUTE_DTYPE)
    return ((g * u) @ p["w_down"].astype(COMPUTE_DTYPE)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (expert-parallel over "tp"; see DESIGN.md §4 for the
# DFEP-balanced placement variant)
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key: jax.Array):
    mo = cfg.moe
    env = get_env()
    tp = env.tp_size()
    e_pad = pad_to(mo.n_experts, tp)
    d = cfg.d_model
    fe = mo.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 6)
    p = {
        "router": _init(ks[0], (d, e_pad), scale=0.006),
        "w_gate": _init(ks[1], (e_pad, d, fe)),
        "w_up": _init(ks[2], (e_pad, d, fe)),
        "w_down": _init(ks[3], (e_pad, fe, d),
                        scale=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    s = {
        "router": (None, None),
        "w_gate": ("tp", "fsdp", None),
        "w_up": ("tp", "fsdp", None),
        "w_down": ("tp", None, "fsdp"),
    }
    if mo.n_shared:
        sh, shs = init_mlp(cfg, ks[4], d_ff=mo.n_shared * fe)
        p["shared"], s["shared"] = sh, shs
    return p, s


def _moe_worker(x, router, w_gate, w_up, w_down, *,
                n_real: int, top_k: int, capacity: int,
                e_lo: jax.Array, tp_axis: str | None, norm_topk: bool):
    """Per-device MoE: local tokens x [T,D] × this shard's experts.

    Tokens are replicated over the tp axis (activations are batch-sharded
    only), so expert-parallelism needs no all-to-all: every shard computes
    its experts' contribution for its tokens and a psum over tp combines.
    """
    t, d = x.shape
    e_pad = router.shape[1]
    e_loc = w_gate.shape[0]
    xc = x.astype(COMPUTE_DTYPE)

    logits = (xc @ router.astype(COMPUTE_DTYPE)).astype(jnp.float32)
    logits = jnp.where(jnp.arange(e_pad)[None, :] < n_real, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)              # [T,k]
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    fe_idx = eidx.reshape(-1)                              # [T*k]
    fg = gates.reshape(-1)
    tok = jnp.arange(t * top_k, dtype=jnp.int32) // top_k
    order = jnp.argsort(fe_idx)
    se, stok, sg = fe_idx[order], tok[order], fg[order]
    starts = jnp.searchsorted(se, jnp.arange(e_pad), side="left")
    pos = jnp.arange(t * top_k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < capacity
    local = (se >= e_lo) & (se < e_lo + e_loc) & keep
    b_e = jnp.where(local, se - e_lo, 0)
    b_p = jnp.where(local, pos, capacity)                  # overflow slot
    buf = jnp.zeros((e_loc, capacity + 1, d), COMPUTE_DTYPE)
    buf = buf.at[b_e, b_p].add(xc[stok] * local[:, None].astype(COMPUTE_DTYPE))
    buf = buf[:, :capacity]

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(COMPUTE_DTYPE)))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(COMPUTE_DTYPE))
    o = jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(COMPUTE_DTYPE))

    o_pad = jnp.concatenate([o, jnp.zeros((e_loc, 1, d), o.dtype)], axis=1)
    contrib = o_pad[b_e, b_p] * (sg * local)[:, None].astype(o.dtype)
    y = jnp.zeros((t, d), jnp.float32).at[stok].add(contrib.astype(jnp.float32))
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)

    # Switch-style load-balance aux loss over the real experts
    me = jnp.mean(probs[:, :n_real], axis=0)
    onehot = jax.nn.one_hot(eidx, e_pad, dtype=jnp.float32)[..., :n_real]
    ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = n_real * jnp.sum(me * ce)
    return y, aux


def moe(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [B,S,D] -> (y [B,S,D], aux_loss scalar)."""
    mo = cfg.moe
    env = get_env()
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    e_pad = p["router"].shape[1]

    if env.active and env.tp is not None:
        tp = env.tp_size()
        dp_ok = t % max(env.dp_size(), 1) == 0 and t >= env.dp_size()
        dp_spec = env.dp if (env.dp and dp_ok) else None
        t_loc = t // env.dp_size() if dp_spec else t
        cap = max(8, int(mo.capacity_factor * t_loc * mo.top_k / mo.n_experts))
        worker = partial(_moe_worker, n_real=mo.n_experts, top_k=mo.top_k,
                         capacity=cap, tp_axis=env.tp, norm_topk=True)

        def wrapped(xt_, router_, wg_, wu_, wd_):
            e_loc = e_pad // tp
            e_lo = jax.lax.axis_index(env.tp) * e_loc
            return worker(xt_, router_, wg_, wu_, wd_, e_lo=e_lo)

        y, aux = jax.shard_map(
            wrapped, mesh=env.mesh,
            in_specs=(P(dp_spec, None), P(None, None),
                      P(env.tp, None, None), P(env.tp, None, None),
                      P(env.tp, None, None)),
            out_specs=(P(dp_spec, None), P()),
            check_vma=False,
        )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        aux = jnp.mean(aux)
    else:
        cap = max(8, int(mo.capacity_factor * t * mo.top_k / mo.n_experts))
        y, aux = _moe_worker(xt, p["router"], p["w_gate"], p["w_up"],
                             p["w_down"], n_real=mo.n_experts, top_k=mo.top_k,
                             capacity=cap, e_lo=jnp.int32(0), tp_axis=None,
                             norm_topk=True)

    y = y.reshape(b, s, d).astype(x.dtype)
    if mo.n_shared:
        y = y + mlp(p["shared"], x)
    return y, aux
