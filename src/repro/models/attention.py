"""Attention: GQA (full/causal), sliding-window, and MLA (DeepSeek-V2).

Which softmax core a full-sequence GQA forward runs is chosen in
`attn_forward`:

- Where the one placement rule of the Pallas kernels holds
  (`kernels/placement.on_one_tpu`: a TPU process, a step on one device)
  and `kernels/flash_attention.flash_attention_blocks` gives blocks for the
  shapes: the Pallas flash kernel pair with its own backward.
- Everywhere else: a *chunked online-softmax* in XLA ops (the pure-jnp
  flash-attention shape, `chunked_attention`): a Python-level query-chunk
  loop with a `lax.scan` over only the key chunks each query chunk can
  see, so causal and sliding-window masking skip work structurally
  instead of masking a full S x S score tensor.  That is every CPU
  process (the tests, LEO's dry-run and hillclimb), every step sharded
  over more than one device (XLA cannot partition a Mosaic kernel across
  a mesh), and MLA.  It is also the oracle the kernel is validated
  against.

Decode uses a KV cache: full cache for "full" attention, a ring buffer of
`window` entries for SWA, and the compressed (kv_lora + k_rope) cache with
*absorbed* projections for MLA — the O(kv_lora) decode path from the
DeepSeek-V2 paper rather than naive per-step decompression.
"""
from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.flash_attention import flash_attention, flash_attention_blocks
from ..kernels.placement import on_one_tpu
from .flags import FUSED_REGION_MARK, get_flags
from .layers import apply_rope, dense_init, linear, rmsnorm, rope_cos_sin

Params = Dict[str, jnp.ndarray]

_NEG_INF = -1e30


# -- chunked online-softmax attention core -------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      chunk: int = 512,
                      window: Optional[int] = None) -> jnp.ndarray:
    """Causal (optionally sliding-window) attention.

    q: (B, S, H, hd); k: (B, S, Kv, hd); v: (B, S, Kv, vd) with H % Kv == 0
    (vd may differ from hd — MLA uses qk_dim 192, v_dim 128).
    Returns (B, S, H, vd).  Work is triangular: query chunk i only touches
    key chunks in [max(0, i - window_chunks), i].
    """
    b, s, h, hd = q.shape
    vd = v.shape[-1]
    kv_heads = k.shape[2]
    groups = h // kv_heads
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    n_chunks = s // chunk

    # GQA: broadcast KV heads per key-chunk inside the loop (never reshape
    # q's head axis — it may be TP-sharded and a Kv x G split would force a
    # reshard).  The repeated chunk is small and fuses into the dot.
    qc = q.reshape(b, n_chunks, chunk, h, hd)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, kv_heads, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, kv_heads, vd), 1, 0)

    win_chunks = None
    if window is not None:
        win_chunks = max(1, -(-window // chunk))  # ceil

    row_ids = jnp.arange(chunk)
    # "pallas_fused" cost-models the validated Pallas flash kernel
    # (kernels/flash_attention.py): the whole key sweep runs as one kernel
    # with (m, l, acc) resident in VMEM scratch.
    fused = get_flags().attention_impl == "pallas_fused"

    outputs = []
    for i in range(n_chunks):
        lo = 0 if win_chunks is None else max(0, i - win_chunks)
        qi = qc[:, i] * scale  # (B, C, H, hd), input dtype

        def kv_step(carry, inputs):
            m, l, acc = carry
            kj, vj, j = inputs
            if groups > 1:
                kj = jnp.repeat(kj, groups, axis=2)
                vj = jnp.repeat(vj, groups, axis=2)
            scores = jnp.einsum("bchd,bxhd->bhcx", qi, kj,
                                preferred_element_type=jnp.float32)
            q_pos = i * chunk + row_ids[:, None]
            k_pos = j * chunk + row_ids[None, :]
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            scores = jnp.where(mask, scores, _NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhcx,bxhd->bhcd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, vd), jnp.float32)
        js = jnp.arange(lo, i + 1)
        with (jax.named_scope(FUSED_REGION_MARK) if fused
              else contextlib.nullcontext()):
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (kc[lo:i + 1], vc[lo:i + 1], js))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
        outputs.append(jnp.moveaxis(out, 1, 2))  # (B, C, H, vd)
    return jnp.concatenate(outputs, axis=1).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, length_mask: jnp.ndarray
                     ) -> jnp.ndarray:
    """One-token attention against a cache.

    q: (B, H, hd); caches (B, S, Kv, hd); length_mask (B, S) bool.
    """
    b, h, hd = q.shape
    kv_heads = k_cache.shape[2]
    groups = h // kv_heads
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, kv_heads, groups, hd) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(length_mask[:, None, None, :], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, hd).astype(q.dtype)


# -- GQA module -----------------------------------------------------------------

def init_attn(key, cfg: ArchConfig, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
         "wk": dense_init(ks[1], (d, kv * hd), dtype=dtype),
         "wv": dense_init(ks[2], (d, kv * hd), dtype=dtype),
         "wo": dense_init(ks[3], (h * hd, d), dtype=dtype)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _attend_in_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      cfg: ArchConfig) -> bool:
    """Whether this forward attends in the Pallas kernel: where
    `on_one_tpu(q)` holds and the kernel takes the shapes."""
    _, s, h, hd = q.shape
    window = cfg.window if cfg.attention == "swa" else None
    return (on_one_tpu(q) and v.shape[-1] == hd
            and flash_attention_blocks(s, hd, h // k.shape[2], window)
            is not None)


def attn_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                 positions: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Full-sequence causal attention (training / prefill)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = linear(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(b, s, kv, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(b, s, kv, hd)
    cos, sin = rope_cos_sin(positions, cfg.rotary_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin, cfg.rope_interleave)
    k = apply_rope(k, cos, sin, cfg.rope_interleave)
    window = cfg.window if cfg.attention == "swa" else None
    if _attend_in_kernel(q, k, v, cfg):
        out = flash_attention(q, k, v, window=window)
    else:
        out = chunked_attention(q, k, v, chunk=chunk, window=window)
    return linear(out.reshape(b, s, h * hd), p["wo"])


def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype
                    ) -> Params:
    kv, hd = cfg.n_kv_heads, cfg.head_dim_
    s = min(max_len, cfg.window) if cfg.attention == "swa" else max_len
    return {"k": jnp.zeros((batch, s, kv, hd), dtype),
            "v": jnp.zeros((batch, s, kv, hd), dtype)}


def attn_decode(p: Params, x: jnp.ndarray, cache: Params, pos: jnp.ndarray,
                cfg: ArchConfig,
                layer_idx: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """x (B, d); pos scalar int32. Returns (y (B, d), new cache).

    When `layer_idx` is given, `cache` holds *layer-stacked* buffers
    (L, B, S, Kv, hd) and the new token is written with a single-token
    dynamic-update-slice directly into the stack — the paged-cache pattern:
    per step the cache costs one token of writes and one layer of reads,
    never a per-layer copy through scan stacking.
    """
    b, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = linear(x, p["wq"], p.get("bq")).reshape(b, h, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(b, kv, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(b, kv, hd)
    cos, sin = rope_cos_sin(pos[None], cfg.rotary_dim, cfg.rope_theta)
    q = apply_rope(q[:, None], cos, sin, cfg.rope_interleave)[:, 0]
    k = apply_rope(k[:, None], cos, sin, cfg.rope_interleave)[:, 0]

    stacked = layer_idx is not None
    cache_len = cache["k"].shape[2 if stacked else 1]
    slot = pos % cache_len if cfg.attention == "swa" else pos
    if stacked:
        upd_k = k[None, :, None].astype(cache["k"].dtype)  # (1,B,1,kv,hd)
        upd_v = v[None, :, None].astype(cache["v"].dtype)
        k_stack = jax.lax.dynamic_update_slice(
            cache["k"], upd_k, (layer_idx, 0, slot, 0, 0))
        v_stack = jax.lax.dynamic_update_slice(
            cache["v"], upd_v, (layer_idx, 0, slot, 0, 0))
        k_cache = jax.lax.dynamic_index_in_dim(k_stack, layer_idx, 0,
                                               keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_stack, layer_idx, 0,
                                               keepdims=False)
        new_cache = {"k": k_stack, "v": v_stack}
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k[:, None].astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v[:, None].astype(cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}

    idx = jnp.arange(cache_len)
    if cfg.attention == "swa":
        valid = (idx[None, :] <= slot) | \
            (jnp.full((1, cache_len), pos >= cache_len))
    else:
        valid = idx[None, :] <= pos
    out = decode_attention(q, k_cache, v_cache, valid)
    y = linear(out.reshape(b, h * hd), p["wo"])
    return y, new_cache


# -- MLA (DeepSeek-V2 multi-head latent attention) -------------------------------

def init_mla(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p: Params = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], (d, cfg.q_lora_rank), dtype=dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(ks[1], (cfg.q_lora_rank, h * (nope + rope_d)),
                               dtype=dtype)
    else:
        p["wq"] = dense_init(ks[1], (d, h * (nope + rope_d)), dtype=dtype)
    p["wkv_a"] = dense_init(ks[2], (d, cfg.kv_lora_rank + rope_d),
                            dtype=dtype)
    p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dtype)
    p["wkv_b"] = dense_init(ks[3], (cfg.kv_lora_rank, h * (nope + vd)),
                            dtype=dtype)
    p["wo"] = dense_init(ks[4], (h * vd, d), dtype=dtype)
    return p


def _mla_q(p: Params, x, cfg: ArchConfig, positions):
    b = x.shape[0]
    s = x.shape[1] if x.ndim == 3 else 1
    h = cfg.n_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    xq = x if x.ndim == 3 else x[:, None]
    if cfg.q_lora_rank:
        q = linear(rmsnorm(linear(xq, p["wq_a"]), p["q_norm"], cfg.norm_eps),
                   p["wq_b"])
    else:
        q = linear(xq, p["wq"])
    q = q.reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                positions: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    kv = linear(x, p["wkv_a"])
    kv_c = rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    k_rope = apply_rope(kv[..., None, cfg.kv_lora_rank:], cos, sin)
    kv_up = linear(kv_c, p["wkv_b"]).reshape(b, s, h, nope + vd)
    k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (b, s, h, rope_d))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = chunked_attention(q, k, v, chunk=chunk)
    return linear(out.reshape(b, s, h * vd), p["wo"])


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype
                   ) -> Params:
    return {"kv_c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim),
                                dtype)}


def mla_decode(p: Params, x: jnp.ndarray, cache: Params, pos: jnp.ndarray,
               cfg: ArchConfig,
               layer_idx: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Params]:
    """Absorbed-projection MLA decode: scores in kv_lora space, O(r) per
    cached token instead of per-head decompression.  With `layer_idx` the
    compressed cache is layer-stacked (L, B, S, r) and updated with a
    single-token write (see `attn_decode`)."""
    b, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(p, x, cfg, pos[None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]        # (B, H, *)

    kv = linear(x, p["wkv_a"])
    kv_c = rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(pos[None], rope_d, cfg.rope_theta)
    k_rope = apply_rope(kv[..., None, r:][:, None], cos, sin)[:, 0, 0]

    if layer_idx is not None:
        kv_stack = jax.lax.dynamic_update_slice(
            cache["kv_c"], kv_c[None, :, None].astype(cache["kv_c"].dtype),
            (layer_idx, 0, pos, 0))
        kr_stack = jax.lax.dynamic_update_slice(
            cache["k_rope"],
            k_rope[None, :, None].astype(cache["k_rope"].dtype),
            (layer_idx, 0, pos, 0))
        kv_cache = jax.lax.dynamic_index_in_dim(kv_stack, layer_idx, 0,
                                                keepdims=False)
        kr_cache = jax.lax.dynamic_index_in_dim(kr_stack, layer_idx, 0,
                                                keepdims=False)
        new_cache = {"kv_c": kv_stack, "k_rope": kr_stack}
        return _mla_decode_core(p, x, cfg, q_nope, q_rope, kv_cache,
                                kr_cache, pos, new_cache)
    kv_cache = jax.lax.dynamic_update_slice(
        cache["kv_c"], kv_c[:, None].astype(cache["kv_c"].dtype),
        (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, None].astype(cache["k_rope"].dtype),
        (0, pos, 0))

    # Absorb W_uk into q: q_c (B, H, r)
    return _mla_decode_core(p, x, cfg, q_nope, q_rope, kv_cache, kr_cache,
                            pos, {"kv_c": kv_cache, "k_rope": kr_cache})


def _mla_decode_core(p: Params, x, cfg: ArchConfig, q_nope, q_rope,
                     kv_cache, kr_cache, pos, new_cache
                     ) -> Tuple[jnp.ndarray, Params]:
    b = x.shape[0]
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    r = cfg.kv_lora_rank
    w_uk = p["wkv_b"][:, : h * nope].reshape(r, h, nope)
    q_c = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk,
                     preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(nope + rope_d)
    q_c = q_c.astype(kv_cache.dtype)
    scores = (jnp.einsum("bhr,bsr->bhs", q_c, kv_cache,
                         preferred_element_type=jnp.float32) +
              jnp.einsum("bhd,bsd->bhs", q_rope.astype(kr_cache.dtype),
                         kr_cache,
                         preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(kv_cache.shape[1])[None, :] <= pos
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pattn.astype(kv_cache.dtype), kv_cache,
                     preferred_element_type=jnp.float32)
    # Absorb W_uv on the way out: (B, H, r) x (r, H, vd) -> (B, H, vd)
    w_uv = p["wkv_b"][:, h * nope:].reshape(r, h, vd)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    y = linear(out.reshape(b, h * vd).astype(x.dtype), p["wo"])
    return y, new_cache
