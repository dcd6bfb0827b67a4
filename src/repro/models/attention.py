"""Attention: GQA/MQA, causal / sliding-window / bidirectional, cross-attn,
and a sequence-parallel decode step.

Three execution paths:
  * ``chunked_attention`` — pure-jnp blockwise online-softmax (the oracle and
    the CPU/dry-run path; memory O(block²) so 32k+ prefill lowers safely).
  * ``repro.kernels.ops.flash_attention`` — the Pallas TPU kernel (selected
    with ``use_flash=True`` on TPU runtimes).
  * ``decode_step`` — one-token decode against a seq-sharded KV cache.  Under
    a mesh this runs as a ``shard_map`` flash-decode: each model-axis shard
    scores its local KV slice and the partial softmaxes are merged with a
    log-sum-exp ``psum`` — KV never leaves its shard (this is the memory-
    system analogue of AMOEBA's fused coalescing unit: one logical access
    serves the whole fused group).

KV caches are ring buffers: slot ``i`` holds absolute position
``p_i = pos - ((pos - i) mod W)`` (valid iff ``p_i >= 0``), which degenerates
to the identity layout when ``W >= seq``.  RoPE is applied at write time so
cached keys never need re-rotation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.parallel import shardctx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    q_dim, kv_dim = cfg.num_heads * hd, cfg.num_kv_heads * hd
    std = 1.0 / math.sqrt(d)
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    params = {
        "wq": layers.truncated_normal(ks[0], (d, q_dim), std, dtype),
        "wk": layers.truncated_normal(ks[1], (d, kv_dim), std, dtype),
        "wv": layers.truncated_normal(ks[2], (d, kv_dim), std, dtype),
        "wo": layers.truncated_normal(ks[3], (q_dim, d), 1.0 / math.sqrt(q_dim), dtype),
    }
    pspecs = {
        "wq": P("data", "model"),
        "wk": P("data", None) if cfg.num_kv_heads % 4 else P("data", "model"),
        "wv": P("data", None) if cfg.num_kv_heads % 4 else P("data", "model"),
        "wo": P("model", "data"),
    }
    # kv projections are sharded over "model" only when the kv-head count is
    # mesh-divisible; MQA/GQA-with-few-heads replicates them (cheap).
    if cfg.qk_norm and not cross:
        params["q_norm"] = jnp.ones((hd,), dtype)
        params["k_norm"] = jnp.ones((hd,), dtype)
        pspecs["q_norm"] = P(None)
        pspecs["k_norm"] = P(None)
    return params, pspecs


def _project_qkv(params, x, cfg: ModelConfig, positions, kv_source=None,
                 apply_positions=True, barrier=False):
    """Returns q (B,S,H,hd), k/v (B,Skv,KV,hd) with norm+rope applied.

    ``barrier`` ends the q/k projections as plain matmuls before the
    per-head reshape and norm (the one-token decode: fused with them, XLA
    on TPU copies each layer's wq and wk into a transposed layout on
    every call).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    src = x if kv_source is None else kv_source
    q = x @ params["wq"]
    k = src @ params["wk"]
    v = (src @ params["wv"]).reshape(B, src.shape[1], cfg.num_kv_heads, hd)
    if barrier and cfg.qk_norm and "q_norm" in params:
        q, k = jax.lax.optimization_barrier((q, k))
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, src.shape[1], cfg.num_kv_heads, hd)
    if cfg.qk_norm and "q_norm" in params:
        q = layers.rmsnorm_headwise(params["q_norm"], q, cfg.norm_eps)
        k = layers.rmsnorm_headwise(params["k_norm"], k, cfg.norm_eps)
    if apply_positions and positions is not None:
        if cfg.mrope:
            q = layers.apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = layers.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise online-softmax attention (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, window: Optional[int] = None,
                      q_block: int = 512, kv_block: int = 512) -> jnp.ndarray:
    """q: (B,S,H,hd); k, v: (B,Skv,KV,hd) -> (B,S,H,hd).

    Double ``lax.scan`` over q- and kv-blocks with a running (m, l, o)
    accumulator.  Memory is O(q_block * kv_block) per head, so 500k-token
    sequences lower without materializing S² scores.
    """
    B, S, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    qb = min(q_block, S)
    kb = min(kv_block, Skv)
    nq = -(-S // qb)
    nk = -(-Skv // kb)
    pad_q = nq * qb - S
    pad_k = nk * kb - Skv

    # (nq, B, qb, KV, G, hd)
    qr = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    qr = qr.reshape(B, nq, qb, KV, G, hd).transpose(1, 0, 2, 3, 4, 5) * scale
    kr = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kr = kr.reshape(B, nk, kb, KV, hd).transpose(1, 0, 2, 3, 4)
    vr = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vr = vr.reshape(B, nk, kb, KV, hd).transpose(1, 0, 2, 3, 4)

    q_idx = jnp.arange(qb)
    k_idx = jnp.arange(kb)

    def kv_step(carry, inp):
        m, l, o, qi_blk, qpos = carry
        ki, kblk, vblk = inp
        kpos = ki * kb + k_idx
        s = jnp.einsum("bqkgh,bskh->bqkgs", qi_blk, kblk,
                       preferred_element_type=jnp.float32)
        mask = (kpos[None, :] < Skv) & jnp.ones((qb, 1), bool)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgs,bskh->bqkgh", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        o = o * corr[..., None] + pv
        return (m_new, l, o, qi_blk, qpos), None

    def q_step(_, inp):
        qi, qblk = inp
        qpos = qi * qb + q_idx
        m0 = jnp.full((B, qb, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qb, KV, G), jnp.float32)
        o0 = jnp.zeros((B, qb, KV, G, hd), jnp.float32)
        (m, l, o, _, _), _ = jax.lax.scan(
            kv_step, (m0, l0, o0, qblk, qpos),
            (jnp.arange(nk), kr, vr))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qb, H, hd)
    return out[:, :S].astype(q.dtype)


def full_attention(params, x, positions, cfg: ModelConfig, *,
                   causal: bool = True, encoder_out=None,
                   use_flash: bool = False,
                   q_block: int = 512, kv_block: int = 512) -> jnp.ndarray:
    """Self- or cross-attention over a full sequence.  Returns (B,S,D)."""
    cross = encoder_out is not None
    q, k, v = _project_qkv(params, x, cfg, None if cross else positions,
                           kv_source=encoder_out)
    q = shardctx.hint(q, "batch", None, "model", None)
    window = None if cross else cfg.attn_window
    if use_flash:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(
            q, k, v, causal=causal and not cross, window=window)
    else:
        out = chunked_attention(q, k, v, causal=causal and not cross,
                                window=window, q_block=q_block,
                                kv_block=kv_block)
    out = out.reshape(x.shape[0], x.shape[1], -1)
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# Decode: one token against a (possibly seq-sharded) ring-buffer KV cache
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jnp.ndarray   # (B, W, KV, hd) — storage dtype (bf16 or int8)
    v: jnp.ndarray   # (B, W, KV, hd)
    k_scale: Any = None   # (B, W, KV, 1) f32 when int8-quantized
    v_scale: Any = None


def cache_pspec(quant: bool = False):
    sp = P("batch", "model", None, None)
    return KVCache(k=sp, v=sp,
                   k_scale=sp if quant else None,
                   v_scale=sp if quant else None)


def _quantize_kv(x: jnp.ndarray):
    """(.., hd) -> int8 payload + per-vector f32 scale (beyond-paper: the
    int8 KV cache halves decode HBM traffic; see EXPERIMENTS.md §Perf C2)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jnp.ndarray, scale, dtype=jnp.float32) -> jnp.ndarray:
    if scale is None:
        return q.astype(dtype)
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               num_layers: Optional[int] = None,
               quant: bool = False) -> KVCache:
    W = min(seq_len, cfg.attn_window) if cfg.attn_window else seq_len
    hd = cfg.resolved_head_dim
    shape = (batch, W, cfg.num_kv_heads, hd)
    if num_layers is not None:
        shape = (num_layers,) + shape
    if quant:
        z = jnp.zeros(shape, jnp.int8)
        s = jnp.ones(shape[:-1] + (1,), jnp.float32)
        return KVCache(k=z, v=z, k_scale=s, v_scale=s)
    z = jnp.zeros(shape, jnp.dtype(cfg.dtype))
    return KVCache(k=z, v=z)


def _ring_valid(pos: jnp.ndarray, W: int, slots: jnp.ndarray) -> jnp.ndarray:
    """Which ring slots hold a live position for each batch element.

    pos: (B,) current absolute position; slots: (S_loc,) global slot indices.
    """
    p = pos[:, None] - jnp.mod(pos[:, None] - slots[None, :], W)
    return p >= 0


def _write_slot_update(buf, new_val, bidx, clamped, in_range, layer=None):
    at = (bidx, clamped) if layer is None else (layer, bidx, clamped)
    cur = buf[at]
    val = jnp.where(jnp.reshape(in_range, (-1,) + (1,) * (cur.ndim - 1)),
                    new_val, cur)
    return buf.at[at].set(val)


def _layer(x, layer):
    """Layer ``layer`` of a stacked cache array (``x`` itself when None)."""
    if x is None or layer is None:
        return x
    return jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)


def _decode_core(q, cache: KVCache, new_k, new_v, pos, *, W, offset,
                 s_loc, update, axis=None, layer=None):
    """Scores one KV shard; LSE-combines across 'model' when mapped.

    q: (B,1,H,hd) -> internally (B,KV,G,hd); cache arrays: (B,s_loc,KV,*),
    or (L,B,s_loc,KV,*) stacked over layers with ``layer`` the one to use:
    the new slot is then written into the stack in place and the layer is
    read from it, so a donated stack is never copied.  Handles both bf16
    and int8-quantized (k_scale/v_scale) caches.
    """
    B, _, H, hd = q.shape
    k_cache, v_cache = cache.k, cache.v
    ks, vs = cache.k_scale, cache.v_scale
    quant = ks is not None
    KV = k_cache.shape[-2]
    G = H // KV
    slots = offset + jnp.arange(s_loc)

    if update:
        write_slot = jnp.mod(pos, W) - offset
        in_range = (write_slot >= 0) & (write_slot < s_loc)
        clamped = jnp.clip(write_slot, 0, s_loc - 1)
        bidx = jnp.arange(B)
        write = partial(_write_slot_update, bidx=bidx, clamped=clamped,
                        in_range=in_range, layer=layer)
        if quant:
            nk_q, nk_s = _quantize_kv(new_k[:, 0])
            nv_q, nv_s = _quantize_kv(new_v[:, 0])
            k_cache = write(k_cache, nk_q)
            v_cache = write(v_cache, nv_q)
            ks = write(ks, nk_s)
            vs = write(vs, nv_s)
        else:
            k_cache = write(k_cache, new_k[:, 0])
            v_cache = write(v_cache, new_v[:, 0])

    valid = _ring_valid(pos, W, slots)                       # (B, s_loc)
    kl, vl = _layer(k_cache, layer), _layer(v_cache, layer)
    ksl, vsl = _layer(ks, layer), _layer(vs, layer)
    kf = _dequantize_kv(kl, ksl) if quant else kl
    vf = _dequantize_kv(vl, vsl) if quant else vl
    qg = q.reshape(B, KV, G, hd) / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, kf,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,KV,G)
    if axis is not None:
        m_g = jax.lax.pmax(m, axis)
    else:
        m_g = m
    p = jnp.exp(s - m_g[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(vf.dtype), vf,
                   preferred_element_type=jnp.float32)
    if axis is not None:
        l = jax.lax.psum(l, axis)
        o = jax.lax.psum(o, axis)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(B, 1, H, hd)
    return out.astype(q.dtype), KVCache(k=k_cache, v=v_cache,
                                        k_scale=ks, v_scale=vs)


def decode_attention(params, cache: KVCache, x_new: jnp.ndarray,
                     pos: jnp.ndarray, cfg: ModelConfig, *,
                     update: bool = True, cross: bool = False,
                     rope_pos: Optional[jnp.ndarray] = None,
                     layer=None) -> Tuple[jnp.ndarray, KVCache]:
    """One-token attention step.

    x_new: (B, 1, D); pos: (B,) absolute position of the new token (drives
    the ring-slot layout); rope_pos overrides the RoPE angle position when
    it differs from the ring position (M-RoPE vision offset).  With
    ``layer`` the cache is stacked over layers (a leading axis) and the
    step reads and updates that layer of it.
    When a mesh is active the cache is seq-sharded over 'model' and the
    softmax is combined with psum; otherwise runs dense locally.
    """
    B = x_new.shape[0]
    W = cache.k.shape[1 if layer is None else 2]
    rp = pos if rope_pos is None else rope_pos
    if cross or not cfg.uses_rope:
        positions = None
    elif cfg.mrope:
        # decode: all three M-RoPE components advance with the text position
        positions = jnp.broadcast_to(rp[:, None, None], (B, 3, 1))
    else:
        positions = rp[:, None]
    q, new_k, new_v = _project_qkv(params, x_new, cfg, positions,
                                   barrier=True)
    mesh = shardctx.current_mesh()

    shardable = (mesh is not None and "model" in mesh.axis_names
                 and W % mesh.shape["model"] == 0)
    if not shardable:
        out, new_cache = _decode_core(
            q, cache, new_k, new_v, pos,
            W=W, offset=0, s_loc=W, update=update, layer=layer)
    else:
        n_model = mesh.shape["model"]
        s_loc = W // n_model
        bat = shardctx.batch_axes() or None
        if bat:
            n_bat = 1
            for a in bat:
                n_bat *= mesh.shape[a]
            if B % n_bat:
                bat = None           # unshardable batch (e.g. B=1): replicate

        def shard_fn(q, c, nk, nv, pos, li):
            idx = jax.lax.axis_index("model")
            return _decode_core(q, c, nk, nv, pos,
                                W=W, offset=idx * s_loc, s_loc=s_loc,
                                update=update, axis="model",
                                layer=None if layer is None else li)

        quant = cache.k_scale is not None
        sp = P(bat, "model") if layer is None else P(None, bat, "model")
        cache_spec = KVCache(k=sp, v=sp, k_scale=sp if quant else None,
                             v_scale=sp if quant else None)
        out, new_cache = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(bat), cache_spec, P(bat), P(bat), P(bat), P()),
            out_specs=(P(bat), cache_spec),
        )(q, cache, new_k, new_v, pos,
          jnp.int32(0) if layer is None else layer)

    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, new_cache


def build_cross_cache(params, encoder_out: jnp.ndarray,
                      cfg: ModelConfig) -> KVCache:
    """Static decode-time KV cache over the encoder output (no RoPE)."""
    _, k, v = _project_qkv(params, encoder_out, cfg, None,
                           apply_positions=False)
    k = shardctx.hint(k, "batch", "model", None, None)
    v = shardctx.hint(v, "batch", "model", None, None)
    return KVCache(k=k, v=v)


def prefill_cache(params, x, positions, cfg: ModelConfig,
                  window_override: Optional[int] = None,
                  quant: bool = False) -> KVCache:
    """Build the decode-layout cache from a full prefill pass."""
    _, k, v = _project_qkv(params, x, cfg, positions)
    W = window_override or (min(x.shape[1], cfg.attn_window)
                            if cfg.attn_window else x.shape[1])
    if cfg.attn_window:
        W = min(W, cfg.attn_window)
    S = x.shape[1]
    if S > W:
        k, v = k[:, -W:], v[:, -W:]
        # ring layout: slot = p mod W; the tail slice starts at position S-W,
        # which lands on slot (S-W) mod W — roll so slots line up.
        shift = (S - W) % W
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
    elif S < W:
        # identity layout; tail slots are unwritten (invalid until pos wraps)
        pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    k = shardctx.hint(k, "batch", "model", None, None)
    v = shardctx.hint(v, "batch", "model", None, None)
    if quant:
        kq, ksc = _quantize_kv(k)
        vq, vsc = _quantize_kv(v)
        return KVCache(k=kq, v=vq, k_scale=ksc, v_scale=vsc)
    return KVCache(k=k, v=v)
