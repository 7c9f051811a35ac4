"""GQA attention: chunked (memory-efficient) prefill/train path + cached
decode path.

The train/prefill path scans over query chunks with an online-softmax
accumulator so (Sq, Skv) score matrices never materialize for long
sequences — the pure-jnp analogue of the Pallas ``flash_attention``
kernel (which `repro.kernels.flash_attention` provides for TPU).

Supports: GQA (n_kv < n_heads), optional QKV bias, qk_norm (per-head
RMSNorm on q/k as in Qwen3), causal or bidirectional masks, sliding
windows, cross-attention, and single-token decode against a KV cache
(optionally a rolling window buffer for SWA).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import rope as rope_lib
from repro.models.layers import _dense_init, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.hd
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": _dense_init(ks[0], (d, H * hd)),
        "wk": _dense_init(ks[1], (d, KV * hd)),
        "wv": _dense_init(ks[2], (d, KV * hd)),
        "wo": _dense_init(ks[3], (H * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    del cross
    return p


def specs_attention(cfg, *, cross: bool = False):
    del cross
    p = {"wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"), "wv": P("fsdp", "tp"),
         "wo": P("tp", "fsdp")}
    if cfg.qkv_bias:
        p.update(bq=P("tp"), bk=P("tp"), bv=P("tp"))
    if cfg.qk_norm:
        p.update(q_norm=P(None), k_norm=P(None))
    return p


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _project_qkv(p, x, x_kv, cfg):
    B, Sq, _ = x.shape
    Skv = x_kv.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x_kv @ p["wk"]
    v = x_kv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Sq, H, hd)
    k = k.reshape(B, Skv, KV, hd)
    v = v.reshape(B, Skv, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _apply_positions(q, k, positions, kv_positions, cfg, positions_thw=None,
                     kv_positions_thw=None):
    if cfg.rope_theta <= 0:
        return q, k
    if cfg.m_rope:
        if positions_thw is None:
            positions_thw = rope_lib.text_positions_thw(positions)
        if kv_positions_thw is None:
            kv_positions_thw = rope_lib.text_positions_thw(kv_positions)
        q = rope_lib.apply_m_rope(q, positions_thw, cfg.rope_theta, cfg.m_rope_sections)
        k = rope_lib.apply_m_rope(k, kv_positions_thw, cfg.rope_theta, cfg.m_rope_sections)
    else:
        q = rope_lib.apply_rope(q, positions, cfg.rope_theta)
        k = rope_lib.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k


# ---------------------------------------------------------------------------
# Chunked blockwise attention core (pure jnp oracle of the Pallas kernel)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                        window: Optional[int], q_chunk: int = 1024):
    """Online-softmax attention scanning over query chunks.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); positions: (B, S*) int32.
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    qg = (q * scale).reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    n = max(1, Sq // q_chunk)
    while Sq % n:
        n -= 1
    C = Sq // n
    q_chunks = qg.reshape(B, n, C, KV, G, hd).swapaxes(0, 1)            # (n,B,C,KV,G,hd)
    qpos_chunks = q_positions.reshape(B, n, C).swapaxes(0, 1)           # (n,B,C)

    def one_chunk(carry, xc):
        qc, qp = xc                                                     # (B,C,KV,G,hd),(B,C)
        s = jnp.einsum("bckgd,bskd->bckgs", qc, kf)                     # (B,C,KV,G,Skv)
        mask = jnp.ones((), jnp.bool_)
        kvp = kv_positions[:, None, None, None, :]                      # (B,1,1,1,Skv)
        qpp = qp[:, :, None, None, None]                                # (B,C,1,1,1)
        if causal:
            mask = kvp <= qpp
        if window is not None:
            mask = mask & (kvp > qpp - window)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, NEG_INF)                                     # guard all-masked rows
        e = jnp.exp(s - m)
        z = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bckgs,bskd->bckgd", e / jnp.maximum(z, 1e-30), vf)
        return carry, o

    _, outs = jax.lax.scan(one_chunk, 0, (q_chunks, qpos_chunks))       # (n,B,C,KV,G,hd)
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def kv_blockwise_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                           window: Optional[int], kv_chunk: int = 1024,
                           seq_spec: Optional[P] = None):
    """Online-softmax attention scanning over KV chunks.

    Unlike q-chunking, the query (and all accumulators) keep their full
    sequence dim, so a sequence-sharded residual stays sharded through the
    scan under GSPMD — per-device score buffers are (B, Sq/shards, H, Ck).
    The jnp analogue of the Pallas flash kernel's kv-sequential axis.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Sq, KV, G, hd).astype(jnp.float32)

    n = max(1, Skv // kv_chunk)
    while Skv % n:
        n -= 1
    Ck = Skv // n
    kc = k.astype(jnp.float32).reshape(B, n, Ck, KV, hd).swapaxes(0, 1)
    vc = v.astype(jnp.float32).reshape(B, n, Ck, KV, hd).swapaxes(0, 1)
    pc = kv_positions.reshape(B, n, Ck).swapaxes(0, 1)
    qpp = q_positions[:, :, None, None, None]                 # (B,Sq,1,1,1)

    # keep the (sharded) q sequence dim pinned through the scan carry
    bspec = seq_spec[0] if seq_spec is not None and len(seq_spec) else None
    sspec = seq_spec[1] if seq_spec is not None and len(seq_spec) > 1 else None
    spec4 = P(bspec, sspec, None, None) if seq_spec is not None else None
    spec5 = P(bspec, sspec, None, None, None) if seq_spec is not None else None

    def pin(m, l, acc):
        if seq_spec is None:
            return m, l, acc
        return (jax.lax.with_sharding_constraint(m, spec4),
                jax.lax.with_sharding_constraint(l, spec4),
                jax.lax.with_sharding_constraint(acc, spec5))

    def step(carry, xc):
        m, l, acc = carry
        kb, vb, pb = xc                                       # (B,Ck,KV,hd),(B,Ck)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kb)           # (B,Sq,KV,G,Ck)
        kvp = pb[:, None, None, None, :]
        mask = kvp >= 0
        if causal:
            mask &= kvp <= qpp
        if window is not None:
            mask &= kvp > qpp - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)[..., None]
        p = jnp.exp(s - m_new[..., None])
        l_new = alpha[..., 0] * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha + jnp.einsum("bqkgs,bskd->bqkgd", p, vb)
        return pin(m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, pin(m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def full_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                   window: Optional[int], kv_valid_len=None, seq_spec=None,
                   kv_heads_major: bool = False):
    """Un-chunked reference path (decode / short sequences).

    With seq_spec (the residual's (batch, seq, ...) spec), pins the
    canonical orientation: q stays sequence-sharded, k/v replicate over
    the sequence axis, scores shard on the q dim — prevents GSPMD from
    flip-flopping between q- and kv-sharded layouts inside scans.
    """
    B, Sq, H, hd = q.shape
    if kv_heads_major:
        _, KV, Skv, _ = k.shape
    else:
        _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    kf, vf = k, v
    if seq_spec is not None and len(seq_spec) > 1:
        b, s = seq_spec[0], seq_spec[1]
        qg = jax.lax.with_sharding_constraint(qg, P(b, s, None, None, None))
        kf = jax.lax.with_sharding_constraint(kf, P(b, None, None, None))
        vf = jax.lax.with_sharding_constraint(vf, P(b, None, None, None))
    kv_eq = "bksd" if kv_heads_major else "bskd"
    # keep k in bf16 on the wire; accumulate in f32 (MXU-native on TPU)
    s = jnp.einsum(f"bqkgd,{kv_eq}->bqkgs", qg, kf,
                   preferred_element_type=jnp.float32)
    if seq_spec is not None and len(seq_spec) > 1:
        s = jax.lax.with_sharding_constraint(
            s, P(seq_spec[0], seq_spec[1], None, None, None))
    k, v = kf, vf
    kvp = kv_positions[:, None, None, None, :]
    qpp = q_positions[:, :, None, None, None]
    mask = jnp.ones(s.shape, jnp.bool_) & (kvp >= 0)   # -1 = unwritten slot
    if causal:
        mask = mask & (kvp <= qpp)
    if window is not None:
        mask = mask & (kvp > qpp - window)
    if kv_valid_len is not None:
        mask = mask & (jnp.arange(Skv)[None, None, None, None, :]
                       < kv_valid_len[:, None, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # v upcast on the fly: XLA absorbs the convert into the value dot's
    # operand stream; feeding bf16 directly made layout assignment pick a
    # transposed layout for the cached v and re-copy the full carried cache
    # every layer (perf iteration #2b, EXPERIMENTS.md §Perf)
    o = jnp.einsum(f"bqkgs,{kv_eq}->bqkgd", w, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["k", "v", "pos"], meta_fields=["window"])
@dataclasses.dataclass
class KVCache:
    """Decode KV cache, stored HEADS-MAJOR: (B, KV, S_buf, hd).

    Layout note (perf iteration #1, EXPERIMENTS.md §Perf): with the naive
    (B, S, KV, hd) layout the decode layer-loop carried the cache in a
    layout that disagreed between the score contraction (wants hd
    innermost) and the value contraction (wants S second-to-last), and
    XLA inserted two full-cache layout copies PER LAYER per step.  With
    (B, KV, S, hd) both dots are layout-natural and the carry stays put.
    """
    k: jax.Array          # (B, KV, S_buf, hd)  [stacked (L, B, ...) across layers]
    v: jax.Array          # (B, KV, S_buf, hd)
    pos: jax.Array        # (B,) next absolute position to write
    window: int = 0       # 0 = linear buffer; >0 = rolling SWA buffer (static)

    @property
    def rolling(self) -> bool:
        return self.window > 0

    def _replace(self, **kw) -> "KVCache":
        return dataclasses.replace(self, **kw)


def init_kv_cache(batch, max_len, cfg, *, window: Optional[int] = None,
                  dtype=jnp.bfloat16):
    """window: cap the buffer at the sliding window (rolling writes)."""
    buf = max_len if window is None else min(max_len, window)
    shape = (batch, cfg.n_kv_heads, buf, cfg.hd)      # heads-major (see KVCache)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((batch,), jnp.int32),
        window=0 if window is None else buf,
    )


def update_kv_cache(cache: KVCache, k_new, v_new):
    """Append one token (decode step). k_new: (B, 1, KV, hd).

    All sequences decode in lockstep in our serving engine, so the write
    index is a single dynamic scalar — XLA SPMD partitions a scalar-start
    dynamic-update-slice along a sequence-sharded buffer in place, with no
    collectives (verified in the dry-run HLO)."""
    B, buf = cache.k.shape[0], cache.k.shape[2]
    pos0 = jnp.max(cache.pos)
    idx = pos0 % buf if cache.rolling else jnp.minimum(pos0, buf - 1)

    def write(bufarr, new):
        # new: (B, 1, KV, hd) -> heads-major (B, KV, 1, hd)
        return jax.lax.dynamic_update_slice_in_dim(
            bufarr, new.swapaxes(1, 2).astype(bufarr.dtype), idx, axis=2)

    return cache._replace(k=write(cache.k, k_new),
                          v=write(cache.v, v_new),
                          pos=cache.pos + 1)


def cache_kv_positions(cache: KVCache):
    """Absolute position of every buffer slot (rolling-aware). (B, S_buf)."""
    B, buf = cache.k.shape[0], cache.k.shape[2]
    slots = jnp.arange(buf)[None, :]                                    # (1, buf)
    if not cache.rolling:
        return jnp.broadcast_to(slots, (B, buf))
    # slot s holds absolute position: the largest p < pos with p % buf == s
    pos = cache.pos[:, None]
    cand = pos - 1 - ((pos - 1 - slots) % buf)
    return jnp.where(cand >= 0, cand, -1)                               # -1 = never written


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def attention_forward(p, x, cfg, *, positions=None, positions_thw=None,
                      causal=True, x_kv=None, kv_positions=None,
                      q_chunk: int = 1024, act_spec=None, seq_spec=None):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    cross = x_kv is not None
    xkv = x if x_kv is None else x_kv
    if kv_positions is None:
        kv_positions = (positions if not cross else jnp.broadcast_to(
            jnp.arange(xkv.shape[1])[None], (B, xkv.shape[1])).astype(jnp.int32))
    q, k, v = _project_qkv(p, x, xkv, cfg)
    if not cross:  # RoPE only applies to self-attention in our archs
        q, k = _apply_positions(q, k, positions, kv_positions, cfg,
                                positions_thw=positions_thw,
                                kv_positions_thw=positions_thw)
    window = cfg.sliding_window if (causal and not cross) else None
    if S <= 4096 and xkv.shape[1] <= 4096:
        o = full_attention(q, k, v, q_positions=positions,
                           kv_positions=kv_positions,
                           causal=causal and not cross, window=window,
                           seq_spec=seq_spec)
    else:
        # long sequences: kv-sequential online softmax keeps the (sharded)
        # q sequence dim intact (see kv_blockwise_attention)
        o = kv_blockwise_attention(q, k, v, q_positions=positions,
                                   kv_positions=kv_positions,
                                   causal=causal and not cross, window=window,
                                   kv_chunk=max(q_chunk, 512),
                                   seq_spec=seq_spec)
    if act_spec is not None:
        o = jax.lax.with_sharding_constraint(o, act_spec)
    return o.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]


def attention_decode(p, x, cfg, cache: KVCache, *, positions_thw=None,
                     cross_kv=None):
    """One-token decode. x: (B, 1, d). Returns (y, new_cache)."""
    B = x.shape[0]
    positions = cache.pos[:, None]                                       # (B, 1)
    if cross_kv is not None:
        k, v = cross_kv
        q = (x @ p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        q = q.reshape(B, 1, cfg.n_heads, cfg.hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        kvp = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (B, k.shape[1]))
        o = full_attention(q, k, v, q_positions=positions, kv_positions=kvp,
                           causal=False, window=None)
        return o.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"], cache, None
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    q, k_new = _apply_positions(q, k_new, positions, positions, cfg,
                                positions_thw=positions_thw,
                                kv_positions_thw=positions_thw)
    cache = update_kv_cache(cache, k_new, v_new)
    kv_pos = cache_kv_positions(cache)
    valid = None if cache.rolling else cache.pos
    o = full_attention(q, cache.k, cache.v, q_positions=positions,
                       kv_positions=kv_pos, causal=True,
                       window=cfg.sliding_window,
                       kv_valid_len=valid, kv_heads_major=True)
    out = o.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    # expose the written token column so callers can write back just that
    # column into a stacked cache (heads-major (B, KV, 1, hd))
    token_kv = (k_new.swapaxes(1, 2), v_new.swapaxes(1, 2))
    return out, cache, token_kv


def attention_prefill(p, x, cfg, cache: KVCache, *, positions=None,
                      positions_thw=None, q_chunk: int = 1024, seq_spec=None):
    """Fused prompt pass: one set of QKV projections used both for the
    attention output and to fill the decode cache.  Returns (out, cache)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, x, cfg)
    q, k = _apply_positions(q, k, positions, positions, cfg,
                            positions_thw=positions_thw,
                            kv_positions_thw=positions_thw)
    window = cfg.sliding_window
    if S <= 4096:
        o = full_attention(q, k, v, q_positions=positions,
                           kv_positions=positions, causal=True, window=window,
                           seq_spec=seq_spec)
    else:
        o = kv_blockwise_attention(q, k, v, q_positions=positions,
                                   kv_positions=positions, causal=True,
                                   window=window, kv_chunk=max(q_chunk, 512),
                                   seq_spec=seq_spec)
    out = o.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
    cache = _store_prefix_kv(cache, k, v, S)
    return out, cache


def _store_prefix_kv(cache: KVCache, k, v, S: int) -> KVCache:
    """Write a full prompt's (rotated) K/V into the cache buffer
    (heads-major layout)."""
    B = k.shape[0]
    buf = cache.k.shape[2]
    take = min(S, buf)
    kw = k[:, -take:].swapaxes(1, 2)     # (B, KV, take, hd)
    vw = v[:, -take:].swapaxes(1, 2)
    if buf > take:
        pad = ((0, 0), (0, 0), (0, buf - take), (0, 0))
        kw, vw = jnp.pad(kw, pad), jnp.pad(vw, pad)
    if cache.rolling and S > buf:
        kw = jnp.roll(kw, shift=S % buf, axis=2)
        vw = jnp.roll(vw, shift=S % buf, axis=2)
    return cache._replace(k=kw.astype(cache.k.dtype), v=vw.astype(cache.v.dtype),
                          pos=jnp.full((B,), S, jnp.int32))


def prefill_kv(p, x, cfg, cache: KVCache, *, positions=None,
               positions_thw=None):
    """Run projections over a prompt and fill the cache (no attention output).

    Used by serve prefill when only the cache (not hidden states) is needed
    downstream; the normal prefill path uses attention_forward and fills the
    cache with the same k/v.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    _, k, v = _project_qkv(p, x, x, cfg)
    _, k = _apply_positions(k, k, positions, positions, cfg,
                            positions_thw=positions_thw,
                            kv_positions_thw=positions_thw)
    del B
    return _store_prefix_kv(cache, k, v, S)
