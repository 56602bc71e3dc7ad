"""Attention: GQA/MQA (+qk-norm), MLA, prefill and KV-cache decode.

Four interchangeable implementations of causal self-attention, selected
by ``cfg.attn_impl``:

* ``xla_chunked`` — q-block scan with an inner dynamic ``fori_loop`` over
  KV blocks up to the causal frontier (flash-attention-style online
  softmax, O(block) memory, no upper-triangle compute).  The default.
* ``naive`` — full [S,S] score matrix.  Small models and tests.
* ``xla_unrolled`` — Python-loop flash attention, for compiles whose
  FLOPs XLA's cost analysis must see: it counts a while-loop body once.
* ``pallas`` — the TPU flash-attention kernel in ``repro.kernels``
  (validated under ``interpret=True`` on CPU).

Weights are stored flat (``wq: [D, H*Dh]``) so parameter shardings always
divide evenly; head-shaped activations get (possibly uneven) logical
sharding constraints, which GSPMD pads transparently.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import shard
from repro.models.layers import apply_rope, dense_init, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    D, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.p_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, Hq * Dh), dt),
        "wk": dense_init(ks[1], (D, Hkv * Dh), dt),
        "wv": dense_init(ks[2], (D, Hkv * Dh), dt),
        "wo": dense_init(ks[3], (Hq * Dh, D), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((Dh,), dt)
        p["k_norm"] = jnp.zeros((Dh,), dt)
    return p


def _qkv(cfg, p, x, pos):
    """Project and position-encode.  x: [B,S,D] → q[B,S,H,Dh], k/v[B,S,KV,Dh]."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt))
    q = q.reshape(B, S, Hq, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.pos == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


# ---------------------------------------------------------------------------
# Core causal attention (three impls)
# ---------------------------------------------------------------------------

def _sdpa_naive(q, k, v, q_off: int = 0, causal: bool = True):
    """q: [B,Sq,H,Dh]; k,v: [B,Skv,KV,Dh].  Full score matrix."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, KV, G, Dh)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(Sq)
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", a, v)
    return o.reshape(B, Sq, H, Dh)


def _sdpa_chunked(q, k, v, chunk: int):
    """Flash-style causal attention: scan q blocks × scan kv blocks.

    Upper-triangle block pairs are skipped by a ``lax.cond`` (a real
    branch at runtime — no wasted compute), which keeps the loop bounds
    static so reverse-mode autodiff works (training path).  Memory is
    O(block), never O(S²).
    """
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(Dh)
    qc = min(chunk, S)
    n_q = S // qc
    assert S % qc == 0, (S, qc)

    kg = k  # [B,S,KV,Dh]
    vg = v

    def q_block(carry, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)
        qg = q_blk.reshape(B, qc, KV, G, Dh)
        acc0 = jnp.zeros((B, qc, KV, G, Dh), jnp.float32)
        m0 = jnp.full((B, qc, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, KV, G), jnp.float32)

        def kv_block(mla, ki):
            m, l, acc = mla

            def compute(args):
                m, l, acc = args
                k_blk = jax.lax.dynamic_slice_in_dim(kg, ki * qc, qc,
                                                     axis=1)
                v_blk = jax.lax.dynamic_slice_in_dim(vg, ki * qc, qc,
                                                     axis=1)
                s = jnp.einsum("bqkgd,btkd->bqkgt", qg, k_blk)
                s = s.astype(jnp.float32) * scale
                qpos = qi * qc + jnp.arange(qc)
                kpos = ki * qc + jnp.arange(qc)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bqkgt,btkd->bqkgd", p.astype(q.dtype), v_blk
                ).astype(jnp.float32)
                return m_new, l, acc

            return jax.lax.cond(ki <= qi, compute, lambda a: a,
                                (m, l, acc)), None

        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, acc0),
                                      jnp.arange(n_q))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return carry, o.reshape(B, qc, H, Dh)

    _, o = jax.lax.scan(q_block, 0, jnp.arange(n_q))
    # o: [n_q, B, qc, H, Dh] → [B, S, H, Dh]
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)


def _sdpa_unrolled(q, k, v, chunk: int):
    """Python-loop flash attention: every (q,kv) block pair is a distinct
    HLO op, so XLA's cost analysis counts the true causal FLOPs
    (while-loop bodies are counted once).  Upper-triangle block pairs are
    skipped at trace time.
    """
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(Dh)
    qc = min(chunk, S)
    n = S // qc
    outs = []
    for qi in range(n):
        qg = q[:, qi * qc:(qi + 1) * qc].reshape(B, qc, KV, G, Dh)
        acc = jnp.zeros((B, qc, KV, G, Dh), jnp.float32)
        m = jnp.full((B, qc, KV, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, qc, KV, G), jnp.float32)
        for ki in range(qi + 1):
            k_blk = k[:, ki * qc:(ki + 1) * qc]
            v_blk = v[:, ki * qc:(ki + 1) * qc]
            s = jnp.einsum("bqkgd,btkd->bqkgt", qg, k_blk)
            s = s.astype(jnp.float32) * scale
            if ki == qi:                     # diagonal block: mask
                t_idx = jnp.arange(qc)
                mask = t_idx[:, None] >= t_idx[None, :]
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqkgt,btkd->bqkgd", p.astype(q.dtype), v_blk
            ).astype(jnp.float32)
            m = m_new
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        outs.append(o.reshape(B, qc, H, Dh))
    return jnp.concatenate(outs, axis=1)


def _gqa_tp_pad(cfg, q, k, v):
    """Pad query heads / replicate KV heads so attention shards evenly.

    When ``H % TP != 0`` (e.g. qwen3's 40 heads on a 16-way model axis),
    GSPMD falls back to "involuntary full rematerialization" — it
    replicates head-sharded tensors at every transition: TB-scale
    collective+copy traffic at full size.  Instead we
    make the head dim divisible: each of the KV heads is replicated
    ``rep = TP/KV`` times and its query group padded to ``rep·⌈G/rep⌉``
    — group-to-KV mapping is preserved, padded heads are sliced off
    after SDPA.  Cost: ≤(H'/H)× attention FLOPs, vs the replication
    pathology it removes.

    Returns (q', k', v', unpad) where unpad maps [B,S,H',Dh]→[B,S,H,Dh].
    """
    from repro.distribution.sharding import axis_size, current_ctx
    tp = axis_size("heads")
    H, KV = cfg.n_heads, cfg.n_kv_heads
    if (not cfg.gqa_pad or current_ctx() is None or tp <= 1
            or not cfg.shard_heads or H % tp == 0 or tp % KV != 0):
        return q, k, v, None
    rep = tp // KV
    G = H // KV
    Gp = -(-G // rep)                      # ceil
    B, S, _, Dh = q.shape
    qg = q.reshape(B, S, KV, G, Dh)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, rep * Gp - G), (0, 0)))
    qp = qg.reshape(B, S, KV * rep, Gp, Dh).reshape(B, S, KV * rep * Gp,
                                                    Dh)
    kp = jnp.repeat(k, rep, axis=2)
    vp = jnp.repeat(v, rep, axis=2)

    def unpad(o):
        o = o.reshape(B, S, KV, rep * Gp, Dh)[:, :, :, :G]
        return o.reshape(B, S, H, Dh)

    return qp, kp, vp, unpad


def sdpa(cfg, q, k, v):
    """Dispatch causal self-attention by ``cfg.attn_impl``."""
    q, k, v, unpad = _gqa_tp_pad(cfg, q, k, v)
    if unpad is not None:
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "seq", "heads", None)
        v = shard(v, "batch", "seq", "heads", None)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=True)
    elif cfg.attn_impl == "xla_unrolled" and q.shape[1] > cfg.attn_chunk:
        o = _sdpa_unrolled(q, k, v, max(cfg.attn_chunk, q.shape[1] // 8))
    elif cfg.attn_impl == "xla_chunked" and q.shape[1] > cfg.attn_chunk:
        o = _sdpa_chunked(q, k, v, cfg.attn_chunk)
    else:
        o = _sdpa_naive(q, k, v)
    return unpad(o) if unpad is not None else o


def attention(cfg, p, x, pos):
    """Full-sequence causal self-attention.  x: [B,S,D]."""
    q, k, v = _qkv(cfg, p, x, pos)
    o = sdpa(cfg, q, k, v)
    o = shard(o, "batch", "seq", "heads", None)
    B, S = x.shape[:2]
    out = jnp.einsum("bse,ed->bsd",
                     o.reshape(B, S, cfg.q_dim), p["wo"].astype(x.dtype))
    return shard(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, n_layers: int | None = None
                  ) -> dict:
    L = n_layers if n_layers is not None else cfg.n_layers
    shp = (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, cfg.act_dtype),
            "v": jnp.zeros(shp, cfg.act_dtype)}


def kv_cache_spec():
    """Logical dim names of a KV cache entry [L,B,S,KV,Dh]."""
    return (None, "batch", "seq_kv", "kv_heads", None)


def cache_seq_axes(cfg):
    """Physical mesh axes the decode-cache sequence dim is sharded over
    (mirrors the cache-spec logic in transformer.py)."""
    from repro.distribution.sharding import axis_size, current_ctx, phys
    ctx = current_ctx()
    if ctx is None:
        return None
    kv_ok = (cfg.shard_heads
             and cfg.n_kv_heads % max(axis_size("kv_heads"), 1) == 0
             and axis_size("kv_heads") > 1)
    if cfg.mla is not None or not kv_ok:
        return phys("seq_kv", "seq_kv_tp")
    return phys("seq_kv")


def _flash_decode_sharded(qg, k_cache, v_cache, pos, scale, axes):
    """Partial-softmax flash-decode over a seq-sharded cache (shard_map).

    qg: [B,KV,G,Dh] (replicated over ``axes``); k/v_cache:
    [B,S,KV,Dh] with S sharded over ``axes``; pos: [B].  Each shard
    computes f32 scores over only its local cache slice — the combine is
    a 3-scalar-ish collective (pmax of m, psum of l and o) instead of a
    gathered [B,H,S] f32 score array.
    """
    from repro.distribution.sharding import current_ctx
    ctx = current_ctx()
    mesh = ctx.mesh
    n_sh = 1
    for a in axes:
        n_sh *= mesh.shape[a]
    S = k_cache.shape[1]
    S_l = S // n_sh
    dp = ctx.rules.get("batch")

    def local(qg, kc, vc, pos):
        # global offset of this shard's cache slice
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        t = idx * S_l + jnp.arange(S_l)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc).astype(jnp.float32)
        s = s * scale
        mask = t[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m = jax.lax.pmax(s.max(axis=-1), axes)
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(p.sum(axis=-1), axes)
        o = jnp.einsum("bkgt,btkd->bkgd", p.astype(qg.dtype), vc)
        o = jax.lax.psum(o.astype(jnp.float32), axes)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(qg.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, axes, None, None),
                  P(dp, axes, None, None), P(dp)),
        out_specs=P(dp, None, None, None),
        check_vma=False)(qg, k_cache, v_cache, pos)


def decode_attention(cfg, p, x, k_cache, v_cache, pos):
    """One-token decode.  x: [B,1,D]; k/v_cache: [B,S_max,KV,Dh] (already
    containing this step's k,v at index ``pos``).  ``pos``: [B] int32.

    When the cache's sequence dim is sharded (MQA/GQA archs whose kv
    heads don't divide the TP degree, and long-context shapes), the
    attention runs as a shard_map flash-decode: per-shard partial
    softmax + a tiny (m, l, o) combine, never materializing a gathered
    [B,H,S] f32 score array.
    """
    B, _, D = x.shape
    Hq, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = Hq // KV
    dt = x.dtype
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)).reshape(B, Hq, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
    if cfg.pos == "rope":
        q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    qg = q.reshape(B, KV, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    axes = cache_seq_axes(cfg) if cfg.flash_decode else None
    if axes:
        o = _flash_decode_sharded(qg, k_cache, v_cache, pos, scale, axes)
        o = o.reshape(B, Hq * Dh)
        out = jnp.einsum("be,ed->bd", o, p["wo"].astype(dt))
        return out[:, None, :]
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache).astype(jnp.float32)
    s = s * scale
    t = jnp.arange(k_cache.shape[1])
    mask = t[None, :] <= pos[:, None]                       # [B,S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bkgt,btkd->bkgd", a, v_cache).reshape(B, Hq * Dh)
    out = jnp.einsum("be,ed->bd", o, p["wo"].astype(dt))
    return out[:, None, :]                                  # [B,1,D]


def append_kv(cfg, p, x, k_cache, v_cache, pos):
    """Project this token's k,v and write them into the cache at ``pos``."""
    B = x.shape[0]
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)).reshape(B, 1, KV, Dh)
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)).reshape(B, 1, KV, Dh)
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"])
    if cfg.pos == "rope":
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, pos].set(k[:, 0])
    v_cache = v_cache.at[bidx, pos].set(v[:, 0])
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(key, cfg) -> dict:
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    dt = cfg.p_dtype
    qk = m.qk_nope + m.qk_rope
    ks = jax.random.split(key, 7)
    return {
        "wq_a": dense_init(ks[0], (D, m.q_lora), dt),         # q down
        "q_a_norm": jnp.zeros((m.q_lora,), dt),
        "wq_b": dense_init(ks[1], (m.q_lora, H * qk), dt),    # q up
        "wkv_a": dense_init(ks[2], (D, m.kv_lora + m.qk_rope), dt),
        "kv_a_norm": jnp.zeros((m.kv_lora,), dt),
        "wk_b": dense_init(ks[3], (m.kv_lora, H * m.qk_nope), dt),
        "wv_b": dense_init(ks[4], (m.kv_lora, H * m.v_dim), dt),
        "wo": dense_init(ks[5], (H * m.v_dim, D), dt),
    }


def _mla_qkv(cfg, p, x, pos):
    """Decompressed-path MLA projections (prefill/training)."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    dt = x.dtype
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt)),
                 p["q_a_norm"])
    q = jnp.einsum("bsr,re->bse", cq, p["wq_b"].astype(dt))
    q = q.reshape(B, S, H, m.qk_nope + m.qk_rope)
    q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]

    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c_kv = rmsnorm(kv[..., :m.kv_lora], p["kv_a_norm"])      # [B,S,kv_lora]
    k_rope = kv[..., m.kv_lora:][:, :, None, :]              # [B,S,1,rope]
    k_nope = jnp.einsum("bsr,re->bse", c_kv, p["wk_b"].astype(dt))
    k_nope = k_nope.reshape(B, S, H, m.qk_nope)
    v = jnp.einsum("bsr,re->bse", c_kv, p["wv_b"].astype(dt))
    v = v.reshape(B, S, H, m.v_dim)

    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    k_rope = apply_rope(k_rope, pos, cfg.rope_theta)
    k_rope1 = k_rope[:, :, 0, :]                             # cached (roped)
    k_rope = jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "heads", None)
    v = shard(v, "batch", "seq", "heads", None)
    return q, k, v, (c_kv, k_rope1)


def mla_attention(cfg, p, x, pos):
    """Full-sequence MLA (prefill/training): decompress then dense SDPA."""
    m = cfg.mla
    q, k, v, _ = _mla_qkv(cfg, p, x, pos)
    # kv heads == q heads after decompression → GQA group of 1.
    o = _mla_sdpa(cfg, q, k, v)
    B, S = x.shape[:2]
    out = jnp.einsum("bse,ed->bsd",
                     o.reshape(B, S, cfg.n_heads * m.v_dim),
                     p["wo"].astype(x.dtype))
    return shard(out, "batch", "seq", "embed")


def _mla_sdpa(cfg, q, k, v):
    """SDPA where q/k dims differ from v dim (MLA: 192 vs 128)."""
    B, S, H, qk = q.shape
    scale = 1.0 / math.sqrt(qk)
    if cfg.attn_impl == "xla_unrolled" and S > cfg.attn_chunk:
        return _sdpa_unrolled_vd(q, k, v,
                                 max(cfg.attn_chunk, S // 8), scale)
    if cfg.attn_impl == "xla_chunked" and S > cfg.attn_chunk:
        return _sdpa_chunked_vd(q, k, v, cfg.attn_chunk, scale)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(S)
    mask = qpos[:, None] >= qpos[None, :]
    s = jnp.where(mask[None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bthd->bqhd", a, v)


def _sdpa_chunked_vd(q, k, v, chunk, scale):
    """Chunked causal SDPA with distinct qk / v head dims (MLA).

    Same structure as :func:`_sdpa_chunked`: static-bound scans with a
    ``lax.cond`` causal skip, so it is reverse-mode differentiable.
    """
    B, S, H, _ = q.shape
    Dv = v.shape[-1]
    qc = min(chunk, S)
    n_q = S // qc

    def q_block(carry, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)
        acc0 = jnp.zeros((B, qc, H, Dv), jnp.float32)
        m0 = jnp.full((B, qc, H), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, H), jnp.float32)

        def kv_block(mla_, ki):
            m, l, acc = mla_

            def compute(args):
                m, l, acc = args
                k_blk = jax.lax.dynamic_slice_in_dim(k, ki * qc, qc,
                                                     axis=1)
                v_blk = jax.lax.dynamic_slice_in_dim(v, ki * qc, qc,
                                                     axis=1)
                s = jnp.einsum("bqhd,bthd->bqht", q_blk, k_blk)
                s = s.astype(jnp.float32) * scale
                qpos = qi * qc + jnp.arange(qc)
                kpos = ki * qc + jnp.arange(qc)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, :, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1))
                pp = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l = l * corr + pp.sum(axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bqht,bthd->bqhd", pp.astype(q.dtype), v_blk
                ).astype(jnp.float32)
                return m_new, l, acc

            return jax.lax.cond(ki <= qi, compute, lambda a: a,
                                (m, l, acc)), None

        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, acc0),
                                      jnp.arange(n_q))
        return carry, (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    _, o = jax.lax.scan(q_block, 0, jnp.arange(n_q))
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dv)


def _sdpa_unrolled_vd(q, k, v, chunk, scale):
    """Unrolled (trace-time loop) MLA SDPA — FLOPs visible to XLA's cost
    analysis."""
    B, S, H, _ = q.shape
    Dv = v.shape[-1]
    qc = min(chunk, S)
    n = S // qc
    outs = []
    for qi in range(n):
        q_blk = q[:, qi * qc:(qi + 1) * qc]
        acc = jnp.zeros((B, qc, H, Dv), jnp.float32)
        m = jnp.full((B, qc, H), NEG_INF, jnp.float32)
        l = jnp.zeros((B, qc, H), jnp.float32)
        for ki in range(qi + 1):
            k_blk = k[:, ki * qc:(ki + 1) * qc]
            v_blk = v[:, ki * qc:(ki + 1) * qc]
            s = jnp.einsum("bqhd,bthd->bqht", q_blk, k_blk)
            s = s.astype(jnp.float32) * scale
            if ki == qi:
                t_idx = jnp.arange(qc)
                mask = t_idx[:, None] >= t_idx[None, :]
                s = jnp.where(mask[None, :, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqht,bthd->bqhd", p.astype(q.dtype), v_blk
            ).astype(jnp.float32)
            m = m_new
        outs.append((acc / jnp.maximum(l, 1e-30)[..., None]
                     ).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def init_mla_cache(cfg, batch: int, max_len: int,
                   n_layers: int | None = None) -> dict:
    """MLA caches the *compressed* latent + shared rope key — the paper-
    faithful memory win (kv_lora + qk_rope per token instead of
    2·H·head_dim)."""
    m = cfg.mla
    L = n_layers if n_layers is not None else cfg.n_layers
    return {
        "c_kv": jnp.zeros((L, batch, max_len, m.kv_lora), cfg.act_dtype),
        "k_rope": jnp.zeros((L, batch, max_len, m.qk_rope), cfg.act_dtype),
    }


def mla_decode(cfg, p, x, c_kv_cache, k_rope_cache, pos):
    """One-token MLA decode with weight absorption.

    Scores are computed directly in the latent space:
      q_lat = q_nope @ W_kb  (absorb)          [B,H,kv_lora]
      s     = q_lat · c_kv + q_rope · k_rope   [B,H,S]
      o_lat = softmax(s) · c_kv                [B,H,kv_lora]
      o     = o_lat @ W_vb                     [B,H,v_dim]
    so the per-token cache stays (kv_lora + qk_rope) wide.
    """
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    dt = x.dtype
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt)),
                 p["q_a_norm"])
    q = jnp.einsum("bsr,re->bse", cq, p["wq_b"].astype(dt))
    q = q.reshape(B, H, m.qk_nope + m.qk_rope)
    q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope[:, None], pos[:, None],
                        cfg.rope_theta)[:, 0]
    wk_b = p["wk_b"].astype(dt).reshape(m.kv_lora, H, m.qk_nope)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wk_b)        # absorb W_kb

    scale = 1.0 / math.sqrt(m.qk_nope + m.qk_rope)
    axes = cache_seq_axes(cfg) if cfg.flash_decode else None
    if axes:
        o_lat = _mla_flash_decode_sharded(q_lat, q_rope, c_kv_cache,
                                          k_rope_cache, pos, scale, axes)
    else:
        s = jnp.einsum("bhr,btr->bht", q_lat, c_kv_cache)
        s = s + jnp.einsum("bhn,btn->bht", q_rope, k_rope_cache)
        s = s.astype(jnp.float32) * scale
        t = jnp.arange(c_kv_cache.shape[1])
        mask = t[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1).astype(dt)
        o_lat = jnp.einsum("bht,btr->bhr", a, c_kv_cache)
    wv_b = p["wv_b"].astype(dt).reshape(m.kv_lora, H, m.v_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_b).reshape(B, H * m.v_dim)
    out = jnp.einsum("be,ed->bd", o, p["wo"].astype(dt))
    return out[:, None, :]


def _mla_flash_decode_sharded(q_lat, q_rope, c_kv_cache, k_rope_cache,
                              pos, scale, axes):
    """MLA flash-decode over a seq-sharded latent cache (shard_map)."""
    from repro.distribution.sharding import current_ctx
    ctx = current_ctx()
    mesh = ctx.mesh
    n_sh = 1
    for a in axes:
        n_sh *= mesh.shape[a]
    S_l = c_kv_cache.shape[1] // n_sh
    dp = ctx.rules.get("batch")

    def local(ql, qr, ckv, krope, pos):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        t = idx * S_l + jnp.arange(S_l)
        s = jnp.einsum("bhr,btr->bht", ql, ckv)
        s = s + jnp.einsum("bhn,btn->bht", qr, krope)
        s = s.astype(jnp.float32) * scale
        mask = t[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        m = jax.lax.pmax(s.max(axis=-1), axes)
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(p.sum(axis=-1), axes)
        o = jnp.einsum("bht,btr->bhr", p.astype(ql.dtype), ckv)
        o = jax.lax.psum(o.astype(jnp.float32), axes)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(ql.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, None, None),
                  P(dp, axes, None), P(dp, axes, None), P(dp)),
        out_specs=P(dp, None, None),
        check_vma=False)(q_lat, q_rope, c_kv_cache, k_rope_cache, pos)


def mla_append_kv(cfg, p, x, c_kv_cache, k_rope_cache, pos):
    m = cfg.mla
    B = x.shape[0]
    dt = x.dtype
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c_kv = rmsnorm(kv[..., :m.kv_lora], p["kv_a_norm"])[:, 0]
    k_rope = apply_rope(kv[..., m.kv_lora:][:, :, None, :],
                        pos[:, None], cfg.rope_theta)[:, 0, 0]
    bidx = jnp.arange(B)
    c_kv_cache = c_kv_cache.at[bidx, pos].set(c_kv)
    k_rope_cache = k_rope_cache.at[bidx, pos].set(k_rope)
    return c_kv_cache, k_rope_cache
