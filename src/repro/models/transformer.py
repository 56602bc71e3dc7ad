"""Model assembly: blocks → layer stack (scan or unrolled) → LM API.

``build_model(cfg)`` returns a :class:`Model` of pure functions:

* ``init(rng) → params`` — per-layer params stacked on a leading ``L``
  axis, consumed via ``jax.lax.scan`` (keeps HLO size O(1) in depth).
* ``forward(params, tokens) → (logits, aux)`` — full-sequence.
* ``loss(params, tokens, labels) → scalar`` — mean xent + MoE aux.
* ``init_cache / prefill / decode_step`` — serving path.
* ``cache_specs(batch, max_len) → pytree[PartitionSpec]`` — decode-cache
  shardings under the active sharding context.

``layer_mode="unroll"`` replaces the scan with a Python loop, for
compiles whose FLOPs XLA's cost analysis must see: it counts a
while-loop body once.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import pspec, shard
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rk
from repro.models.common import ModelCfg
from repro.models.layers import (apply_norm, embed, init_embed, init_mlp,
                                 lm_logits, mlp, rmsnorm, sinusoidal_pe,
                                 softmax_xent)


class Model(NamedTuple):
    cfg: ModelCfg
    init: Callable
    forward: Callable          # (params, tokens) -> (logits, aux)
    loss: Callable             # (params, tokens, labels) -> loss
    init_cache: Callable       # (batch, max_len) -> cache
    prefill: Callable          # (params, tokens, cache) -> (logits, cache)
    decode_step: Callable      # (params, tok[B,1], cache, pos[B]) -> (logits, cache)
    cache_specs: Callable      # (batch, max_len) -> pytree of PartitionSpec


def _norm_param(cfg, key):
    if cfg.norm == "layernorm_np":
        return {}
    return {key: jnp.zeros((cfg.d_model,), cfg.p_dtype)}


def _maybe(p, key):
    return p.get(key)


# ---------------------------------------------------------------------------
# Dense / MoE transformer block
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    p = {}
    p.update({f"ln1{k}": v for k, v in _norm_param(cfg, "s").items()})
    p.update({f"ln2{k}": v for k, v in _norm_param(cfg, "s").items()})
    p["attn"] = attn.init_mla(k1, cfg) if cfg.mla else \
        attn.init_attention(k1, cfg)
    p["mlp"] = init_moe(k2, cfg) if cfg.moe else init_mlp(k2, cfg)
    return p


def init_moe(key, cfg):
    return moe_mod.init_moe(key, cfg)


def _block_mlp(cfg, p, x, *, decode: bool):
    if cfg.moe is not None:
        return moe_mod.moe(cfg, p["mlp"], x, decode=decode)
    return mlp(cfg, p["mlp"], x), jnp.float32(0.0)


def dense_block(cfg, p, x, pos, *, want_kv: bool):
    """Full-seq block.  Returns (x, kv_for_cache, aux)."""
    h = apply_norm(cfg, x, _maybe(p, "ln1s"))
    if cfg.mla is not None:
        q, k, v, latent = attn._mla_qkv(cfg, p["attn"], h, pos)
        o = attn._mla_sdpa(cfg, q, k, v)
        B, S = x.shape[:2]
        a = jnp.einsum("bse,ed->bsd",
                       o.reshape(B, S, cfg.n_heads * cfg.mla.v_dim),
                       p["attn"]["wo"].astype(x.dtype))
        kv = latent if want_kv else None
    else:
        q, k, v = attn._qkv(cfg, p["attn"], h, pos)
        o = attn.sdpa(cfg, q, k, v)
        B, S = x.shape[:2]
        a = jnp.einsum("bse,ed->bsd", o.reshape(B, S, cfg.q_dim),
                       p["attn"]["wo"].astype(x.dtype))
        kv = (k, v) if want_kv else None
    x = shard(x + a, "batch", "act_seq", "embed")
    h = apply_norm(cfg, x, _maybe(p, "ln2s"))
    y, aux = _block_mlp(cfg, p, h, decode=False)
    return shard(x + y, "batch", "act_seq", "embed"), kv, aux


def dense_block_decode(cfg, p, x, cache_l, pos):
    """One-token block.  cache_l: per-layer cache dict (write-through)."""
    h = apply_norm(cfg, x, _maybe(p, "ln1s"))
    if cfg.mla is not None:
        c_kv, k_rope = attn.mla_append_kv(cfg, p["attn"], h,
                                          cache_l["c_kv"],
                                          cache_l["k_rope"], pos)
        a = attn.mla_decode(cfg, p["attn"], h, c_kv, k_rope, pos)
        cache_l = {"c_kv": c_kv, "k_rope": k_rope}
    else:
        k_c, v_c = attn.append_kv(cfg, p["attn"], h, cache_l["k"],
                                  cache_l["v"], pos)
        a = attn.decode_attention(cfg, p["attn"], h, k_c, v_c, pos)
        cache_l = {"k": k_c, "v": v_c}
    x = x + a
    h = apply_norm(cfg, x, _maybe(p, "ln2s"))
    y, _ = _block_mlp(cfg, p, h, decode=True)
    return x + y, cache_l


# ---------------------------------------------------------------------------
# zamba2 hybrid block (mamba2 backbone + shared attention block)
# ---------------------------------------------------------------------------

def init_hybrid_shared(key, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), cfg.p_dtype),
            "ln2": jnp.zeros((cfg.d_model,), cfg.p_dtype),
            "attn": attn.init_attention(k1, cfg),
            "mlp": init_mlp(k2, cfg)}


def shared_attn_block(cfg, sp, x, pos, *, want_kv: bool):
    h = rmsnorm(x, sp["ln1"])
    q, k, v = attn._qkv(cfg, sp["attn"], h, pos)
    o = attn.sdpa(cfg, q, k, v)
    B, S = x.shape[:2]
    a = jnp.einsum("bse,ed->bsd", o.reshape(B, S, cfg.q_dim),
                   sp["attn"]["wo"].astype(x.dtype))
    x = x + a
    x = x + mlp(cfg, sp["mlp"], rmsnorm(x, sp["ln2"]))
    return x, ((k, v) if want_kv else None)


def shared_attn_decode(cfg, sp, x, k_c, v_c, pos):
    h = rmsnorm(x, sp["ln1"])
    k_c, v_c = attn.append_kv(cfg, sp["attn"], h, k_c, v_c, pos)
    a = attn.decode_attention(cfg, sp["attn"], h, k_c, v_c, pos)
    x = x + a
    x = x + mlp(cfg, sp["mlp"], rmsnorm(x, sp["ln2"]))
    return x, k_c, v_c


# ---------------------------------------------------------------------------
# Layer-stack drivers
# ---------------------------------------------------------------------------

def _stacked_init(init_one, key, cfg):
    keys = jax.random.split(key, cfg.n_layers)
    return jax.vmap(lambda k: init_one(k, cfg))(keys)


def _split_layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def build_model(cfg: ModelCfg, layer_mode: str = "scan") -> Model:
    if cfg.family == "rwkv6":
        return _build_rwkv(cfg, layer_mode)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg, layer_mode)
    return _build_dense(cfg, layer_mode)


def _positions(tokens):
    return jnp.arange(tokens.shape[1])


def _embed_in(cfg, params, tokens):
    x = embed(cfg, params["embed"], tokens)
    if cfg.pos == "sinusoidal":
        x = x + sinusoidal_pe(tokens.shape[1], cfg.d_model
                              ).astype(x.dtype)[None]
    return x


def _sinusoidal_at(pos, d_model, dtype):
    """Position-embedding rows at dynamic positions ``pos`` [B]."""
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    ang = pos[:, None].astype(jnp.float32) / jnp.power(1e4, dim / d_model)
    pe = jnp.zeros((pos.shape[0], d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe.astype(dtype)


# -- dense / moe -----------------------------------------------------------

def _build_dense(cfg: ModelCfg, layer_mode: str) -> Model:
    L = cfg.n_layers

    def init(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "embed": init_embed(k1, cfg),
            "layers": _stacked_init(init_dense_block, k2, cfg),
            "final_norm": (jnp.zeros((cfg.d_model,), cfg.p_dtype)
                           if cfg.norm == "rmsnorm" else jnp.zeros((0,))),
        }

    def _stack_forward(params, x, pos, want_kv):
        aux0 = jnp.float32(0.0)

        def body_fn(x, p_l):
            y, kv, aux = dense_block(cfg, p_l, x, pos, want_kv=want_kv)
            return y, kv, aux
        body_fn = _remat(cfg, body_fn)

        if layer_mode == "scan":
            def scan_body(carry, p_l):
                x, aux = carry
                y, kv, a = body_fn(x, p_l)
                return (y, aux + a), kv
            (x, aux), kvs = jax.lax.scan(scan_body, (x, aux0),
                                         params["layers"])
        else:
            aux, kvs_list = aux0, []
            for i in range(L):
                x, kv, a = body_fn(x, _split_layer(params["layers"], i))
                aux = aux + a
                kvs_list.append(kv)
            kvs = (jax.tree.map(lambda *xs: jnp.stack(xs), *kvs_list)
                   if want_kv else None)
        return x, kvs, aux

    def forward(params, tokens):
        x = _embed_in(cfg, params, tokens)
        x, _, aux = _stack_forward(params, x, _positions(tokens), False)
        x = apply_norm(cfg, x, params["final_norm"]
                       if cfg.norm == "rmsnorm" else None)
        return lm_logits(cfg, params["embed"], x), aux

    def loss(params, tokens, labels):
        logits, aux = forward(params, tokens)
        return softmax_xent(logits, labels) + aux

    def init_cache(batch, max_len):
        if cfg.mla is not None:
            return attn.init_mla_cache(cfg, batch, max_len)
        return attn.init_kv_cache(cfg, batch, max_len)

    def prefill(params, tokens, cache):
        S = tokens.shape[1]
        x = _embed_in(cfg, params, tokens)
        x, kvs, _ = _stack_forward(params, x, _positions(tokens), True)
        x = apply_norm(cfg, x, params["final_norm"]
                       if cfg.norm == "rmsnorm" else None)
        logits = lm_logits(cfg, params["embed"], x[:, -1:])
        if cfg.mla is not None:
            c_kv, k_rope = kvs
            cache = {
                "c_kv": jax.lax.dynamic_update_slice_in_dim(
                    cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, 2),
                "k_rope": jax.lax.dynamic_update_slice_in_dim(
                    cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                    0, 2),
            }
        else:
            k, v = kvs
            cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, 2),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, 2),
            }
        return logits, cache

    def decode_step(params, tok, cache, pos):
        x = embed(cfg, params["embed"], tok)
        if cfg.pos == "sinusoidal":
            x = x + _sinusoidal_at(pos, cfg.d_model, x.dtype)[:, None]

        def body_fn(x, p_l, cache_l):
            return dense_block_decode(cfg, p_l, x, cache_l, pos)

        if layer_mode == "scan":
            def scan_body(x, inp):
                p_l, cache_l = inp
                return body_fn(x, p_l, cache_l)
            x, cache = jax.lax.scan(scan_body, x, (params["layers"], cache))
        else:
            outs = []
            for i in range(L):
                x, c = body_fn(x, _split_layer(params["layers"], i),
                               _split_layer(cache, i))
                outs.append(c)
            cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        x = apply_norm(cfg, x, params["final_norm"]
                       if cfg.norm == "rmsnorm" else None)
        return lm_logits(cfg, params["embed"], x), cache

    return Model(cfg, init, forward, loss, init_cache, prefill, decode_step,
                 partial(_dense_cache_specs, cfg))


# -- rwkv6 ------------------------------------------------------------------

def _build_rwkv(cfg: ModelCfg, layer_mode: str) -> Model:
    L = cfg.n_layers

    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {"embed": init_embed(k1, cfg),
                "layers": _stacked_init(rk.init_rwkv_block, k2, cfg),
                "final_norm": jnp.zeros((cfg.d_model,), cfg.p_dtype)}

    def _run(params, x, state):
        def body_fn(x, p_l, st_l):
            return rk.rwkv_block(cfg, p_l, x, st_l,
                                 chunk=cfg.rwkv.chunk)
        body_fn = _remat(cfg, body_fn)
        if layer_mode == "scan":
            def scan_body(x, inp):
                p_l, st_l = inp
                return body_fn(x, p_l, st_l)
            x, state = jax.lax.scan(scan_body, x, (params["layers"], state))
        else:
            outs = []
            for i in range(L):
                x, st = body_fn(x, _split_layer(params["layers"], i),
                                _split_layer(state, i))
                outs.append(st)
            state = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return x, state

    def forward(params, tokens):
        x = _embed_in(cfg, params, tokens)
        st = rk.init_rwkv_state(cfg, tokens.shape[0])
        x, _ = _run(params, x, st)
        x = rmsnorm(x, params["final_norm"])
        return lm_logits(cfg, params["embed"], x), jnp.float32(0.0)

    def loss(params, tokens, labels):
        logits, _ = forward(params, tokens)
        return softmax_xent(logits, labels)

    def init_cache(batch, max_len):
        return rk.init_rwkv_state(cfg, batch)     # O(1) in max_len

    def prefill(params, tokens, cache):
        x = _embed_in(cfg, params, tokens)
        x, cache = _run(params, x, cache)
        x = rmsnorm(x[:, -1:], params["final_norm"])
        return lm_logits(cfg, params["embed"], x), cache

    def decode_step(params, tok, cache, pos):
        x = embed(cfg, params["embed"], tok)
        x, cache = _run(params, x, cache)
        x = rmsnorm(x, params["final_norm"])
        return lm_logits(cfg, params["embed"], x), cache

    return Model(cfg, init, forward, loss, init_cache, prefill, decode_step,
                 partial(_rwkv_cache_specs, cfg))


# -- zamba2 hybrid ----------------------------------------------------------

def _build_hybrid(cfg: ModelCfg, layer_mode: str) -> Model:
    L = cfg.n_layers
    every = cfg.hybrid_attn_every
    n_attn = L // every if every else 0

    def init(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"embed": init_embed(k1, cfg),
                "layers": _stacked_init(
                    lambda k, c: {"m": m2.init_mamba2(k, c),
                                  "ln": jnp.zeros((c.d_model,), c.p_dtype)},
                    k2, cfg),
                "shared": init_hybrid_shared(k3, cfg),
                "final_norm": jnp.zeros((cfg.d_model,), cfg.p_dtype)}

    def _layer(params, x, p_l, st_l, li, pos, attn_kv, want_kv):
        """One mamba layer (+ shared attn block every ``every`` layers).

        ``li`` may be a Python int (unrolled mode — the branch resolves at
        trace time, keeping the shared-attn FLOPs visible to XLA's cost
        analysis) or a traced index (scan mode — ``lax.cond``).
        """
        h = rmsnorm(x, p_l["ln"])
        y, st_out = m2.mamba2_block(cfg, p_l["m"], h, st_l)
        x = shard(x + y, "batch", "act_seq", "embed")
        if every:
            k_c, v_c = attn_kv

            def with_attn(x):
                xa, kv = shared_attn_block(cfg, params["shared"], x, pos,
                                           want_kv=want_kv)
                if want_kv:
                    ai = li // every
                    k2_ = jax.lax.dynamic_update_index_in_dim(
                        k_c, kv[0].astype(k_c.dtype), ai, 0)
                    v2_ = jax.lax.dynamic_update_index_in_dim(
                        v_c, kv[1].astype(v_c.dtype), ai, 0)
                    return xa, (k2_, v2_)
                return xa, (k_c, v_c)

            if isinstance(li, int):                    # unrolled: static
                if li % every == every - 1:
                    x, attn_kv = with_attn(x)
            else:
                x, attn_kv = jax.lax.cond(li % every == every - 1,
                                          with_attn,
                                          lambda x: (x, (k_c, v_c)), x)
        return x, st_out, attn_kv

    def _run(params, x, state, pos, want_kv, attn_cache):
        k_c, v_c = attn_cache
        if layer_mode == "scan":
            def scan_body(carry, inp):
                x, kcs = carry
                (p_l, st_l), li = inp
                x, st_out, kcs = _layer(params, x, p_l, st_l, li, pos,
                                        kcs, want_kv)
                return (x, kcs), st_out
            (x, (k_c, v_c)), state = jax.lax.scan(
                scan_body, (x, (k_c, v_c)),
                ((params["layers"], state), jnp.arange(L)))
        else:
            outs = []
            for i in range(L):
                x, st, (k_c, v_c) = _layer(
                    params, x, _split_layer(params["layers"], i),
                    _split_layer(state, i), i, pos,
                    (k_c, v_c), want_kv)
                outs.append(st)
            state = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return x, state, (k_c, v_c)

    def forward(params, tokens):
        B, S = tokens.shape
        x = _embed_in(cfg, params, tokens)
        st = m2.init_mamba_state(cfg, B)
        kv_shape = (n_attn, B, S, cfg.n_kv_heads, cfg.head_dim)
        dummy = (jnp.zeros(kv_shape, cfg.act_dtype),
                 jnp.zeros(kv_shape, cfg.act_dtype))
        x, _, _ = _run(params, x, st, _positions(tokens), False, dummy)
        x = rmsnorm(x, params["final_norm"])
        return lm_logits(cfg, params["embed"], x), jnp.float32(0.0)

    def loss(params, tokens, labels):
        logits, _ = forward(params, tokens)
        return softmax_xent(logits, labels)

    def init_cache(batch, max_len):
        c = m2.init_mamba_state(cfg, batch)
        c["attn_k"] = jnp.zeros(
            (n_attn, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
            cfg.act_dtype)
        c["attn_v"] = jnp.zeros_like(c["attn_k"])
        return c

    def prefill(params, tokens, cache):
        B, S = tokens.shape
        x = _embed_in(cfg, params, tokens)
        st = {"conv": cache["conv"], "ssm": cache["ssm"]}
        kv_shape = (n_attn, B, S, cfg.n_kv_heads, cfg.head_dim)
        fresh = (jnp.zeros(kv_shape, cfg.act_dtype),
                 jnp.zeros(kv_shape, cfg.act_dtype))
        x, st, (k_c, v_c) = _run(params, x, st, _positions(tokens), True,
                                 fresh)
        new_cache = {
            "conv": st["conv"], "ssm": st["ssm"],
            "attn_k": jax.lax.dynamic_update_slice_in_dim(
                cache["attn_k"], k_c, 0, 2),
            "attn_v": jax.lax.dynamic_update_slice_in_dim(
                cache["attn_v"], v_c, 0, 2),
        }
        x = rmsnorm(x[:, -1:], params["final_norm"])
        return lm_logits(cfg, params["embed"], x), new_cache

    def decode_step(params, tok, cache, pos):
        B = tok.shape[0]
        x = embed(cfg, params["embed"], tok)
        st = {"conv": cache["conv"], "ssm": cache["ssm"]}

        def _layer_d(carry, inp):
            x, k_c, v_c = carry
            (p_l, st_l), li = inp
            h = rmsnorm(x, p_l["ln"])
            y, st_out = m2.mamba2_block(cfg, p_l["m"], h, st_l)
            x = x + y

            def with_attn(args):
                x, k_c, v_c = args
                ai = li // every
                xa, k_l, v_l = shared_attn_decode(
                    cfg, params["shared"], x, k_c[ai], v_c[ai], pos)
                k_c = jax.lax.dynamic_update_index_in_dim(k_c, k_l, ai, 0)
                v_c = jax.lax.dynamic_update_index_in_dim(v_c, v_l, ai, 0)
                return xa, k_c, v_c

            if every:
                if isinstance(li, int):                # unrolled: static
                    if li % every == every - 1:
                        x, k_c, v_c = with_attn((x, k_c, v_c))
                else:
                    x, k_c, v_c = jax.lax.cond(
                        li % every == every - 1, with_attn,
                        lambda a: a, (x, k_c, v_c))
            return (x, k_c, v_c), st_out

        if layer_mode == "scan":
            (x, k_c, v_c), st = jax.lax.scan(
                _layer_d, (x, cache["attn_k"], cache["attn_v"]),
                ((params["layers"], st), jnp.arange(L)))
        else:
            k_c, v_c = cache["attn_k"], cache["attn_v"]
            outs = []
            for i in range(L):
                (x, k_c, v_c), st_out = _layer_d(
                    (x, k_c, v_c),
                    ((_split_layer(params["layers"], i),
                      _split_layer(st, i)), i))
                outs.append(st_out)
            st = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        cache = {"conv": st["conv"], "ssm": st["ssm"],
                 "attn_k": k_c, "attn_v": v_c}
        x = rmsnorm(x, params["final_norm"])
        return lm_logits(cfg, params["embed"], x), cache

    return Model(cfg, init, forward, loss, init_cache, prefill, decode_step,
                 partial(_hybrid_cache_specs, cfg))


# ---------------------------------------------------------------------------
# Decode-cache PartitionSpecs (logical → physical via active rules)
# ---------------------------------------------------------------------------

def _dense_cache_specs(cfg, batch=None, max_len=None):
    """Decode-cache shardings, divisibility-aware.

    When the arch's kv heads divide the TP degree, shard them; otherwise
    shard the cache *sequence* dim over the model axis instead (decode
    attention then executes as a flash-decode: per-shard partial softmax
    merged by GSPMD's reduction).  MLA's latent cache has no head dim —
    it always seq-shards.  ``seq_kv`` (data axis) is added when the
    rules map it.
    """
    from repro.distribution.sharding import axis_size, phys
    if cfg.mla is not None:
        seq = phys("seq_kv", "seq_kv_tp")
        return {"c_kv": P(None, *pspec("batch"), seq, None),
                "k_rope": P(None, *pspec("batch"), seq, None)}
    kv_ok = (cfg.shard_heads
             and cfg.n_kv_heads % max(axis_size("kv_heads"), 1) == 0
             and axis_size("kv_heads") > 1)
    if kv_ok:
        seq = phys("seq_kv")
        kv = phys("kv_heads")
    else:
        seq = phys("seq_kv", "seq_kv_tp")
        kv = None
    b = phys("batch")
    return {"k": P(None, b, seq, kv, None),
            "v": P(None, b, seq, kv, None)}


def _rwkv_cache_specs(cfg, batch=None, max_len=None):
    from repro.distribution.sharding import axis_size, phys
    H = cfg.d_model // cfg.rwkv.head_size
    h_ok = H % max(axis_size("heads"), 1) == 0
    b = phys("batch")
    return {"tm_shift": P(None, b, None),
            "cm_shift": P(None, b, None),
            "wkv": P(None, b, "model" if h_ok and axis_size("heads") > 1
                     else None, None, None)}


def _hybrid_cache_specs(cfg, batch=None, max_len=None):
    from repro.distribution.sharding import axis_size, phys
    b = phys("batch")
    ssm_h = (cfg.ssm.expand * cfg.d_model) // cfg.ssm.head_dim
    h_ok = ssm_h % max(axis_size("heads"), 1) == 0
    kv_ok = (cfg.n_kv_heads % max(axis_size("kv_heads"), 1) == 0
             and axis_size("kv_heads") > 1)
    seq = phys("seq_kv") if kv_ok else phys("seq_kv", "seq_kv_tp")
    return {"conv": P(None, b, None, None),
            "ssm": P(None, b, "model" if h_ok and axis_size("heads") > 1
                     else None, None, None),
            "attn_k": P(None, b, seq, phys("kv_heads") if kv_ok else None,
                        None),
            "attn_v": P(None, b, seq, phys("kv_heads") if kv_ok else None,
                        None)}
