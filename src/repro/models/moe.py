"""Mixture-of-Experts: router, dense oracle, and expert-parallel dispatch.

Three compute paths:

* ``moe_dense`` — dropless oracle: every (token, expert) pair is computed
  and masked by the combine weights.  Exact; used by smoke tests, as the
  reference for the EP path, and for *decode* steps (token count per
  device ≪ expert count, so dense-local + psum is both exact and cheap —
  expert weights stay sharded, XLA reduces partial sums over the model
  axis).
* ``moe_ep`` — production path for train/prefill: per-device top-k
  routing, capacity-bounded sort-based dispatch into an ``[E, C, D]``
  buffer, ``all_to_all`` over the model (expert) axis, batched expert
  FFN, reverse ``all_to_all``, weighted combine.  Tokens over capacity
  are dropped (standard GShard/Switch semantics; capacity_factor controls
  the drop rate).
* shared experts (DeepSeek-V2) are a plain dense MLP added to the output.

Router losses: Switch-style load-balance aux (``E·Σ f_e·P_e``) and z-loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import current_ctx, shard
from repro.models.layers import dense_init


def init_moe(key, cfg) -> dict:
    e, D = cfg.moe, cfg.d_model
    F = e.d_ff_expert
    dt = cfg.p_dtype
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (D, e.n_experts), dt),
        "w_gate": dense_init(ks[1], (e.n_experts, D, F), dt, in_axis=-2),
        "w_in": dense_init(ks[2], (e.n_experts, D, F), dt, in_axis=-2),
        "w_out": dense_init(ks[3], (e.n_experts, F, D), dt, in_axis=-2),
    }
    if e.n_shared > 0:
        Fs = e.n_shared * F
        p["shared"] = {
            "w_gate": dense_init(ks[4], (D, Fs), dt),
            "w_in": dense_init(ks[5], (D, Fs), dt),
            "w_out": dense_init(ks[6], (Fs, D), dt),
        }
    return p


def _act(cfg, g, h):
    a = jax.nn.silu(g) if cfg.mlp != "geglu" else jax.nn.gelu(g, True)
    return a * h


def _router(cfg, p, xf):
    """xf: [T, D] → gates [T,k], idx [T,k] i32, aux losses (f32 scalars)."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: fraction routed vs mean prob (Switch eq. 4-6)
    one_hot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.float32)
    f = one_hot.sum(axis=(0, 1)) / (xf.shape[0] * e.top_k)
    pmean = probs.mean(axis=0)
    aux = e.n_experts * jnp.sum(f * pmean) * e.aux_coef
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * e.router_z_coef
    return gates.astype(xf.dtype), idx, aux + z


def _shared_mlp(cfg, p, x):
    dt = x.dtype
    sp = p["shared"]
    g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dt))
    h = jnp.einsum("bsd,df->bsf", x, sp["w_in"].astype(dt))
    g = shard(g, "batch", "seq", "ff")
    h = shard(h, "batch", "seq", "ff")
    return jnp.einsum("bsf,fd->bsd", _act(cfg, g, h), sp["w_out"].astype(dt))


# ---------------------------------------------------------------------------
# Dense (oracle / decode) path — pure SPMD, no shard_map
# ---------------------------------------------------------------------------

def moe_dense(cfg, p, x):
    """x: [B,S,D].  Every expert computed for every token, masked combine.

    With expert weights sharded over the model axis, GSPMD computes the
    per-shard partial sums and inserts one all-reduce — this is exactly
    dense-local expert parallelism.  Cost/token = E_local experts, which
    is the right trade for decode (T per device small); the EP path below
    is the train/prefill fast path.
    """
    B, S, D = x.shape
    e = cfg.moe
    xf = x.reshape(B * S, D)
    gates, idx, aux = _router(cfg, p, xf)
    # combine weights [T, E]
    comb = jnp.zeros((B * S, e.n_experts), x.dtype)
    comb = comb.at[jnp.arange(B * S)[:, None], idx].add(gates)
    dt = x.dtype
    g = jnp.einsum("td,edf->etf", xf, p["w_gate"].astype(dt))
    h = jnp.einsum("td,edf->etf", xf, p["w_in"].astype(dt))
    hh = _act(cfg, g, h) * comb.T[:, :, None]
    y = jnp.einsum("etf,efd->td", hh, p["w_out"].astype(dt))
    y = y.reshape(B, S, D)
    if e.n_shared > 0:
        y = y + _shared_mlp(cfg, p, x)
    return shard(y, "batch", "seq", "embed"), aux


# ---------------------------------------------------------------------------
# Expert-parallel (sorted dispatch + all_to_all) path
# ---------------------------------------------------------------------------

def _capacity(t_local: int, cfg) -> int:
    e = cfg.moe
    c = int(math.ceil(t_local * e.top_k / e.n_experts * e.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def moe_ep(cfg, p, x):
    """Expert-parallel MoE for many-token steps (train / prefill).

    Requires an active sharding context; falls back to the dense oracle
    otherwise (tests, single-device runs).
    """
    ctx = current_ctx()
    if ctx is None:
        return moe_dense(cfg, p, x)
    B, S, D = x.shape
    e = cfg.moe
    tp = ctx.tp_axis
    M = ctx.mesh.shape[tp]
    dp = ctx.rules.get("batch")
    fsdp = ctx.rules.get("fsdp")
    if S % M != 0 or e.n_experts % M != 0:
        return moe_dense(cfg, p, x)
    E_l = e.n_experts // M

    def local(xl, wr, wg, wi, wo):
        # xl: [B_l, S_l, D]; wr: [D,E]; wg/wi: [E_l, D', F]; wo: [E_l, F, D']
        if fsdp is not None:  # FSDP: gather the layer's weights before use
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wi = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp, axis=2, tiled=True)
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, D)
        gates, idx, aux = _router(cfg, {"router": wr}, xf)
        dp_axes = (dp if isinstance(dp, tuple) else
                   ((dp,) if dp is not None else ()))
        aux = jax.lax.pmean(aux, (*dp_axes, tp))
        C = _capacity(T, cfg)
        A = T * e.top_k
        e_flat = idx.reshape(A)
        t_flat = jnp.repeat(jnp.arange(T), e.top_k)
        g_flat = gates.reshape(A)
        order = jnp.argsort(e_flat)                      # stable
        e_s, t_s, g_s = e_flat[order], t_flat[order], g_flat[order]
        starts = jnp.searchsorted(e_s, jnp.arange(e.n_experts))
        pos = jnp.arange(A) - starts[e_s]
        keep = pos < C
        pos_c = jnp.where(keep, pos, 0)
        buf = jnp.zeros((e.n_experts, C, D), xl.dtype)
        src = jnp.where(keep[:, None], xf[t_s], 0)
        buf = buf.at[e_s, pos_c].add(src)
        # dispatch: every device sends C slots of each expert to its owner
        recv = jax.lax.all_to_all(buf, tp, split_axis=0, concat_axis=1,
                                  tiled=True)            # [E_l, M*C, D]
        dt = xl.dtype
        g1 = jnp.einsum("ecd,edf->ecf", recv, wg.astype(dt))
        h1 = jnp.einsum("ecd,edf->ecf", recv, wi.astype(dt))
        y = jnp.einsum("ecf,efd->ecd", _act(cfg, g1, h1), wo.astype(dt))
        back = jax.lax.all_to_all(y, tp, split_axis=1, concat_axis=0,
                                  tiled=True)            # [E, C, D]
        contrib = back[e_s, pos_c] * keep[:, None]
        out = jnp.zeros((T, D), xl.dtype)
        out = out.at[t_s].add(g_s[:, None] * contrib)
        return out.reshape(Bl, Sl, D), aux

    wspec_df = P(tp, fsdp, None)   # [E, D, F] experts over model (+fsdp on D)
    wspec_fd = P(tp, None, fsdp)
    y, aux = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(dp, tp, None), P(None, None),
                  wspec_df, wspec_df, wspec_fd),
        out_specs=(P(dp, tp, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    if e.n_shared > 0:
        y = y + _shared_mlp(cfg, p, x)
    return shard(y, "batch", "seq", "embed"), aux


def moe(cfg, p, x, *, decode: bool = False):
    """Dispatch: dense-local for decode / tiny token counts, EP otherwise."""
    if decode or x.shape[0] * x.shape[1] < 4 * cfg.moe.n_experts:
        return moe_dense(cfg, p, x)
    return moe_ep(cfg, p, x)
