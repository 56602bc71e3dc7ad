"""Sharded-execution tests (subprocesses with fake host devices, so the
main pytest process keeps its single CPU device)."""
import pytest

pytestmark = pytest.mark.slow


COMMON = """
import os, sys
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro import configs
from repro.distribution.sharding import ShardCtx, make_rules, sharding_ctx
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import build_model

def get_f32(name):
    # f32 so sharded-vs-single comparisons test *math*, not bf16
    # reduction-order noise
    return dataclasses.replace(configs.get_smoke(name), dtype="float32")
"""


def test_moe_ep_matches_dense_on_mesh(devices_script):
    out = devices_script(COMMON + """
import dataclasses
from repro.models import moe as moe_mod
from repro.models.common import MoECfg

cfg = dataclasses.replace(
    configs.get_smoke("dbrx-132b"),
    moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=64, capacity_factor=16.0))
p = moe_mod.init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)
y_ref, aux_ref = moe_mod.moe_dense(cfg, p, x)

mesh = make_test_mesh((2, 2), ("data", "model"))
ctx = ShardCtx(mesh=mesh, rules=make_rules())
with sharding_ctx(ctx):
    y, aux = jax.jit(lambda p, x: moe_mod.moe_ep(cfg, p, x))(p, x)
np.testing.assert_allclose(np.asarray(y, np.float32),
                           np.asarray(y_ref, np.float32),
                           rtol=2e-2, atol=2e-2)
print("aux", float(aux), float(aux_ref))
assert abs(float(aux) - float(aux_ref)) < 1e-3
print("OK")
""", n_devices=4)
    assert "OK" in out


def test_seq_sharded_decode_cache_matches(devices_script):
    """Flash-decode: cache sequence dim sharded over model axis."""
    out = devices_script(COMMON + """
cfg = get_f32("qwen3-14b")      # kv=2 heads < tp → seq-sharded
model = build_model(cfg)
params = model.init(jax.random.key(0))
B, S = 2, 32
toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
cache = model.init_cache(B, S + 4)
lg_ref, cache_ref = jax.jit(model.prefill)(params, toks, cache)
pos = jnp.full((B,), S, jnp.int32)
dec_ref, _ = jax.jit(model.decode_step)(params, toks[:, :1], cache_ref, pos)

mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = ShardCtx(mesh=mesh, rules=make_rules())
with sharding_ctx(ctx):
    model_s = build_model(cfg)
    cspec = model_s.cache_specs(B, S + 4)
    # qwen smoke: kv_heads=2 does not divide model=4 → seq-sharded cache
    assert cspec["k"][2] is not None, cspec
    csh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspec,
                       is_leaf=lambda s: isinstance(s, P))
    cache_s = jax.tree.map(jax.device_put, cache_ref, csh)
    dec_s, _ = jax.jit(model_s.decode_step)(params, toks[:, :1], cache_s,
                                            pos)
np.testing.assert_allclose(np.asarray(dec_ref, np.float32),
                           np.asarray(dec_s, np.float32),
                           rtol=1e-3, atol=1e-3)
print("OK")
""", n_devices=8)
    assert "OK" in out
