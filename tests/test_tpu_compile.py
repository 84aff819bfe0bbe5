"""The main-path Pallas kernels compile for a TPU v5e at starcoder2-3b's
widths — a chip that is described, not attached, so this runs on the CPU.

Interpret mode accepts block shapes the TPU lowering refuses (a block
that slices one KV head out of (S, KV, hd) did, for KV > 1), so each
kernel is compiled here exactly as the chip's compiler would see it.  The
topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this module.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import autotune as AT
from repro.kernels import decode_attention as DA
from repro.kernels import flash_attention as FA
from repro.kernels import qmatmul as QM

CFG = get_config("starcoder2-3b")
D, F = CFG.d_model, CFG.d_ff
KV, HD = CFG.n_kv_heads, CFG.head_dim
G = CFG.n_heads // KV
# the projections' (K, N): wq/wo, wk/wv, w_up, w_down
PROJ = [(D, CFG.n_heads * HD), (D, KV * HD), (D, F), (F, D)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("k,n", PROJ)
@pytest.mark.parametrize("mode", ["w8a16", "w8a8"])
def test_qmatmul_compiles(one_chip, mode, k, n, m):
    x_dtype = "int8" if mode == "w8a8" else "bf16"
    tc = AT.best_config(m, k, n, mode=mode, x_dtype="bf16",
                        out_dtype="bf16", has_bias=True,
                        cache=AT.AutotuneCache(""))
    mp, kp, np_ = (-(-m // tc.bm) * tc.bm, -(-k // tc.bk) * tc.bk,
                   -(-n // tc.bn) * tc.bn)
    if mode == "w8a8":
        fn = lambda x, w, ws, b: QM.qmatmul_w8a8(
            x, w, jnp.ones((), jnp.float32), ws, b, out_dtype=jnp.bfloat16,
            **tc.as_kwargs())
    else:
        fn = lambda x, w, ws, b: QM.qmatmul_w8a16(
            x, w, ws, b, out_dtype=jnp.bfloat16, **tc.as_kwargs())
    _compile(one_chip, fn,
             ((mp, kp), jnp.int8 if x_dtype == "int8" else jnp.bfloat16),
             ((kp, np_), jnp.int8), ((np_,), jnp.float32),
             ((np_,), jnp.float32))


B, S, BS = 8, 256, 16
Q = ((B, KV, 16, HD), jnp.bfloat16)          # G=12 padded to bf16's 16
CACHE = ((B, S, KV, HD), jnp.int8)
SCALE = ((B, S, KV), jnp.float32)
VL = ((B,), jnp.int32)
NEW = ((B, 1, KV, HD), jnp.float32)


def test_decode_attention_contiguous_compiles(one_chip):
    _compile(one_chip, lambda q, k, ks, v, vs, vl: DA.decode_attention_int8(
        q, k, ks, v, vs, vl, sm_scale=HD ** -0.5),
        Q, CACHE, SCALE, CACHE, SCALE, VL)


def test_decode_attention_append_compiles(one_chip):
    _compile(one_chip, lambda q, k, ks, v, vs, vl, kn, vn:
             DA.decode_attention_int8(q, k, ks, v, vs, vl, kn, vn,
                                      sm_scale=HD ** -0.5),
             Q, CACHE, SCALE, CACHE, SCALE, VL, NEW, NEW)


def test_decode_attention_paged_compiles(one_chip):
    nb = B * S // BS + 1
    blocks = ((nb, BS, KV, HD), jnp.int8)
    scales = ((nb, BS, KV), jnp.float32)
    _compile(one_chip, lambda q, k, ks, v, vs, vl, t, kn, vn:
             DA.decode_attention_int8_paged(q, k, ks, v, vs, vl, t, kn, vn,
                                            sm_scale=HD ** -0.5),
             Q, blocks, scales, blocks, scales, VL,
             ((B, S // BS), jnp.int32), NEW, NEW)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles(one_chip, dtype):
    bh, sq = CFG.n_heads, 512
    qkv = ((bh, sq, HD), dtype)
    _compile(one_chip, lambda q, k, v: FA.flash_attention_bhsd(
        q, k, v, causal=True, out_dtype=dtype), qkv, qkv, qkv)


def test_int8_kv_slot_step_compiles_and_fits(one_chip, monkeypatch):
    """The whole paged int8-KV slot step at full width, for one chip: the
    fused decode kernel is in it and the program fits 16 GB."""
    from repro.core.qlinear import W8A16
    from repro.kernels import ops
    from repro.models import registry as R
    from repro.runtime import steps as ST

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(CFG, kv_quant=True)
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(lambda k: R.init_quantized(k, cfg),
                                jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(
        lambda: R.init_paged_cache(cfg, 8, 32, 16, 17)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    step = jax.jit(ST.make_slot_decode_step(cfg, mode=W8A16),
                   donate_argnums=(2,))
    compiled = step.lower(params, i32(8, 1), cache, i32(8),
                          jax.ShapeDtypeStruct((8,), bool,
                                               sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
