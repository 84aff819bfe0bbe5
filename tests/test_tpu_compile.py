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
import re

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


# (layers, K, N): each projection as a stack of one, and the SSD
# in-projection stacks of mamba2-1.3b (8576: 8512 padded to 128s) and
# granite-4.0-h-small (16768), read in place with a ragged last column block
STACKS = [pytest.param(1, k, n, id=f"{k}-{n}") for k, n in PROJ] + [
    pytest.param(48, 2048, 8576, id="48x2048-8576"),
    pytest.param(36, 4096, 16768, id="36x4096-16768")]


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("layers,k,n", STACKS)
@pytest.mark.parametrize("mode", ["w8a16", "w8a8"])
def test_qmatmul_compiles(one_chip, mode, layers, k, n, m):
    x_dtype = "int8" if mode == "w8a8" else "bf16"
    tc = AT.best_config(m, k, n, mode=mode, x_dtype="bf16",
                        out_dtype="bf16", has_bias=True,
                        cache=AT.AutotuneCache(""))
    assert k % tc.bk == 0, tc
    mp = -(-m // tc.bm) * tc.bm
    layer = jnp.int32(layers - 1)
    if mode == "w8a8":
        fn = lambda x, w, ws, b: QM.qmatmul_w8a8(
            x, w, jnp.ones((), jnp.float32), ws, layer, b,
            out_dtype=jnp.bfloat16, **tc.as_kwargs())
    else:
        fn = lambda x, w, ws, b: QM.qmatmul_w8a16(
            x, w, ws, layer, b, out_dtype=jnp.bfloat16, **tc.as_kwargs())
    text = _compile(one_chip, fn,
                    ((mp, k), jnp.int8 if x_dtype == "int8" else jnp.bfloat16),
                    ((layers, k, n), jnp.int8), ((layers, 1, n), jnp.float32),
                    ((n,), jnp.float32))
    assert f"%qmatmul_{mode}" in text
    assert not _int8_weight_copies(text, [(layers, k, n)])


def _int8_shapes(params):
    return [x.shape for x in jax.tree_util.tree_leaves(params)
            if x.dtype == jnp.int8]


def _int8_weight_copies(text, weights):
    """The ops of a compiled program (outside fusion bodies) that write an
    int8 array shaped like a weight: a copy, pad, slice or dynamic slice,
    or a fusion named for one, whose result is a whole stack of
    ``weights`` ((L, K, N) shapes) or one layer's (K, N), padded or not."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    kn = {tuple(w[-2:]) for w in weights}
    found, comp = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = s8\[([\d,]*)\]\S* "
                      r"([\w-]+)\(", line)
        if not op or comp in fused:
            continue
        name, dims, opcode = op.group(1), op.group(2), op.group(3)
        if not (opcode in ("copy", "pad", "slice", "dynamic-slice")
                or (opcode == "fusion" and "slice" in name)):
            continue
        dims = tuple(int(d) for d in dims.split(",") if d)
        if any(dims[-2] == k and n <= dims[-1] < n + 512 for k, n in kn
               if len(dims) >= 2):
            found.append(name)
    return found


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


def test_ssm_slot_step_updates_state_in_place(one_chip, monkeypatch):
    """The mamba2-1.3b slot step at published widths (32 slots, W8A16,
    depth cut to 4): the state kernel is in it, the float32 state is
    updated in the donated buffer, no temporary holds as much as one
    layer's state, and the int8 linears read their layer stacks in place:
    no weight is sliced, relaid out or padded (the in-projection is
    stored at 8576 columns, 8512 padded to a multiple of 128)."""
    from repro.core.qlinear import W8A16
    from repro.kernels import ops
    from repro.models import registry as R
    from repro.runtime import steps as ST

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("mamba2-1.3b"), n_layers=4)
    slots = 32
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(lambda k: R.init_quantized(k, cfg),
                                jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(lambda: R.init_cache(cfg, slots, 32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    step = jax.jit(ST.make_slot_decode_step(cfg, mode=W8A16),
                   donate_argnums=(2,))
    compiled = step.lower(params, i32(slots, 1), cache, i32(slots),
                          jax.ShapeDtypeStruct((slots,), bool,
                                               sharding=one_chip)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "%ssm_state_update" in ln]
    assert calls, "the state kernel is not in the compiled step"
    assert params["layers"]["in_proj"]["w"].shape == (4, 2048, 8576)
    assert "%qmatmul_w8a16" in text
    assert not _int8_weight_copies(text, _int8_shapes(params))
    nbytes = lambda x: x.size * x.dtype.itemsize
    layer_state = nbytes(cache["h"]) // cfg.n_layers        # 67 MB
    cache_bytes = sum(nbytes(x) for x in cache.values())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_state
    assert mem.alias_size_in_bytes >= cache_bytes


GRANITE = get_config("granite-4.0-h-small")


@pytest.mark.parametrize("k,n,per_expert", [(4096, 1536, False),
                                            (768, 4096, True)])
def test_moe_w8a16_compiles(one_chip, k, n, per_expert):
    """The held-expert kernel at granite-4.0-h-small's widths: 9 experts
    of one layer of a 40-layer stack, 16 rows (the slots), the
    up-projection (rows shared by every expert) and the down-projection
    (rows per expert)."""
    from repro.kernels import moe_w8a16 as MW
    from repro.kernels import ops
    e, m = GRANITE.n_held, 16
    x = ((e, m, k) if per_expert else (m, k), jnp.bfloat16)
    text = _compile(one_chip, lambda x, w, s, c: MW.moe_w8a16(
        x, w, s, jnp.int32(3), c, bm=m, bn=ops._lane_block(n),
        bk=ops._lane_block(k)),
        x, ((GRANITE.n_layers, e, k, n), jnp.int8),
        ((GRANITE.n_layers, e, 1, n), jnp.float32), ((e,), jnp.int32))
    assert "moe_w8a16" in text


def test_granite_slot_step_reads_int8_experts_in_place(one_chip, monkeypatch):
    """granite-4.0-h-small's slot step at published widths and 16 slots,
    W8A16, depth cut to one period of the layer pattern (9 Mamba layers
    and one attention layer, each with its MoE block): the held-expert
    and state kernels are in it, no bf16 or float32 array of a held
    expert's weight shape exists in it (the kernel converts int8 tiles in
    VMEM), its temporaries stay under two layers' state (71 MB here,
    259 MB at all 40 layers): no copy of the state or of a weight
    stack, and the shared int8 linears (Mamba and attention projections,
    shared MLP) read their layer stacks in place: no weight is sliced or
    padded."""
    from repro.core.qlinear import W8A16
    from repro.kernels import ops
    from repro.models import registry as R
    from repro.runtime import steps as ST

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(GRANITE, n_layers=10,
                              layer_types=GRANITE.layer_types[:10])
    slots = 16
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(lambda k: R.init_quantized(k, cfg),
                                jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(lambda: R.init_cache(cfg, slots, 512)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    step = jax.jit(ST.make_slot_decode_step(cfg, mode=W8A16),
                   donate_argnums=(2,))
    compiled = step.lower(params, i32(slots, 1), cache, i32(slots),
                          jax.ShapeDtypeStruct((slots,), bool,
                                               sharding=one_chip)).compile()
    text = compiled.as_text()
    calls = lambda name: [ln for ln in text.splitlines()
                          if 'custom_call_target="tpu_custom_call"' in ln
                          and f"%{name}" in ln]
    assert calls("moe_w8a16") and calls("ssm_state_update")
    assert calls("qmatmul_w8a16")
    assert not _int8_weight_copies(text, _int8_shapes(params))
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held
    for dims in ((e, d, 2 * f), (d, 2 * f), (e, f, d), (f, d)):
        shape = ",".join(map(str, dims))
        for dt in ("bf16", "f32"):
            assert f"{dt}[{shape}]" not in text, (dt, shape)
    nbytes = lambda x: x.size * x.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * nbytes(cache["h"]) // 9  # 134 MB
    assert mem.alias_size_in_bytes >= sum(nbytes(x) for x in cache.values())
