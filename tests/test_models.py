"""Per-architecture smoke tests (reduced configs) + model-level oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.core.qlinear import FP, W8A16, W8A8
from repro.core.quant import quantize_tree
from repro.models import registry as R

ARCHS = list_archs()
KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=16):
    batch = {"tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["encoder_embeds"] = jax.random.normal(
            KEY, (b, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            KEY, (b, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = get_config(arch).reduced()
        params = R.init(KEY, cfg)
        batch = _batch(cfg)
        logits = R.apply_forward(params, cfg, batch)
        assert logits.shape == (2, 16, cfg.vocab)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    def test_one_train_step(self, arch):
        from repro.optim import make_optimizer
        from repro.runtime import steps as ST
        cfg = get_config(arch).reduced()
        params = R.init(KEY, cfg)
        opt = make_optimizer("adamw", lr=1e-3)
        opt_state = opt.init(params)
        batch = _batch(cfg)
        batch["labels"] = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
        step = ST.make_train_step(cfg, opt)
        new_params, _, metrics = step(params, opt_state, batch, KEY)
        assert bool(jnp.isfinite(metrics["loss"]))
        # params actually changed
        delta = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), params, new_params)
        assert max(jax.tree_util.tree_leaves(delta)) > 0

    def test_decode_step(self, arch):
        cfg = get_config(arch).reduced()
        params = R.init(KEY, cfg)
        batch = _batch(cfg)
        cache = R.init_cache(cfg, 2, 64)
        m = R.module_for(cfg)
        if cfg.family == "encdec":
            cache = m.prime_cache(params, cache, batch["encoder_embeds"],
                                  cfg)
        if cfg.family == "vlm":
            cache = m.prime_cache(params, cache, batch["vision_embeds"], cfg)
        d = {"tokens": batch["tokens"][:, :1], "cache_index": jnp.array(0)}
        logits, new_cache = R.apply_decode(params, cfg, d, cache)
        assert logits.shape == (2, 1, cfg.vocab)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    def test_quantized_forward_close_to_fp(self, arch):
        cfg = get_config(arch).reduced()
        params = R.init(KEY, cfg)
        batch = _batch(cfg)
        fp = R.apply_forward(params, cfg, batch).astype(jnp.float32)
        qp = quantize_tree(params, min_size=2048)
        q = R.apply_forward(qp, cfg, batch, mode=W8A16).astype(jnp.float32)
        rel = float(jnp.linalg.norm(q - fp) / (jnp.linalg.norm(fp) + 1e-9))
        assert rel < 0.15, f"{arch}: quantized deviates {rel:.3f}"


@pytest.mark.parametrize("arch", ["starcoder2-3b", "mixtral-8x22b",
                                  "recurrentgemma-9b", "mamba2-1.3b",
                                  "whisper-medium"])
def test_decode_matches_forward(arch):
    """Stepwise decode must reproduce the teacher-forced forward logits."""
    cfg = get_config(arch).reduced()
    params = R.init(KEY, cfg)
    batch = _batch(cfg, b=2, s=8)
    ref = R.apply_forward(params, cfg, batch).astype(jnp.float32)
    cache = R.init_cache(cfg, 2, 32)
    m = R.module_for(cfg)
    if cfg.family == "encdec":
        cache = m.prime_cache(params, cache, batch["encoder_embeds"], cfg)
    outs = []
    for i in range(8):
        d = {"tokens": batch["tokens"][:, i:i + 1],
             "cache_index": jnp.array(i)}
        lg, cache = R.apply_decode(params, cfg, d, cache)
        outs.append(lg[:, 0].astype(jnp.float32))
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    err = float(jnp.max(jnp.abs(dec - ref))) / scale
    assert err < 0.05, f"{arch}: decode/forward relative gap {err:.4f}"


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("arch", ["mamba2-1.3b", "granite-4.0-h-small"])
def test_in_proj_padding_is_idempotent_and_changes_no_logit(arch,
                                                            interpret):
    """The served layout pads the int8 SSD in-projection (552 columns at
    the reduced widths) with zero columns to 640, once: a second pass
    leaves it as it is, and decode logits with and without the padding
    are equal, on the CPU oracle and through the kernel body."""
    from repro.core.qlinear import QuantMode
    cfg = get_config(arch).reduced()
    params = quantize_tree(R.init(KEY, cfg), min_size=2048)
    padded = R.serving_params(cfg, params)
    block = "layers" if cfg.family == "ssm" else "mamba"
    w, wp = params[block]["in_proj"]["w"], padded[block]["in_proj"]["w"]
    width = 2 * cfg.d_inner + 2 * cfg.ssm_state + cfg.ssm_heads
    assert w.shape[-1] == width == 552 and wp.shape[-1] == 640
    np.testing.assert_array_equal(np.asarray(wp.values[..., width:]), 0)
    np.testing.assert_array_equal(np.asarray(wp.values[..., :width]),
                                  np.asarray(w.values))
    again = R.serving_params(cfg, padded)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(again),
                                      jax.tree_util.tree_leaves(padded)))
    mode = QuantMode(enabled=True, act_bits=16, interpret=interpret)
    tokens = jax.random.randint(KEY, (2, 3), 0, cfg.vocab)
    caches = [R.init_cache(cfg, 2, 16), R.init_cache(cfg, 2, 16)]
    for i in range(3):
        d = {"tokens": tokens[:, i:i + 1], "cache_index": jnp.array(i)}
        outs = []
        for j, p in enumerate((params, padded)):
            lg, caches[j] = R.apply_decode(p, cfg, d, caches[j], mode=mode)
            outs.append(np.asarray(lg))
        np.testing.assert_array_equal(outs[0], outs[1])


class TestSSDOracle:
    """The chunked SSD algorithm vs a naive sequential recurrence."""

    def _naive_ssd(self, xh, dt, a_log, B, C):
        b, s, h, hd = xh.shape
        n = B.shape[-1]
        A = -jnp.exp(a_log)
        state = jnp.zeros((b, h, hd, n), jnp.float32)
        ys = []
        for t in range(s):
            a_t = jnp.exp(dt[:, t] * A[None])                  # (B,H)
            contrib = jnp.einsum("bn,bhd,bh->bhdn", B[:, t], xh[:, t],
                                 dt[:, t])
            state = a_t[..., None, None] * state + contrib
            ys.append(jnp.einsum("bn,bhdn->bhd", C[:, t], state))
        return jnp.stack(ys, axis=1)

    @pytest.mark.parametrize("s,chunk", [(16, 4), (17, 4), (8, 8), (12, 5)])
    def test_chunked_matches_naive(self, s, chunk):
        from repro.models.ssm import _ssd_chunked
        b, h, hd, n = 2, 3, 4, 5
        keys = jax.random.split(KEY, 4)
        xh = jax.random.normal(keys[0], (b, s, h, hd))
        dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
        a_log = jnp.log(jnp.linspace(1.0, 4.0, h))
        B = jax.random.normal(keys[2], (b, s, n))
        C = jax.random.normal(keys[3], (b, s, n))
        got = _ssd_chunked(xh, dt, a_log, B, C, chunk)
        want = self._naive_ssd(xh, dt, a_log, B, C)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestRGLRUOracle:
    def test_associative_scan_matches_sequential(self):
        from repro.models.rglru import init_rglru, rglru
        p = init_rglru(KEY, 16)
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 12, 16))
        y_par, last = rglru(p, x)
        # sequential via repeated single-step decode
        state = jnp.zeros((2, 16), jnp.float32)
        outs = []
        for t in range(12):
            yt, state = rglru(p, x[:, t:t + 1], state=state)
            outs.append(yt[:, 0])
        y_seq = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(y_par, np.float32),
                                   np.asarray(y_seq, np.float32),
                                   rtol=1e-4, atol=1e-4)


class TestMoE:
    def test_capacity_drops_are_bounded(self):
        from repro.models.moe import moe_ffn, init_moe_ffn
        cfg = get_config("qwen2-moe-a2.7b").reduced()
        p = init_moe_ffn(KEY, cfg)
        x = jax.random.normal(KEY, (2, 16, cfg.d_model))
        out = moe_ffn(p, x, cfg)
        assert out.shape == x.shape
        assert bool(jnp.isfinite(out).all())

    def test_sliding_window_mask(self):
        """Mixtral SWA: token t must not attend beyond window."""
        from repro.models.layers import _chunked_attention
        b, s, h, hd = 1, 12, 1, 4
        q = jnp.ones((b, s, h, hd))
        k = jnp.ones((b, s, h, hd))
        # one-hot values reveal which positions were attended
        v = jnp.eye(s)[None, :, None, :4 * ((s + 3) // 4)][..., :hd]
        v = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)
                             .reshape(1, s, 1, 1), (b, s, h, hd))
        out = _chunked_attention(q, k, v, causal=True, window=4, q_block=4)
        # position 11 attends {8,9,10,11} -> mean 9.5
        assert float(out[0, 11, 0, 0]) == pytest.approx(9.5, abs=1e-3)
        # position 2 attends {0,1,2} -> mean 1.0
        assert float(out[0, 2, 0, 0]) == pytest.approx(1.0, abs=1e-3)


class TestPaperNets:
    def test_weight_counts_match_table1(self):
        from repro.configs.paper_apps import PAPER_APP_CONFIGS
        from repro.models import paper_nets as PN
        for name, cfg in PAPER_APP_CONFIGS.items():
            params = PN.init_app(KEY, cfg)
            w = PN.weight_count(params)
            assert w == pytest.approx(cfg.weights_target_m * 1e6,
                                      rel=0.20), name

    @pytest.mark.parametrize("name", ["MLP0", "LSTM1", "CNN0"])
    def test_quantized_close(self, name):
        from repro.configs.paper_apps import PAPER_APP_CONFIGS
        from repro.models import paper_nets as PN
        cfg = PAPER_APP_CONFIGS[name]
        params = PN.init_app(KEY, cfg)
        x = PN.app_input(cfg, batch=4)
        y = PN.apply_app(params, cfg, x).astype(jnp.float32)
        qp = quantize_tree(params, min_size=1024)
        yq = PN.apply_app(qp, cfg, x, mode=W8A16).astype(jnp.float32)
        rel = float(jnp.linalg.norm(yq - y) / (jnp.linalg.norm(y) + 1e-9))
        assert rel < 0.1
