"""StarCoder2 as served: its weights from a seed, and a plain reference.

The reference is the model's forward pass over a whole sequence in
float32 at ``highest`` matmul precision: no kernels, no cache, no
batching.  It imports nothing of the program under test.  Departures
from the published architecture (arXiv:2402.19173, HF
``bigcode/starcoder2-3b``), which the program shares:

- the attention output projection and the MLP carry no bias (the
  published model has ``use_bias`` on every linear layer); the reference
  takes those biases as zero;
- the published 4096-token sliding window is longer than any request of
  the cells, so attention is plainly causal.

Weights are int8 values with float32 scales, per output column for the
linear layers and per row for the tied embedding, as a W8A16 deployment
stores them.  ``make_weights`` draws them on the device in one jitted
call; ``program_tree`` lays the same arrays out as the program's
parameter tree, with ``("int8", values, scale)`` where the program
takes a quantized tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT8_STD = 73.6          # std of a uniform int8 on [-127, 127]


def dims(c):
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                hd=c["head_dim"], F=c["intermediate_size"],
                V=c["vocab_size"])


def linears(c):
    """The int8 linear layers of one decoder layer: (name, d_in, d_out)."""
    return _linears(dims(c))


def _linears(d):
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return [("wq", D, H * hd), ("wk", D, KV * hd), ("wv", D, KV * hd),
            ("wo", H * hd, D), ("w_up", D, F), ("w_down", F, D)]


def token_flops(c, ctx):
    """Forward FLOPs of one token at context length ``ctx`` (the token
    included): the linear layers, attention's scores and context, and the
    LM head.  The embedding gather and the norms are not counted."""
    d = dims(c)
    lin = sum(2 * k * n for _, k, n in linears(c)) * d["L"]
    attn = 4 * d["L"] * d["H"] * d["hd"] * ctx
    return lin + attn + 2 * d["D"] * d["V"]


def _int8(key, shape):
    v = jax.random.bits(key, shape, jnp.uint8)
    v = jax.lax.bitcast_convert_type(v, jnp.int8)
    return jnp.maximum(v, jnp.int8(-127))


def _col_scale(key, lead, d_in, d_out):
    u = jax.random.uniform(key, lead + (1, d_out), jnp.float32, 0.75, 1.25)
    return u / (INT8_STD * d_in ** 0.5)


@functools.partial(jax.jit, static_argnums=1)
def _make(key, spec):
    d = dict(spec)
    L, D, V = d["L"], d["D"], d["V"]
    ks = iter(jax.random.split(key, 64))
    w = {"embed_q": _int8(next(ks), (V, D)),
         "embed_s": jax.random.uniform(next(ks), (V, 1), jnp.float32,
                                       0.75, 1.25) / (INT8_STD * D ** 0.5)}
    for name, k, n in _linears(d):
        # one layer at a time, so the temporaries stay one layer's size
        w[name + "_q"] = jax.lax.map(lambda kk: _int8(kk, (k, n)),
                                     jax.random.split(next(ks), L))
        w[name + "_s"] = _col_scale(next(ks), (L,), k, n)
    for name in ("wq", "wk", "wv"):
        n = dict((a, b) for a, _, b in _linears(d))[name]
        w[name + "_b"] = 0.1 * jax.random.normal(next(ks), (L, n))
    for ln in ("ln1", "ln2"):
        w[ln + "_w"] = 1.0 + 0.1 * jax.random.normal(next(ks), (L, D))
        w[ln + "_b"] = 0.05 * jax.random.normal(next(ks), (L, D))
    w["lnf_w"] = 1.0 + 0.1 * jax.random.normal(next(ks), (D,))
    w["lnf_b"] = 0.05 * jax.random.normal(next(ks), (D,))
    return w


def make_weights(key, c):
    return _make(key, tuple(sorted(dims(c).items())))


def program_tree(w):
    q = lambda n: ("int8", w[n + "_q"], w[n + "_s"])
    return {
        "embed": {"table": ("int8", w["embed_q"], w["embed_s"])},
        "layers": {
            "ln_attn": {"scale": w["ln1_w"], "bias": w["ln1_b"]},
            "attn": {"wq": {"w": q("wq"), "b": w["wq_b"]},
                     "wk": {"w": q("wk"), "b": w["wk_b"]},
                     "wv": {"w": q("wv"), "b": w["wv_b"]},
                     "wo": {"w": q("wo")}},
            "ln_mlp": {"scale": w["ln2_w"], "bias": w["ln2_b"]},
            "mlp": {"w_up": {"w": q("w_up")},
                    "w_down": {"w": q("w_down")}},
        },
        "ln_f": {"scale": w["lnf_w"], "bias": w["lnf_b"]},
    }


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..T-1; x is (T, heads, hd)."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_block=256):
    """Causal GQA attention; q (T, H, hd), k and v (T, KV, hd)."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = t // q_block
    qb = q.reshape(nb, q_block, h, hd)

    def block(i, qi):
        s = jnp.einsum("qhd,khd->hqk", qi, k) / hd ** 0.5
        qpos = i * q_block + jnp.arange(q_block)
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qb))
    return out.reshape(t, h, hd)


def logits(w, tokens, c):
    """Logits (T, V) of ``tokens`` (T,), T a multiple of 256."""
    d = dims(c)
    H, KV, hd = d["H"], d["KV"], d["hd"]
    eps, theta = c["norm_epsilon"], c["rope_theta"]
    deq = lambda n, l: w[n + "_q"][l].astype(jnp.float32) * w[n + "_s"][l]
    emb = w["embed_q"].astype(jnp.float32) * w["embed_s"]
    x = emb[tokens]
    t = tokens.shape[0]

    def layer(x, l):
        h = _layernorm(x, w["ln1_w"][l], w["ln1_b"][l], eps)
        q = (h @ deq("wq", l) + w["wq_b"][l]).reshape(t, H, hd)
        k = (h @ deq("wk", l) + w["wk_b"][l]).reshape(t, KV, hd)
        v = (h @ deq("wv", l) + w["wv_b"][l]).reshape(t, KV, hd)
        a = _attention(_rope(q, theta), _rope(k, theta), v)
        x = x + a.reshape(t, H * hd) @ deq("wo", l)
        h = _layernorm(x, w["ln2_w"][l], w["ln2_b"][l], eps)
        u = jax.nn.gelu(h @ deq("w_up", l), approximate=True)
        return x + u @ deq("w_down", l), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(d["L"]))
    x = _layernorm(x, w["lnf_w"], w["lnf_b"], eps)
    return x @ emb.T
