"""Granite-4.0-H-Small as served: its weights from a seed, and a plain
reference.

The reference is the model's forward pass over a whole sequence in
float32 at ``highest`` matmul precision (HF ``granitemoehybrid``).  Each
of the 40 layers, with m the residual multiplier (0.22):

    h = x + m * mixer(rmsnorm(x))
    x = h + m * (routed(rmsnorm(h)) + shared(rmsnorm(h)))

- the mixer is Mamba-2 (``layer_types`` "mamba") or GQA attention with
  no positional embedding, scores scaled by ``attention_multiplier``;
- Mamba-2 runs its recurrence as its definition, one token at a time,

      h_t = exp(dt_t * A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

  after a causal depthwise convolution (with bias) and SiLU over
  (x, B, C), and normalises ``y * silu(z)`` with the gated RMSNorm
  before the out-projection;
- routed: the router scores all published experts, a softmax runs over
  each token's top-k scores, and the experts held here are computed
  densely over every token and weighted by their share of that softmax
  (0 where a token did not choose them); the experts held on the other
  chips of the deployment are left out, as the program leaves them out;
- shared: one SiLU-gated MLP, counted once;
- embeddings times ``embedding_multiplier``, logits over the tied
  embedding divided by ``logits_scaling``.

It imports nothing of the program under test.  Departures from the
published architecture, which the program shares:

- the Mamba in-projection's outputs are ordered x, z, B, C, dt
  (published: z, x, B, C, dt), and the attention's q, k and v are three
  matrices (published: three as well, fused only in some kernels); both
  only rename random weights;
- only ``num_local_experts`` of the published ``deployment.
  num_experts_published`` experts are held and computed (the chip's
  share, see the configuration file).

Weights are int8 values with float32 scales for every projection (per
output column, per expert for the experts) and the tied embedding (per
row), generated directly in the program's layout so that
``program_tree`` hands the engine the same buffers; the router, the
convolution, A, dt bias, D and the norms stay float32, as the program
keeps them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT8_STD = 73.6          # std of a uniform int8 on [-127, 127]


def dims(c):
    D = c["hidden_size"]
    types = tuple(c["layer_types"])
    H = c["num_attention_heads"]
    return dict(
        L=c["num_hidden_layers"], D=D, V=c["vocab_size"],
        Lm=types.count("mamba"), La=types.count("attention"),
        H=H, KV=c["num_key_value_heads"], hd=D // H,
        din=c["mamba_expand"] * D, N=c["mamba_d_state"],
        mh=c["mamba_n_heads"], mhd=c["mamba_d_head"], W=c["mamba_d_conv"],
        E=c["deployment"]["num_experts_published"],
        held=c["num_local_experts"], off=c["deployment"]["expert_offset"],
        k=c["num_experts_per_tok"], F=c["intermediate_size"],
        S=c["shared_intermediate_size"])


def _mamba_linears(d):
    return [("mamba.in_proj", d["D"], 2 * d["din"] + 2 * d["N"] + d["mh"]),
            ("mamba.out_proj", d["din"], d["D"])]


def _attn_linears(d):
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    return [("attn.q", d["D"], q), ("attn.k", d["D"], kv),
            ("attn.v", d["D"], kv), ("attn.o", q, d["D"])]


def _shared_linears(d):
    return [("shared.gate_up", d["D"], 2 * d["S"]),
            ("shared.down", d["S"], d["D"])]


def linears(c):
    """The int8 linear layers: (name, d_in, d_out).  Mixers differ by
    layer (``mamba.*`` in the Mamba layers, ``attn.*`` in the attention
    layers); every layer has ``shared.*`` and one ``expert<e>.gate_up``
    and ``expert<e>.down`` per expert held here."""
    d = dims(c)
    experts = []
    for e in range(d["held"]):
        experts += [(f"expert{e}.gate_up", d["D"], 2 * d["F"]),
                    (f"expert{e}.down", d["F"], d["D"])]
    return (_mamba_linears(d) + _attn_linears(d) + _shared_linears(d)
            + experts)


def token_flops(c, ctx):
    """Forward FLOPs of one token at ``ctx`` earlier positions: every
    layer's mixer (projections, convolution and state update, or
    projections and attention over ``ctx + 1`` positions), router and
    shared MLP, this chip's share of the routed experts (top-k x held / E
    experts a token), and the LM head."""
    d = dims(c)
    lin = lambda ls: sum(2 * k * n for _, k, n in ls)
    conv = 2 * d["W"] * (d["din"] + 2 * d["N"])
    ssm = 6 * d["mh"] * d["mhd"] * d["N"]
    attn = 4 * (ctx + 1) * d["H"] * d["hd"]
    expert = 2 * (d["D"] * 2 * d["F"] + d["F"] * d["D"])
    moe = (2 * d["D"] * d["E"] + lin(_shared_linears(d))
           + d["k"] * d["held"] / d["E"] * expert)
    return (d["Lm"] * (lin(_mamba_linears(d)) + conv + ssm)
            + d["La"] * (lin(_attn_linears(d)) + attn)
            + d["L"] * moe + 2 * d["D"] * d["V"])


def _int8(key, shape):
    v = jax.random.bits(key, shape, jnp.uint8)
    v = jax.lax.bitcast_convert_type(v, jnp.int8)
    return jnp.maximum(v, jnp.int8(-127))


def _qlinear(key, lead, k, n):
    """int8 values (*lead, k, n), one layer at a time, and float32 column
    scales (*lead, 1, n) that give the dequantised weight a std of
    1/sqrt(k)."""
    kv, ks = jax.random.split(key)
    keys = jax.random.split(kv, lead[0])
    q = jax.lax.map(lambda kk: _int8(kk, (*lead[1:], k, n)), keys)
    s = jax.random.uniform(ks, (*lead, 1, n), jnp.float32, 0.75, 1.25) / (
        INT8_STD * k ** 0.5)
    return q, s


def _norm(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape)


@functools.partial(jax.jit, static_argnums=1)
def _make(key, spec):
    d = dict(spec)
    D, Lm, La, L = d["D"], d["Lm"], d["La"], d["L"]
    C = d["din"] + 2 * d["N"]
    ks = iter(jax.random.split(key, 40))
    w = {"embed_q": _int8(next(ks), (d["V"], D)),
         # rows of norm 1/6, brought to 2 by the embedding multiplier
         # (12): each residual branch adds about 0.22 sqrt(D), so the
         # tied head's logits are set by the layers and not by the
         # token's own embedding, which would repeat it
         "embed_s": jax.random.uniform(next(ks), (d["V"], 1), jnp.float32,
                                       0.75, 1.25) / (INT8_STD * 6.0
                                                      * D ** 0.5),
         "lnf_w": _norm(next(ks), (D,))}
    for name, k, n in _mamba_linears(d):
        w[name + "_q"], w[name + "_s"] = _qlinear(next(ks), (Lm,), k, n)
    w["mamba.norm_w"] = _norm(next(ks), (Lm, D))
    w["mamba.gate_norm_w"] = _norm(next(ks), (Lm, d["din"]))
    w["mamba.conv_w"] = 0.3 * jax.random.truncated_normal(
        next(ks), -2, 2, (Lm, d["W"], C))
    w["mamba.conv_b"] = 0.05 * jax.random.normal(next(ks), (Lm, C))
    w["mamba.a_log"] = jnp.log(jax.random.uniform(
        next(ks), (Lm, d["mh"]), jnp.float32, 1.0, 16.0))
    # dt log-uniform on [1e-3, 1e-1], stored as softplus^-1(dt)
    dt = jnp.exp(jax.random.uniform(next(ks), (Lm, d["mh"]), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    w["mamba.dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    w["mamba.D"] = 1.0 + 0.1 * jax.random.normal(next(ks), (Lm, d["mh"]))
    for name, k, n in _attn_linears(d):
        w[name + "_q"], w[name + "_s"] = _qlinear(next(ks), (La,), k, n)
    w["attn.norm_w"] = _norm(next(ks), (La, D))
    for name, k, n in _shared_linears(d):
        w[name + "_q"], w[name + "_s"] = _qlinear(next(ks), (L,), k, n)
    w["moe.norm_w"] = _norm(next(ks), (L, D))
    w["moe.router"] = jax.random.normal(next(ks), (L, D, d["E"])) * D ** -0.5
    w["expert.gate_up_q"], w["expert.gate_up_s"] = _qlinear(
        next(ks), (L, d["held"]), D, 2 * d["F"])
    w["expert.down_q"], w["expert.down_s"] = _qlinear(
        next(ks), (L, d["held"]), d["F"], D)
    return w


def make_weights(key, c):
    return _make(key, tuple(sorted(dims(c).items())))


def program_tree(w):
    q = lambda name: {"w": ("int8", w[name + "_q"], w[name + "_s"])}
    return {
        "embed": {"table": ("int8", w["embed_q"], w["embed_s"])},
        "mamba": {
            "norm": {"scale": w["mamba.norm_w"]},
            "in_proj": q("mamba.in_proj"),
            "conv_w": w["mamba.conv_w"], "conv_b": w["mamba.conv_b"],
            "a_log": w["mamba.a_log"], "dt_bias": w["mamba.dt_bias"],
            "D": w["mamba.D"],
            "gate_norm": {"scale": w["mamba.gate_norm_w"]},
            "out_proj": q("mamba.out_proj"),
        },
        "attn": {
            "norm": {"scale": w["attn.norm_w"]},
            "attn": {"wq": q("attn.q"), "wk": q("attn.k"),
                     "wv": q("attn.v"), "wo": q("attn.o")},
        },
        "moe": {
            "norm": {"scale": w["moe.norm_w"]},
            "router": w["moe.router"],
            "experts": {"gate_up": q("expert.gate_up"),
                        "down": q("expert.down")},
            "shared": {"gate_up": q("shared.gate_up"),
                       "down": q("shared.down")},
        },
        "ln_f": {"scale": w["lnf_w"]},
    }


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu_glu(h):
    g, u = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(g) * u


def logits(w, tokens, c):
    """Logits (T, V) of ``tokens`` (T,)."""
    d = dims(c)
    din, N, mh, mhd, W = d["din"], d["N"], d["mh"], d["mhd"], d["W"]
    H, KV, hd = d["H"], d["KV"], d["hd"]
    eps = c["rms_norm_eps"]
    m = c["residual_multiplier"]
    t = tokens.shape[0]

    def deq(name, *idx):
        q, s = w[name + "_q"], w[name + "_s"]
        for i in idx:
            q, s = q[i], s[i]
        return q.astype(jnp.float32) * s

    emb = w["embed_q"].astype(jnp.float32) * w["embed_s"]
    x = emb[tokens] * c["embedding_multiplier"]

    def mamba(x, l):
        h = _rmsnorm(x, w["mamba.norm_w"][l], eps)
        p = h @ deq("mamba.in_proj", l)
        xz, z, bc, dt = (p[:, :din], p[:, din:2 * din],
                         p[:, 2 * din:2 * din + 2 * N], p[:, 2 * din + 2 * N:])
        dt = jax.nn.softplus(dt + w["mamba.dt_bias"][l])          # (T, mh)
        u = jnp.concatenate([xz, bc], -1)
        up = jnp.pad(u, ((W - 1, 0), (0, 0)))
        conv = sum(up[i:i + t] * w["mamba.conv_w"][l][i] for i in range(W))
        u = jax.nn.silu(conv + w["mamba.conv_b"][l])
        xs, B, C = (u[:, :din].reshape(t, mh, mhd), u[:, din:din + N],
                    u[:, din + N:])
        A = -jnp.exp(w["mamba.a_log"][l])

        def step(hs, inp):
            x_t, b_t, c_t, dt_t = inp
            hs = (jnp.exp(dt_t * A)[:, None, None] * hs
                  + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None])
            return hs, hs @ c_t

        _, y = jax.lax.scan(step, jnp.zeros((mh, mhd, N), jnp.float32),
                            (xs, B, C, dt))
        y = y + w["mamba.D"][l][None, :, None] * xs
        y = y.reshape(t, din) * jax.nn.silu(z)
        y = _rmsnorm(y, w["mamba.gate_norm_w"][l], eps)
        return x + m * (y @ deq("mamba.out_proj", l))

    def attention(x, a):
        h = _rmsnorm(x, w["attn.norm_w"][a], eps)
        q = (h @ deq("attn.q", a)).reshape(t, KV, H // KV, hd)
        k = (h @ deq("attn.k", a)).reshape(t, KV, hd)
        v = (h @ deq("attn.v", a)).reshape(t, KV, hd)
        s = jnp.einsum("qkgd,skd->kgqs", q, k) * c["attention_multiplier"]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
        return x + m * (o.reshape(t, H * hd) @ deq("attn.o", a))

    def moe(x, l):
        h = _rmsnorm(x, w["moe.norm_w"][l], eps)
        top_v, top_i = jax.lax.top_k(h @ w["moe.router"][l], d["k"])
        gates = jax.nn.softmax(top_v, -1)                        # (T, k)
        held = d["off"] + jnp.arange(d["held"])
        comb = jnp.einsum("tk,tke->te", gates,
                          (top_i[..., None] == held).astype(jnp.float32))
        out = _silu_glu(h @ deq("shared.gate_up", l)) @ deq("shared.down", l)
        fe = _silu_glu(jnp.einsum("td,edf->etf", h,
                                  deq("expert.gate_up", l)))
        fe = jnp.einsum("etf,efd->etd", fe, deq("expert.down", l))
        return x + m * (out + jnp.einsum("te,etd->td", comb, fe))

    # one period of the layer pattern, scanned over the periods
    types = list(c["layer_types"])
    plen = next(p for p in range(1, d["L"] + 1)
                if d["L"] % p == 0 and types == types[:p] * (d["L"] // p))
    period = types[:plen]
    nm, na = period.count("mamba"), period.count("attention")

    def one_period(x, p):
        mi, ai = 0, 0
        for i, kind in enumerate(period):
            if kind == "mamba":
                x = mamba(x, p * nm + mi)
                mi += 1
            else:
                x = attention(x, p * na + ai)
                ai += 1
            x = moe(x, p * plen + i)
        return x, None

    x, _ = jax.lax.scan(one_period, x, jnp.arange(d["L"] // plen))
    x = _rmsnorm(x, w["lnf_w"], eps)
    return x @ emb.T / c["logits_scaling"]
