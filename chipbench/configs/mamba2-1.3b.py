"""Mamba-2 as served: its weights from a seed, and a plain reference.

The reference is the model's forward pass over a whole sequence in
float32 at ``highest`` matmul precision, with the state-space recurrence
written as its definition, one token at a time:

    h_t = exp(dt_t * A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

It imports nothing of the program under test.  Departures from the
published architecture (arXiv:2405.21060, HF ``state-spaces/mamba2-1.3b``),
which the program shares:

- the published block normalizes ``y * silu(z)`` with a gated RMSNorm
  before the output projection; the program has no such norm, and
  neither has the reference;
- the in-projection's outputs are ordered x, z, B, C, dt (published:
  z, x, B, C, dt), which only renames random weights.

Weights are int8 values with float32 scales for the in- and
out-projections (per output column) and the tied embedding (per row);
the convolution, A, dt bias, D and the norms stay float32, as the
program keeps them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT8_STD = 73.6          # std of a uniform int8 on [-127, 127]


def dims(c):
    s = c["ssm_cfg"]
    D = c["d_model"]
    din = s["expand"] * D
    return dict(L=c["n_layer"], D=D, din=din, N=s["d_state"],
                hd=s["headdim"], H=din // s["headdim"], W=s["d_conv"],
                V=c["vocab_size"])


def linears(c):
    """The int8 linear layers of one block: (name, d_in, d_out)."""
    return _linears(dims(c))


def _linears(d):
    return [("in_proj", d["D"], 2 * d["din"] + 2 * d["N"] + d["H"]),
            ("out_proj", d["din"], d["D"])]


def token_flops(c, ctx):
    """Forward FLOPs of one token (``ctx`` does not matter): the linear
    layers, the causal convolution, the state update and read-out, and
    the LM head."""
    d = dims(c)
    lin = sum(2 * k * n for _, k, n in linears(c))
    conv = 2 * d["W"] * (d["din"] + 2 * d["N"])
    ssm = 6 * d["H"] * d["hd"] * d["N"]
    return d["L"] * (lin + conv + ssm) + 2 * d["D"] * d["V"]


def _int8(key, shape):
    v = jax.random.bits(key, shape, jnp.uint8)
    v = jax.lax.bitcast_convert_type(v, jnp.int8)
    return jnp.maximum(v, jnp.int8(-127))


@functools.partial(jax.jit, static_argnums=1)
def _make(key, spec):
    d = dict(spec)
    L, D, V, H, C = d["L"], d["D"], d["V"], d["H"], d["din"] + 2 * d["N"]
    ks = iter(jax.random.split(key, 32))
    w = {"embed_q": _int8(next(ks), (V, D)),
         "embed_s": jax.random.uniform(next(ks), (V, 1), jnp.float32,
                                       0.75, 1.25) / (INT8_STD * D ** 0.5)}
    for name, k, n in _linears(d):
        w[name + "_q"] = jax.lax.map(lambda kk: _int8(kk, (k, n)),
                                     jax.random.split(next(ks), L))
        w[name + "_s"] = jax.random.uniform(
            next(ks), (L, 1, n), jnp.float32, 0.75, 1.25) / (
                INT8_STD * k ** 0.5)
    w["norm_w"] = 1.0 + 0.1 * jax.random.normal(next(ks), (L, D))
    w["conv_w"] = 0.3 * jax.random.truncated_normal(next(ks), -2, 2,
                                                    (L, d["W"], C))
    w["conv_b"] = 0.05 * jax.random.normal(next(ks), (L, C))
    w["a_log"] = jnp.log(jax.random.uniform(next(ks), (L, H),
                                            jnp.float32, 1.0, 16.0))
    # dt log-uniform on [1e-3, 1e-1], stored as softplus^-1(dt)
    dt = jnp.exp(jax.random.uniform(next(ks), (L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    w["D"] = 1.0 + 0.1 * jax.random.normal(next(ks), (L, H))
    w["lnf_w"] = 1.0 + 0.1 * jax.random.normal(next(ks), (D,))
    return w


def make_weights(key, c):
    return _make(key, tuple(sorted(dims(c).items())))


def program_tree(w):
    return {
        "embed": {"table": ("int8", w["embed_q"], w["embed_s"])},
        "layers": {
            "norm": {"scale": w["norm_w"]},
            "in_proj": {"w": ("int8", w["in_proj_q"], w["in_proj_s"])},
            "conv_w": w["conv_w"], "conv_b": w["conv_b"],
            "a_log": w["a_log"], "dt_bias": w["dt_bias"], "D": w["D"],
            "out_proj": {"w": ("int8", w["out_proj_q"], w["out_proj_s"])},
        },
        "ln_f": {"scale": w["lnf_w"]},
    }


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def logits(w, tokens, c):
    """Logits (T, V) of ``tokens`` (T,)."""
    d = dims(c)
    din, N, H, hd, W = d["din"], d["N"], d["H"], d["hd"], d["W"]
    eps = c["norm_epsilon"]
    deq = lambda n, l: w[n + "_q"][l].astype(jnp.float32) * w[n + "_s"][l]
    emb = w["embed_q"].astype(jnp.float32) * w["embed_s"]
    x = emb[tokens]
    t = tokens.shape[0]

    def layer(x, l):
        h = _rmsnorm(x, w["norm_w"][l], eps)
        p = h @ deq("in_proj", l)
        xz, z, bc, dt = (p[:, :din], p[:, din:2 * din],
                         p[:, 2 * din:2 * din + 2 * N], p[:, 2 * din + 2 * N:])
        dt = jax.nn.softplus(dt + w["dt_bias"][l])                # (T, H)
        u = jnp.concatenate([xz, bc], -1)
        up = jnp.pad(u, ((W - 1, 0), (0, 0)))
        conv = sum(up[i:i + t] * w["conv_w"][l][i] for i in range(W))
        u = jax.nn.silu(conv + w["conv_b"][l])
        xs, B, C = (u[:, :din].reshape(t, H, hd), u[:, din:din + N],
                    u[:, din + N:])
        A = -jnp.exp(w["a_log"][l])

        def step(hs, inp):
            x_t, b_t, c_t, dt_t = inp
            hs = (jnp.exp(dt_t * A)[:, None, None] * hs
                  + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None])
            return hs, hs @ c_t

        _, y = jax.lax.scan(step, jnp.zeros((H, hd, N), jnp.float32),
                            (xs, B, C, dt))
        y = y + w["D"][l][None, :, None] * xs
        y = y.reshape(t, din) * jax.nn.silu(z)
        return x + y @ deq("out_proj", l), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(d["L"]))
    x = _rmsnorm(x, w["lnf_w"], eps)
    return x @ emb.T
