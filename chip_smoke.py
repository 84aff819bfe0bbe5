#!/usr/bin/env python3
"""Prove the serving engine runs on a TPU at starcoder2-3b's full width.

    python chip_smoke.py               # one chip: kernels, launcher, engine
    python chip_smoke.py --four-chips  # 4 replicas and tp=4 vs one chip

Run from the root of a checkout, on a machine with a TPU.  One process
drives every phase (a chip belongs to one process at a time).  Weights are
random from ``--seed`` and quantized to int8; the model is starcoder2-3b
at its published widths and depth (30 layers, d_model 3072, GQA 24/2,
vocab 49152).

One chip: each Pallas kernel against its ``kernels/ref.py`` oracle at the
model's shapes, then a few requests through ``repro.launch.serve.main``,
then the int8-KV ``Engine``, contiguous and paged.  ``--four-chips``:
the same trace through a ``ReplicaRouter`` over four one-chip replicas
and through ``ShardedExecutor(tp=4)``, each compared with the one-chip
engine.  Any failed check raises, so the exit code is non-zero.  With no
TPU the script exits non-zero before it prints a result.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax

ARCH = "starcoder2-3b"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {msg}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.all(np.isfinite(got)), "kernel output is not finite")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def kernel_checks(cfg, seed: int) -> None:
    """Each main-path Pallas kernel against its oracle at the model's
    shapes.  The oracles run under float32 ``highest`` matmul precision;
    the error is max|kernel - oracle| / max|oracle|."""
    import jax.numpy as jnp

    from repro.core.quant import quantize, quantize_weight
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(seed)
    d, f = cfg.d_model, cfg.d_ff
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kvh
    ks = iter(jax.random.split(key, 32))

    def run(name, got, oracle, tol):
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = rel_err(got, want)
        log(f"kernel {name}: max rel err {err!r} (tolerance {tol!r})")
        check(err <= tol, f"{name} error {err} above {tol}")

    # w8a16 takes bf16 activations and writes bf16: one bf16 rounding of
    # the dequantized weights and of the output gives at most 4e-3 at
    # these shapes, hence 2e-2.  w8a8 accumulates int8 products in int32
    # exactly and dequantizes in f32 in the oracle's order.
    for m in (8, 2048):
        for k, n in ((d, d), (d, f), (f, d)):
            x = jax.random.normal(next(ks), (m, k), jnp.float32)
            w = quantize_weight(jax.random.normal(next(ks), (k, n)) * k ** -0.5)
            xb = x.astype(jnp.bfloat16)
            run(f"qmatmul_w8a16 {m}x{k}x{n}",
                ops.qmatmul(xb, w, out_dtype=jnp.bfloat16),
                lambda: ref.qmatmul_w8a16_ref(
                    xb, w.values, w.scale.reshape(-1),
                    out_dtype=jnp.float32), 2e-2)
            xq = quantize(x, bits=8, axis=None)
            run(f"qmatmul_w8a8 {m}x{k}x{n}",
                ops.qmatmul(x, w, x_q=xq, out_dtype=jnp.float32),
                lambda: ref.qmatmul_w8a8_ref(
                    xq.values, w.values, xq.scale, w.scale.reshape(-1),
                    out_dtype=jnp.float32), 1e-5)

    # decode attention, f32 out.  The cache holds normal k/v quantized
    # per (token, head) the way the model writes it.  Emulating one bf16
    # pass per matmul on these shapes gives 4e-3, hence 2e-2.
    def q8(shape):
        t = jax.random.normal(next(ks), shape, jnp.float32)
        sc = jnp.maximum(jnp.max(jnp.abs(t), -1, keepdims=True), 1e-6) / 127
        return jnp.round(t / sc).astype(jnp.int8), sc

    b, s, bs = 8, 256, 16
    q = jax.random.normal(next(ks), (b, kvh, g, hd), jnp.float32)
    (kc, ksc), (vc, vsc) = q8((b, s, kvh, hd)), q8((b, s, kvh, hd))
    vl = jax.random.randint(next(ks), (b,), 0, s + 1, jnp.int32)
    kn = jax.random.normal(next(ks), (b, 1, kvh, hd), jnp.float32)
    vn = jax.random.normal(next(ks), (b, 1, kvh, hd), jnp.float32)
    run("decode_attention contiguous",
        ops.decode_attention(q, kc, vc, ksc, vsc, vl),
        lambda: ref.decode_attention_int8_ref(q, kc, vc, ksc, vsc, vl),
        2e-2)
    run("decode_attention append",
        ops.decode_attention(q, kc, vc, ksc, vsc, vl, k_new=kn, v_new=vn),
        lambda: ref.decode_attention_int8_ref(q, kc, vc, ksc, vsc, vl,
                                              k_new=kn, v_new=vn), 2e-2)
    nb = b * s // bs + 1
    perm = jax.random.permutation(next(ks), nb - 1) + 1
    tables = perm.reshape(b, s // bs).astype(jnp.int32)
    (kp, ksp), (vp, vsp) = q8((nb, bs, kvh, hd)), q8((nb, bs, kvh, hd))
    run("decode_attention paged",
        ops.decode_attention(q, kp, vp, ksp, vsp, vl, block_tables=tables,
                             k_new=kn, v_new=vn),
        lambda: ref.decode_attention_paged_ref(
            q, kp, vp, ksp, vsp, vl, tables, k_new=kn, v_new=vn), 2e-2)

    # flash attention (prefill): bf16 in and out
    sq = 256
    qf, kf, vf = (jax.random.normal(next(ks), (1, sq, cfg.n_heads, hd),
                                    jnp.float32).astype(jnp.bfloat16)
                  for _ in range(3))

    def flash_oracle():
        bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, sq, hd)
        o = ref.flash_attention_ref(bhsd(qf), bhsd(kf), bhsd(vf),
                                    causal=True, out_dtype=jnp.float32)
        return o.reshape(1, cfg.n_heads, sq, hd).transpose(0, 2, 1, 3)

    run("flash_attention causal",
        ops.flash_attention(qf, kf, vf, causal=True), flash_oracle, 2e-2)


def serve_launcher(seed: int) -> None:
    from repro.launch import serve
    argv = ["--arch", ARCH, "--seed", str(seed), "--n-requests", "8",
            "--rate", "200", "--max-batch", "8", "--deadline-ms", "2000",
            "--prompt-len", "6", "--gen-tokens", "8", "--decode-tokens", "4"]
    log(f"launch/serve.py main({' '.join(argv)})")
    t0 = time.perf_counter()
    rc = serve.main(argv)
    log(f"launch/serve.py rc={rc} in {time.perf_counter() - t0!r} s "
        f"(cold: includes init, quantization and every compile)")
    check(rc == 0, f"launch/serve.py returned {rc}")


def requests(cfg, n: int, seed: int):
    from repro import engine as E
    return E.synthetic_requests(n, rate_per_s=500.0, vocab=cfg.vocab,
                                prompt_len=6, max_new_tokens=10, seed=seed)


def serve_engine(label: str, eng, reqs, vocab: int):
    """Warm up (compile), serve, and hold the report to the contract."""
    t0 = time.perf_counter()
    eng.warmup()
    t1 = time.perf_counter()
    rep = eng.serve(reqs)
    t2 = time.perf_counter()
    bad = [r.rid for r in rep.results
           if r.status != "ok" or len(r.tokens) != reqs[0].max_new_tokens
           or not all(0 <= t < vocab for t in r.tokens)]
    log(f"engine {label}: warmup+compile {t1 - t0!r} s, serve {t2 - t1!r} s,"
        f" {len(rep.results)} requests, {rep.generated_tokens} tokens in "
        f"{rep.ticks} ticks; not ok: {bad}; nonfinite_samples="
        f"{rep.nonfinite_samples} leaked_blocks={rep.leaked_blocks}")
    check(len(rep.results) == len(reqs), f"{label}: lost requests")
    check(not bad, f"{label}: requests not ok or malformed: {bad}")
    check(rep.nonfinite_samples == 0, f"{label}: non-finite samples")
    check(rep.leaked_blocks == 0, f"{label}: leaked blocks")
    return rep


def compare(label: str, got: dict, want: dict) -> int:
    """Exact-match count of per-request token lists; each divergence is
    reported by its first diverging position."""
    same = 0
    for rid in sorted(want):
        a, b = got.get(rid), want[rid]
        if a == b:
            same += 1
            continue
        pos = next((i for i, (x, y) in enumerate(zip(a or [], b))
                    if x != y), min(len(a or []), len(b)))
        log(f"{label}: request {rid} diverges at token {pos}: "
            f"{(a or [])[pos:pos + 3]} vs {b[pos:pos + 3]}")
    log(f"{label}: {same}/{len(want)} requests identical")
    return same


def devices_of(tree) -> list:
    return sorted({str(d) for x in jax.tree_util.tree_leaves(tree)
                   for d in x.devices()})


def one_chip(cfg, qcfg, params, seed: int) -> None:
    import jax.numpy as jnp

    from repro import engine as E
    from repro.core.qlinear import W8A16

    reqs = requests(cfg, 12, seed)
    kw = dict(mode=W8A16, num_slots=8, max_seq=32)
    eng = E.Engine(qcfg, params, **kw)
    contiguous = serve_engine("int8-KV contiguous", eng, reqs, cfg.vocab)
    paged = serve_engine("int8-KV paged block_size=16",
                         E.Engine(qcfg, params, block_size=16, **kw),
                         reqs, cfg.vocab)
    compare("paged vs contiguous", paged.outputs(), contiguous.outputs())

    ln = eng.lanes[None]
    compiled = ln.step.lower(ln.params, jnp.zeros((8, 1), jnp.int32),
                             ln.cache, jnp.zeros((8,), jnp.int32),
                             jnp.zeros((8,), bool)).compile()
    text = compiled.as_text()
    sites = text.count('custom_call_target="tpu_custom_call"')
    log(f"slot step (int8 KV, contiguous): tpu_custom_call in compiled HLO: "
        f"{'tpu_custom_call' in text} ({sites} call sites); memory "
        f"{compiled.memory_analysis()}")
    check("tpu_custom_call" in text, "no Pallas kernel in the slot step")


def four_chips(cfg, qcfg, params, seed: int) -> None:
    from repro import engine as E
    from repro.core.qlinear import W8A16

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    reqs = requests(cfg, 16, seed)
    kw = dict(mode=W8A16, num_slots=8, max_seq=32, block_size=16)
    base = serve_engine("one chip", E.Engine(qcfg, params, **kw), reqs,
                        cfg.vocab).outputs()

    fleet = [E.Engine(qcfg, params, name=f"replica{i}",
                      backend=E.SingleDeviceExecutor(device=devs[i]), **kw)
             for i in range(4)]
    for eng in fleet:
        eng.warmup()
    t0 = time.perf_counter()
    rrep = E.ReplicaRouter(fleet).serve(reqs)
    log(f"router: 4 replicas served {len(rrep.results)} requests in "
        f"{time.perf_counter() - t0!r} s (replicas run one after another); "
        f"per-replica requests {rrep.replica_requests}; leaked_blocks="
        f"{rrep.leaked_blocks}")
    placed = {}
    for eng in fleet:
        ln = eng.lanes[None]
        placed[eng.name] = (devices_of(ln.params), devices_of(ln.cache))
        log(f"router: {eng.name} params on {placed[eng.name][0]}, cache on "
            f"{placed[eng.name][1]}")
    homes = [p[0] for p in placed.values()]
    check(all(len(h) == 1 for h in homes)
          and len({h[0] for h in homes}) == 4,
          f"replicas are not on 4 distinct devices: {placed}")
    check(all(r.status == "ok" for r in rrep.results), "router: not all ok")
    compare("router (4 replicas) vs one chip", rrep.outputs(), base)

    tp = E.Engine(qcfg, params, backend=E.ShardedExecutor(tp=4), **kw)
    log(f"tp=4: params on {devices_of(tp.lanes[None].params)}")
    trep = serve_engine("ShardedExecutor(tp=4)", tp, reqs, cfg.vocab)
    compare("tp=4 vs one chip", trep.outputs(), base)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica router and tp=4 paths and "
                         "the one-chip engine they are compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}: "
              f"{devs}); this check runs only on a chip", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform})")

    from repro.configs import get_config
    from repro.core.quant import tree_weight_bytes
    from repro.models import registry as R

    cfg = get_config(ARCH)
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    if not args.four_chips:
        kernel_checks(cfg, args.seed)
        serve_launcher(args.seed)

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        R.init_quantized(jax.random.PRNGKey(args.seed), cfg))
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab}; int8 weights "
        f"on the device: {tree_weight_bytes(params)} bytes; init+quantize "
        f"(cold, compile included) {time.perf_counter() - t0!r} s")

    if args.four_chips:
        four_chips(cfg, qcfg, params, args.seed)
    else:
        one_chip(cfg, qcfg, params, args.seed)

    stats = devs[0].memory_stats() or {}
    log(f"device 0 peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
