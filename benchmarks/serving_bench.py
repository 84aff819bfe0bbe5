"""Serving-path benchmark rows: tokens/s, service-time curve, chosen tiles.

The perf trajectory every future PR has to beat.  Runs a reduced arch end
to end on whatever backend is present (CPU offline, TPU in production):
post-training int8 quantization, the measured prefill service curve, the
fused multi-token decode loop (jit'd ``lax.scan``, donated int8 KV cache),
and the autotuner's chosen tile configs for the arch's serving matmuls.

Row schema (stable; asserted by tests/test_bench_smoke.py)::

  {"kind": "tokens_per_s",  "arch", "batch", "num_tokens", "tokens_per_s",
   "seconds"}
  {"kind": "service_time",  "arch", "batch", "seconds"}
  {"kind": "chosen_tile",   "arch", "op", "m", "k", "n", "mode",
   "bm", "bn", "bk", "vmem_bytes"}
  {"kind": "engine",        "arch", "family", "rate", "n_requests",
   "num_slots", "p99_s", "tokens_per_s", "mean_occupancy", "ticks",
   "admissions_while_busy", "occupancy_curve", "prefill_chunk",
   "mean_ttft_s", "p99_ttft_s", "block_size", "num_blocks",
   "kv_hbm_bytes", "peak_blocks_used", "mean_block_util",
   "shared_block_hits", "shared_hit_rate", "prefill_tokens_skipped",
   "effective_concurrency", "spec_k", "draft_layers",
   "accepted_per_dispatch", "latency_per_token_s", "model",
   "model_p99_s", "model_mean_ttft_s", "model_goodput_tokens_per_s",
   "model_mean_occupancy"}

The ``engine`` rows are the continuous-batching section: one row per
(family, offered rate) — p99 vs load is the Table 4 story told by the
live engine, now for EVERY registry family (dense, moe, ssm, hybrid,
encdec, vlm — the last two behind per-slot primed cross-K/V, so their
ttft includes the prime dispatch), with the slot-occupancy curve
downsampled inline and the admission-to-first-token columns showing
what chunked prefill buys.  The memory columns (KV-HBM bytes resident,
block utilization, shared-prefix hit rate, effective concurrency) are
live on every row; the non-default values come from the paged-KV rows
(``block_size`` set), where admission is priced in worst-case blocks
and identical prompt prefixes share refcounted blocks.
Timing comes from a measured per-tick cost replayed under the virtual
clock, so the rows are structurally deterministic offline while still
tracking real step cost.
"""
from __future__ import annotations

import dataclasses
import warnings


def serving_rows(arch: str = "starcoder2-3b", *, quant: str = "w8a16",
                 seq: int = 16, decode_tokens: int = 8,
                 batches=(1, 8), tile_m=(8, 32, 128)):
    """Benchmark one reduced arch; returns a list of schema rows."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.qlinear import FP, W8A16, W8A8
    from repro.core.quant import quantize_tree
    from repro.kernels import autotune as AT
    from repro.launch import serve as SV
    from repro.models import registry as R
    from repro.runtime import steps as ST

    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[quant]
    # int8 KV cache: the serving configuration this PR's decode path is for
    cfg = dataclasses.replace(get_config(arch).reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    if mode.enabled:
        params = quantize_tree(params, min_size=2048)

    rows = []
    prefill = jax.jit(ST.make_prefill_step(cfg, mode=mode))
    _, curve = SV.measure_service_curve(
        prefill, params, cfg, batches=batches, seq=seq, iters=2,
        max_batch=max(batches), return_times=True)
    for b, t in sorted(curve.items()):
        rows.append({"kind": "service_time", "arch": cfg.name,
                     "batch": b, "seconds": t})

    with warnings.catch_warnings():
        # CPU backends warn that donated buffers were not usable
        warnings.simplefilter("ignore")
        for b in batches:
            bb, tps, dt = SV.measure_decode_tps(
                cfg, params, mode, b, s_max=max(2 * seq, 64),
                num_tokens=decode_tokens, iters=2)
            rows.append({"kind": "tokens_per_s", "arch": cfg.name,
                         "batch": bb, "num_tokens": decode_tokens,
                         "tokens_per_s": tps, "seconds": dt})

    for r in AT.tune_arch(cfg, m_values=tile_m):
        r = dict(r)
        r["kind"] = "chosen_tile"
        rows.append(r)
    rows.extend(engine_rows(arch, quant=quant))
    # EVERY registry family through the same slot engine (the paper's
    # all-NN-families serving argument): compact per-family rows — the
    # encdec/vlm entries decode behind per-slot primed cross-K/V, so
    # their ttft columns include the prime dispatch cost
    for fam_arch in ("qwen2-moe-a2.7b", "mamba2-1.3b", "recurrentgemma-9b",
                     "whisper-medium", "llama-3.2-vision-90b"):
        rows.extend(engine_rows(fam_arch, quant=quant, rates=(400.0,),
                                n_requests=10, num_slots=4, prompt_len=6,
                                gen_tokens=4))
    # the paged-KV engine row: block-table decode with a shared system
    # prompt, so the memory columns show block reuse under load
    rows.extend(engine_rows(arch, quant=quant, rates=(800.0,),
                            n_requests=16, num_slots=4, prompt_len=6,
                            gen_tokens=6, block_size=4,
                            shared_prefix_len=4))
    # the multi-tenant row: bursty MMPP two-class trace under per-class
    # quotas + preemption — per-class p99/ttft and goodput-under-SLO
    rows.extend(two_class_rows(arch, quant=quant))
    # multi-model multiplexing: two dedicated engines vs one multiplexed
    # engine on the same per-model offered rates — the +2model row's
    # combined occupancy beats either +dedicated row's
    rows.extend(multiplex_rows(quant=quant))
    # the speculative rows, paired with the default rate-800 row above
    # (same arch, same trace) so the accepted_per_dispatch/ticks columns
    # show what draft-and-verify buys: the full-depth self-draft is the
    # mechanical upper bound (every proposal accepted, ticks cut by
    # ~spec_k+1), the 1-layer self-draft is the realistic cheap proposer
    # with partial acceptance — both bit-for-bit the non-spec outputs
    rows.extend(engine_rows(arch, quant=quant, rates=(800.0,), spec_k=3))
    rows.extend(engine_rows(arch, quant=quant, rates=(800.0,), spec_k=3,
                            draft_layers=1))
    # the fleet row: the same trace behind the replica router — two
    # engines, occupancy-projected placement, per-replica columns
    rows.extend(router_rows(arch, quant=quant))
    return rows


def _downsample(xs, n=32):
    if len(xs) <= n:
        return list(xs)
    step = (len(xs) - 1) / (n - 1)      # endpoints kept: the curve's
    return [xs[round(i * step)] for i in range(n)]   # drain-down is visible


def engine_rows(arch: str = "starcoder2-3b", *, quant: str = "w8a16",
                rates=(200.0, 800.0), n_requests: int = 24,
                num_slots: int = 8, prompt_len: int = 3,
                gen_tokens: int = 6, prefill_chunk: int = 4,
                block_size=None, num_blocks=None,
                shared_prefix_len: int = 0,
                spec_k: int = 0, draft_layers=None):
    """Continuous-batching engine rows: p99 + occupancy + admission-to-
    first-token vs offered rate, for any token-only decode family.
    ``block_size`` switches the engine to the paged KV cache (and
    ``shared_prefix_len`` gives the trace a common system prompt whose
    blocks the paged engine shares across requests).  ``spec_k`` turns on
    per-slot draft-and-verify speculative decoding with a truncated
    self-draft of ``draft_layers`` layers (default: full depth, the
    accept-everything upper bound)."""
    import jax

    from repro import engine as E
    from repro.configs import get_config
    from repro.core.qlinear import FP, W8A16, W8A8
    from repro.core.quant import quantize_tree
    from repro.models import registry as R

    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[quant]
    cfg = dataclasses.replace(get_config(arch).reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    if mode.enabled:
        params = quantize_tree(params, min_size=2048)
    dl = (draft_layers or cfg.n_layers) if spec_k else None
    eng = E.Engine(cfg, params, mode=mode, num_slots=num_slots,
                   max_seq=prompt_len + gen_tokens,   # Engine rounds up
                   prefill_chunk=prefill_chunk or None,
                   block_size=block_size, num_blocks=num_blocks,
                   spec_k=spec_k, draft_layers=dl)
    # encdec/vlm: per-request sources for the prime dispatch (their ttft
    # columns therefore include the prime cost)
    source_shape = R.source_shape(cfg)

    # warm the jit cache first (the first serve pays trace+compile), then
    # measure the real per-tick cost on a second wall-clock run and replay
    # each offered rate under the virtual clock at that tick cost —
    # deterministic shape, real steady-state timing
    warm_reqs = E.synthetic_requests(
        max(4, num_slots), rate_per_s=1e6, vocab=cfg.vocab,
        prompt_len=prompt_len, max_new_tokens=gen_tokens,
        source_shape=source_shape)
    eng.serve(warm_reqs, clock="wall")
    warm = eng.serve(warm_reqs, clock="wall")
    tick_s = warm.wall_s / max(warm.ticks, 1)

    rows = []
    for rate in rates:
        reqs = E.synthetic_requests(
            n_requests, rate_per_s=rate, vocab=cfg.vocab,
            prompt_len=prompt_len, max_new_tokens=gen_tokens,
            shared_prefix_len=shared_prefix_len,
            source_shape=source_shape)
        rep = eng.serve(reqs, clock="virtual", tick_s=tick_s)
        rows.append(_engine_row(cfg, rate, n_requests, rep,
                                draft_layers=dl or 0))
    return rows


def _engine_row(cfg, rate, n_requests, rep, draft_layers: int = 0,
                model=None, replicas: int = 1, tp: int = 1,
                replica_occupancy=None):
    """One BENCH engine row from an EngineReport (schema pinned by
    tests/test_bench_smoke.py).  ``model`` labels the row's lane story:
    a lane tag for a dedicated single-model engine in a multiplex
    comparison, a "+"-joined tag list for a multiplexed engine, None
    for ordinary single-model rows.  ``replicas``/``tp``/
    ``replica_occupancy`` are the fleet columns: 1/1/{} everywhere
    except the ``+router`` rows built by :func:`router_rows`."""
    return {
        # fleet columns (scale-out rows only; the defaults mean "one
        # engine, one device" — today's rows byte-identically)
        "replicas": replicas, "tp": tp,
        "replica_occupancy": dict(replica_occupancy or {}),
        "kind": "engine", "arch": cfg.name, "family": cfg.family,
        "model": model,
        # per-model columns (populated on multiplexed engines; empty
        # dicts everywhere else — the keys are always present)
        "model_p99_s": dict(rep.model_p99_latency_s),
        "model_mean_ttft_s": dict(rep.model_mean_ttft_s),
        "model_goodput_tokens_per_s": dict(rep.model_goodput_tokens_per_s),
        "model_mean_occupancy": dict(rep.model_mean_occupancy),
        "rate": rate,
        "n_requests": n_requests, "num_slots": rep.num_slots,
        "p99_s": rep.p99_latency_s,
        "tokens_per_s": rep.tokens_per_s,
        "mean_occupancy": rep.mean_occupancy,
        "ticks": rep.ticks,
        "admissions_while_busy": rep.admissions_while_busy,
        "occupancy_curve": _downsample(rep.occupancy),
        "prefill_chunk": rep.prefill_chunk,
        "mean_ttft_s": rep.mean_ttft_s,
        "p99_ttft_s": rep.p99_ttft_s,
        "block_size": rep.block_size,
        "num_blocks": rep.num_blocks,
        "kv_hbm_bytes": rep.kv_hbm_bytes,
        "peak_blocks_used": rep.peak_blocks_used,
        "mean_block_util": rep.mean_block_util,
        "shared_block_hits": rep.shared_block_hits,
        "shared_hit_rate": rep.shared_hit_rate,
        "prefill_tokens_skipped": rep.prefill_tokens_skipped,
        "effective_concurrency": rep.effective_concurrency,
        # overload robustness: per-SLO-class tails + the honest metric
        # at scale (goodput counts only completed-on-time requests)
        "class_p99_latency_s": dict(rep.class_p99_latency_s),
        "class_mean_ttft_s": dict(rep.class_mean_ttft_s),
        "class_p99_ttft_s": dict(rep.class_p99_ttft_s),
        "goodput_tokens_per_s": rep.goodput_tokens_per_s,
        "slo_attainment": rep.slo_attainment,
        "preempted": rep.preempted,
        "dropped": rep.dropped,
        "failed": rep.failed,
        "unfinished": rep.unfinished,
        # speculative decoding: tokens committed per verify dispatch
        # (exactly 1.0 when spec_k == 0 — the accounting's fixed point)
        # and the honest per-token latency that makes the win legible
        "spec_k": rep.spec_k,
        "draft_layers": draft_layers,
        "accepted_per_dispatch": rep.accepted_per_dispatch,
        "latency_per_token_s": rep.latency_per_token_s,
    }


def two_class_rows(arch: str = "starcoder2-3b", *, quant: str = "w8a16",
                   rate: float = 800.0, n_requests: int = 24,
                   num_slots: int = 4, batch_quota: int = 2):
    """The multi-tenant BENCH row: a bursty MMPP two-class trace served
    under per-class slot quotas with preemption on, so the per-class
    columns diverge (interactive holds its tail while batch absorbs the
    overload) and the goodput/SLO-attainment columns mean something."""
    import jax

    from benchmarks import traces as TR
    from repro import engine as E
    from repro.configs import get_config
    from repro.core import batching as bt
    from repro.core.qlinear import FP, W8A16, W8A8
    from repro.core.quant import quantize_tree
    from repro.models import registry as R

    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[quant]
    cfg = dataclasses.replace(get_config(arch).reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    if mode.enabled:
        params = quantize_tree(params, min_size=2048)
    policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=num_slots,
                                max_wait_s=0.0,
                                class_quotas={"batch": batch_quota})
    eng = E.Engine(cfg, params, mode=mode, num_slots=num_slots,
                   max_seq=24, prefill_chunk=4, block_size=4,
                   policy=policy)
    reqs = TR.two_class_trace(n_requests, rate_per_s=rate, vocab=cfg.vocab,
                              seed=0, interactive_deadline_s=0.05,
                              batch_deadline_s=2.0,
                              prompt_len=(2, 8), max_new_tokens=(2, 8))
    # first wall serve pays trace+compile; measure the real per-tick
    # cost on the second and replay under the virtual clock (same
    # discipline as engine_rows — deadlines are meaningless against a
    # tick cost that includes compilation)
    eng.serve(reqs[:num_slots], clock="wall")
    warm = eng.serve(reqs[:num_slots], clock="wall")
    tick_s = warm.wall_s / max(warm.ticks, 1)
    rep = eng.serve(reqs, clock="virtual", tick_s=tick_s, preemption=True)
    row = _engine_row(cfg, rate, n_requests, rep)
    row["arch"] = cfg.name + "+2class"
    return [row]


def _multiplex_pair(quant: str = "w8a16"):
    """The two lanes the multiplex BENCH/smoke stories share: a reduced
    dense arch and a reduced MoE arch with their params (quantized per
    ``quant``), as ``(tag, cfg, params)`` triples."""
    import jax

    from repro.configs import get_config
    from repro.core.qlinear import FP, W8A16, W8A8
    from repro.core.quant import quantize_tree
    from repro.models import registry as R

    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[quant]
    out = []
    for tag, arch, seed in (("a", "starcoder2-3b", 0),
                            ("b", "qwen2-moe-a2.7b", 1)):
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  kv_quant=True)
        params = R.init(jax.random.PRNGKey(seed), cfg)
        if mode.enabled:
            params = quantize_tree(params, min_size=2048)
        out.append((tag, cfg, params))
    return mode, out


def multiplex_rows(*, quant: str = "w8a16", rate: float = 600.0,
                   n_requests: int = 16, num_slots: int = 4):
    """The multi-model BENCH rows: each lane served by a dedicated
    engine at its offered rate (arch suffix ``+dedicated``), then BOTH
    lanes multiplexed on ONE engine at the SAME per-model offered rates
    (suffix ``+2model``).  The multiplexed row's combined occupancy must
    beat either dedicated row's — the whole point of leasing one slot
    budget across models instead of static partitioning; the smoke gate
    and tests/test_bench_smoke.py assert exactly that."""
    from repro import engine as E

    mode, pair = _multiplex_pair(quant)
    per_model = {}
    for tag, cfg, params in pair:
        per_model[tag] = E.synthetic_requests(
            n_requests, rate_per_s=rate, vocab=cfg.vocab, prompt_len=4,
            max_new_tokens=6, seed=ord(tag), model=tag)
    # unique rids across the merged trace; the dedicated replays serve
    # the SAME offset-rid requests so prompts match token for token
    per_model["b"] = [dataclasses.replace(r, rid=r.rid + 1000)
                      for r in per_model["b"]]

    rows = []
    dedicated_occ = {}
    for tag, cfg, params in pair:
        eng = E.Engine(cfg, params, mode=mode, num_slots=num_slots,
                       max_seq=16, prefill_chunk=4, block_size=4)
        sub = [dataclasses.replace(r, model=None) for r in per_model[tag]]
        eng.serve(sub[:num_slots], clock="wall")
        warm = eng.serve(sub[:num_slots], clock="wall")
        tick_s = warm.wall_s / max(warm.ticks, 1)
        rep = eng.serve(sub, clock="virtual", tick_s=tick_s)
        dedicated_occ[tag] = rep.mean_occupancy
        row = _engine_row(cfg, rate, n_requests, rep, model=tag)
        row["arch"] = cfg.name + "+dedicated"
        rows.append(row)

    meng = E.Engine(models={t: (c, p) for t, c, p in pair}, mode=mode,
                    num_slots=num_slots, max_seq=16, prefill_chunk=4,
                    block_size=4)
    merged = sorted(per_model["a"] + per_model["b"],
                    key=lambda r: r.arrival_s)
    meng.serve(merged[:num_slots], clock="wall")
    warm = meng.serve(merged[:num_slots], clock="wall")
    tick_s = warm.wall_s / max(warm.ticks, 1)
    mrep = meng.serve(merged, clock="virtual", tick_s=tick_s)
    if mrep.mean_occupancy <= max(dedicated_occ.values()):
        raise AssertionError(
            f"multiplexed occupancy {mrep.mean_occupancy:.3f} does not "
            f"beat the dedicated engines' {dedicated_occ} at the same "
            "offered rates — slot leasing is not consolidating load")
    row = _engine_row(pair[0][1], 2 * rate, 2 * n_requests, mrep,
                      model="a+b")
    row["arch"] = pair[0][1].name + "+2model"
    rows.append(row)
    return rows


def _router_row(cfg, rate, n_requests, rrep, *, replicas, tp,
                draft_layers=0):
    """One BENCH engine row for a routed fleet: fleet-level tails and
    throughput from the RouterReport, capacity/accounting columns summed
    or averaged across the per-replica EngineReports, and the fleet
    columns (``replicas``/``tp``/``replica_occupancy``) filled in."""
    import numpy as np

    from repro.core import batching as bt

    reps = list(rrep.replicas.values())
    row = _engine_row(cfg, rate, n_requests, reps[0],
                      draft_layers=draft_layers, replicas=replicas,
                      tp=tp, replica_occupancy=rrep.replica_occupancy)
    mean = lambda xs: float(np.mean(xs))
    ttfts = [r.ttft_s for r in rrep.results if r.emitted]
    row.update({
        "p99_s": rrep.p99_latency_s,
        "tokens_per_s": rrep.tokens_per_s,
        "goodput_tokens_per_s": rrep.goodput_tokens_per_s,
        "mean_ttft_s": rrep.mean_ttft_s,
        "p99_ttft_s": bt.p99(ttfts),
        "ticks": sum(r.ticks for r in reps),
        "admissions_while_busy": sum(r.admissions_while_busy
                                     for r in reps),
        "mean_occupancy": mean([r.mean_occupancy for r in reps]),
        "occupancy_curve": _downsample(
            [x for r in reps for x in r.occupancy]),
        "kv_hbm_bytes": sum(r.kv_hbm_bytes for r in reps),
        "peak_blocks_used": max(r.peak_blocks_used for r in reps),
        "mean_block_util": mean([r.mean_block_util for r in reps]),
        "shared_block_hits": sum(r.shared_block_hits for r in reps),
        "shared_hit_rate": mean([r.shared_hit_rate for r in reps]),
        "prefill_tokens_skipped": sum(r.prefill_tokens_skipped
                                      for r in reps),
        "effective_concurrency": sum(r.effective_concurrency
                                     for r in reps),
        "slo_attainment": mean([r.slo_attainment for r in reps]),
        "preempted": sum(r.preempted for r in reps),
        "dropped": sum(r.dropped for r in reps),
        "failed": sum(r.failed for r in reps),
        "unfinished": sum(r.unfinished for r in reps),
        "accepted_per_dispatch": mean([r.accepted_per_dispatch
                                       for r in reps]),
        "latency_per_token_s": mean([r.latency_per_token_s
                                     for r in reps]),
    })
    # per-class tails: the fleet's honest (conservative) view is the
    # worst replica's tail per class
    for key in ("class_p99_latency_s", "class_mean_ttft_s",
                "class_p99_ttft_s"):
        merged = {}
        for r in reps:
            for cls, v in getattr(r, key).items():
                merged[cls] = max(merged.get(cls, 0.0), v)
        row[key] = merged
    return row


def router_rows(arch: str = "starcoder2-3b", *, quant: str = "w8a16",
                rate: float = 800.0, n_requests: int = 32,
                num_slots: int = 4, replicas: int = 2):
    """The ``+router`` BENCH row: the engine trace served by a
    :class:`repro.engine.ReplicaRouter` over ``replicas`` identically-
    configured engines — same virtual-clock discipline as
    ``engine_rows`` (tick cost measured on one warmed replica, then the
    fleet replayed deterministically), with per-replica occupancy in
    the fleet columns."""
    import jax

    from repro import engine as E
    from repro.configs import get_config
    from repro.core.qlinear import FP, W8A16, W8A8
    from repro.core.quant import quantize_tree
    from repro.models import registry as R

    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[quant]
    cfg = dataclasses.replace(get_config(arch).reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    if mode.enabled:
        params = quantize_tree(params, min_size=2048)
    engines = [E.Engine(cfg, params, mode=mode, num_slots=num_slots,
                        max_seq=16, prefill_chunk=4, block_size=4)
               for _ in range(replicas)]
    warm_reqs = E.synthetic_requests(num_slots, rate_per_s=1e6,
                                     vocab=cfg.vocab, prompt_len=3,
                                     max_new_tokens=6)
    engines[0].serve(warm_reqs, clock="wall")
    warm = engines[0].serve(warm_reqs, clock="wall")
    tick_s = warm.wall_s / max(warm.ticks, 1)
    router = E.ReplicaRouter(engines)
    reqs = E.synthetic_requests(n_requests, rate_per_s=rate,
                                vocab=cfg.vocab, prompt_len=3,
                                max_new_tokens=6)
    rrep = router.serve(reqs, clock="virtual", tick_s=tick_s)
    if rrep.refused:
        raise AssertionError(f"router BENCH row refused {rrep.refused} "
                             "requests on an uncapped fleet")
    row = _router_row(cfg, rate, n_requests, rrep, replicas=replicas,
                      tp=1)
    row["arch"] = cfg.name + "+router"
    return [row]


def router_smoke() -> dict:
    """The fleet gate (``benchmarks/run.py --smoke``): 2 replicas x 2
    model lanes behind the replica router, a bursty two-model two-class
    trace with preemption and tight per-lane block pools.  The
    invariants:

    - routed outputs are bit-for-bit each lane's own sequential
      reference (placement is invisible in the tokens: replicas share
      no device state — decode-contract rule 9);
    - nothing is lost (one typed result per request) and every
      replica's block pools drain clean (``leaked_blocks == 0``
      summed over the fleet);
    - both replicas actually took work (the projection spreads load
      instead of degenerating to replica 0)."""
    from benchmarks import traces as TR
    from repro import engine as E

    mode, pair = _multiplex_pair("w8a16")
    cfgs = {t: c for t, c, _ in pair}
    prms = {t: p for t, _, p in pair}
    reqs = TR.two_class_trace(
        160, rate_per_s=2000.0, vocab=0, seed=7,
        interactive_deadline_s=1e9, batch_deadline_s=1e9,
        prompt_len=(2, 8), max_new_tokens=(2, 6),
        arrival=TR.mmpp_process(dwell_s=(0.05, 0.0125)),
        models=[(t, cfgs[t].vocab) for t, _, _ in pair])
    want = {}
    for t in cfgs:
        sub = [dataclasses.replace(r, model=None)
               for r in reqs if r.model == t]
        want[t] = E.reference_outputs(cfgs[t], prms[t], sub, max_seq=16)

    engines = [E.Engine(models={t: (cfgs[t], prms[t]) for t in cfgs},
                        mode=mode, num_slots=4, max_seq=16,
                        prefill_chunk=4, block_size=4, num_blocks=13)
               for _ in range(2)]
    router = E.ReplicaRouter(engines)
    rep = router.serve(reqs, clock="virtual", tick_s=1e-3,
                       preemption=True)
    if len(rep.results) != len(reqs):
        raise AssertionError(
            f"router smoke lost requests: {len(rep.results)}/{len(reqs)}")
    if rep.refused:
        raise AssertionError(f"router smoke refused {rep.refused} "
                             "requests on an uncapped fleet")
    if rep.leaked_blocks != 0:
        raise AssertionError(f"router smoke leaked {rep.leaked_blocks} "
                             "KV blocks across the fleet")
    if min(rep.replica_requests.values()) <= 0:
        raise AssertionError(
            f"router smoke starved a replica: {rep.replica_requests}")
    bad = [r.rid for r in rep.results
           if r.status == "ok" and r.tokens != want[r.model][r.rid]]
    if bad:
        raise AssertionError(
            f"routed outputs diverge from per-model references for rids "
            f"{bad[:8]} — placement is not invisible in the tokens")
    return {"requests": len(rep.results),
            "replicas": len(engines),
            "replica_requests": dict(rep.replica_requests),
            "replica_occupancy": {n: round(v, 3) for n, v
                                  in rep.replica_occupancy.items()},
            "preempted": sum(r.preempted for r in rep.replicas.values()),
            "leaked_blocks": rep.leaked_blocks,
            "goodput_tokens_per_s": rep.goodput_tokens_per_s}


def sharded_smoke() -> dict:
    """The tensor-parallel gate (``benchmarks/run.py --smoke``): the
    sharded executor must be bit-for-bit the single-device engine on
    the same trace.  With one visible device the tp=1 conformance pair
    still runs (same shard_map plumbing, 1-way mesh); the multi-device
    pair needs a forced host mesh
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``, set before
    jax starts) and reports itself skipped otherwise — the full
    per-family 200-request gates live in tests/test_sharded.py."""
    import jax

    from repro import engine as E
    from repro.configs import get_config
    from repro.models import registry as R

    ndev = len(jax.devices())
    tp = min(4, ndev)
    cfg = dataclasses.replace(
        get_config("starcoder2-3b").reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    reqs = E.synthetic_requests(24, rate_per_s=2000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5)
    kw = dict(num_slots=4, max_seq=16, prefill_chunk=2, block_size=4)
    r1 = E.Engine(cfg, params, **kw).serve(reqs, tick_s=1e-3)
    r2 = E.Engine(cfg, params, backend=E.ShardedExecutor(tp=tp),
                  **kw).serve(reqs, tick_s=1e-3)
    if r1.outputs() != r2.outputs():
        raise AssertionError(
            f"sharded executor (tp={tp}) outputs diverge from the "
            "single-device engine — slot-axis sharding lost bit parity")
    return {"tp": tp, "devices": ndev,
            "requests": len(r2.results),
            "multi_device": tp > 1,
            "skipped_multi": (None if tp > 1 else
                              "1 visible device; force a mesh with "
                              "XLA_FLAGS="
                              "--xla_force_host_platform_device_count=4")}


def multiplex_smoke() -> dict:
    """The multi-model gate (``benchmarks/run.py --smoke``): two model
    families multiplexed on one engine through a bursty two-model
    two-class trace, with preemption, an under-provisioned per-lane
    block pool, and a seeded fault plan striking across lanes.  The
    invariants:

    - per-model outputs are bit-for-bit each lane's own sequential
      reference for every non-failed completed request (cross-model
      interleaving, preemption, and faults are invisible in the tokens);
    - nothing is lost (one typed result per request) and both lanes'
      block pools drain clean (``leaked_blocks == 0`` summed);
    - prefix keys are model-fingerprinted (the same token prompt hashes
      to different chains on different lanes), so paged sharing cannot
      cross models even before the lane-private pools make it
      structurally impossible;
    - the multiplexed occupancy consolidation holds (the
      ``multiplex_rows`` comparison runs as part of the gate)."""
    from benchmarks import traces as TR
    from repro import engine as E

    mode, pair = _multiplex_pair("w8a16")
    cfgs = {t: c for t, c, _ in pair}
    prms = {t: p for t, _, p in pair}
    reqs = TR.two_class_trace(
        160, rate_per_s=2000.0, vocab=0, seed=7,
        interactive_deadline_s=1e9, batch_deadline_s=1e9,
        prompt_len=(2, 8), max_new_tokens=(2, 6),
        arrival=TR.mmpp_process(dwell_s=(0.05, 0.0125)),
        models=[(t, cfgs[t].vocab) for t, _, _ in pair])
    want = {}
    for t in cfgs:
        sub = [dataclasses.replace(r, model=None)
               for r in reqs if r.model == t]
        want[t] = E.reference_outputs(cfgs[t], prms[t], sub, max_seq=16)

    eng = E.Engine(models={t: (cfgs[t], prms[t]) for t in cfgs},
                   mode=mode, num_slots=4, max_seq=16, prefill_chunk=4,
                   block_size=4, num_blocks=13)
    plan = E.FaultPlan.random(seed=42, n_faults=12, max_tick=300,
                              num_slots=8)   # global ids span 2 lanes
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3, preemption=True,
                    fault_plan=plan)
    if len(rep.results) != len(reqs):
        raise AssertionError(
            f"multiplex smoke lost requests: {len(rep.results)}/{len(reqs)}")
    if rep.leaked_blocks != 0:
        raise AssertionError(f"multiplex smoke leaked {rep.leaked_blocks} "
                             "KV blocks across lanes")
    if rep.preempted <= 0:
        raise AssertionError("multiplex smoke never preempted: the "
                             "per-lane block pools are not tight enough")
    if not plan.fired:
        raise AssertionError("no scheduled fault fired across the lanes")
    bad = [r.rid for r in rep.results
           if r.status == "ok" and r.tokens != want[r.model][r.rid]]
    if bad:
        raise AssertionError(
            f"multiplexed outputs diverge from per-model references for "
            f"rids {bad[:8]} — model state is leaking across lanes")
    probe = next(r for r in reqs
                 if r.model == "a" and len(r.prompt) >= 4)
    ka = eng.lanes["a"]._prefix_keys(dataclasses.replace(probe, model=None))
    kb = eng.lanes["b"]._prefix_keys(dataclasses.replace(probe, model=None))
    if ka == kb:
        raise AssertionError("prefix keys are not model-fingerprinted: "
                             "identical prompts hash equal across lanes")
    mrows = multiplex_rows(quant="w8a16")
    occ = {r["arch"].rsplit("+", 1)[1]: r["mean_occupancy"]
           for r in mrows}
    return {"requests": len(rep.results),
            "preempted": rep.preempted,
            "failed": rep.failed,
            "faults_fired": len(plan.fired),
            "leaked_blocks": rep.leaked_blocks,
            "model_mean_occupancy": dict(rep.model_mean_occupancy),
            "multiplexed_occupancy": occ.get("2model"),
            "goodput_tokens_per_s": rep.goodput_tokens_per_s}


def engine_smoke(n_requests: int = 12) -> dict:
    """Offline smoke: a short continuous-batching run whose outputs must
    match the sequential per-token reference bit-for-bit (per-token AND
    chunked prefill; dense AND a recurrent family AND an
    encoder-conditioned family through its prime dispatch), plus an
    interpret-mode parity check of the fused decode-attention kernel's
    append path (current-token k/v operand).  Exercised by
    ``benchmarks/run.py --smoke`` so cost-engine or kernel regressions
    surface in the smoke gate.

    The paged-KV gates ride along: (1) a 200-request pseudo-Poisson
    shared-prefix trace served from KV blocks behind per-slot block
    tables (chunked prefill, slot AND block reuse, shared-prefix blocks
    refcounted across tenants) must match the sequential reference
    bit-for-bit; (2) so must a prime family (whisper) through the same
    paged path; (3) a trace whose live requests exceed what the block
    budget could hold contiguously must complete under blocks-limited
    admission; (4) the block-gather decode-attention kernel must match
    ``kernels/ref.py`` under the Pallas interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import engine as E
    from repro.configs import get_config
    from repro.kernels import ops, ref
    from repro.models import registry as R

    cfg = dataclasses.replace(
        get_config("starcoder2-3b").reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    reqs = E.synthetic_requests(n_requests, rate_per_s=2000.0,
                                vocab=cfg.vocab, prompt_len=3,
                                max_new_tokens=5)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    # explicit raises (not asserts): the gate must hold under python -O
    if rep.outputs() != want:
        raise AssertionError("engine outputs != sequential reference")
    if rep.admissions_while_busy <= 0:
        raise AssertionError("no mid-generation admissions: the engine "
                             "is not batching continuously")
    chunked = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2)
    repc = chunked.serve(reqs, clock="virtual", tick_s=1e-3)
    if repc.outputs() != want:
        raise AssertionError("chunked-prefill outputs != per-token "
                             "reference")
    if repc.mean_ttft_s >= rep.mean_ttft_s:
        raise AssertionError("chunked prefill did not cut "
                             "admission-to-first-token")
    # a recurrent family through the same slot engine (reset-at-zero
    # scrub + frozen inactive state)
    scfg = get_config("mamba2-1.3b").reduced()
    sparams = R.init(jax.random.PRNGKey(1), scfg)
    sreqs = E.synthetic_requests(6, rate_per_s=2000.0, vocab=scfg.vocab,
                                 prompt_len=4, max_new_tokens=3)
    srep = E.Engine(scfg, sparams, num_slots=2, max_seq=16,
                    prefill_chunk=2).serve(sreqs, clock="virtual",
                                           tick_s=1e-3)
    if srep.outputs() != E.reference_outputs(scfg, sparams, sreqs,
                                             max_seq=16):
        raise AssertionError("ssm engine outputs != sequential reference")
    # an encoder-conditioned family through the same slot engine: a prime
    # dispatch writes each request's cross-K/V into its slot row at
    # admission, and slot reuse across tenants must stay bit-for-bit
    wcfg = get_config("whisper-medium").reduced()
    wparams = R.init(jax.random.PRNGKey(2), wcfg)
    wreqs = E.synthetic_requests(
        6, rate_per_s=2000.0, vocab=wcfg.vocab, prompt_len=3,
        max_new_tokens=3, source_shape=R.source_shape(wcfg))
    wrep = E.Engine(wcfg, wparams, num_slots=2, max_seq=16).serve(
        wreqs, clock="virtual", tick_s=1e-3)
    if wrep.outputs() != E.reference_outputs(wcfg, wparams, wreqs,
                                             max_seq=16):
        raise AssertionError("encdec engine outputs != sequential "
                             "reference (primed cross-K/V slot path)")

    # ---- paged-KV gates ----------------------------------------------
    # (1) the acceptance trace: 200 pseudo-Poisson requests with a shared
    # system-prompt prefix through the paged engine (blocks + tables +
    # chunked prefill + refcounted prefix sharing), bit-for-bit vs the
    # sequential reference, with slot reuse AND block reuse exercised
    preqs = E.synthetic_requests(200, rate_per_s=2000.0, vocab=cfg.vocab,
                                 prompt_len=6, max_new_tokens=5,
                                 shared_prefix_len=4)
    pwant = E.reference_outputs(cfg, params, preqs, max_seq=16)
    peng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                    prefill_chunk=4, block_size=4)
    prep = peng.serve(preqs, clock="virtual", tick_s=1e-3)
    if prep.outputs() != pwant:
        raise AssertionError("paged engine outputs != sequential "
                             "reference (200-request shared-prefix trace)")
    if prep.shared_block_hits <= 0:
        raise AssertionError("paged engine shared no prefix blocks on a "
                             "shared-prefix trace")
    if prep.admissions_while_busy <= 0:
        raise AssertionError("paged engine admitted nothing mid-flight")
    # (2) a prime family (encdec) through the same paged path
    pwrep = E.Engine(wcfg, wparams, num_slots=2, max_seq=16,
                     prefill_chunk=2, block_size=4).serve(
        wreqs, clock="virtual", tick_s=1e-3)
    if pwrep.outputs() != E.reference_outputs(wcfg, wparams, wreqs,
                                              max_seq=16):
        raise AssertionError("paged encdec outputs != sequential "
                             "reference")
    # (3) blocks-limited admission: 8 slots but only 16 usable blocks
    # (what 4 contiguous rows would hold) — more live requests than the
    # contiguous pool could serve, and every request still completes
    lreqs = E.synthetic_requests(20, rate_per_s=5000.0, vocab=cfg.vocab,
                                 prompt_len=6, max_new_tokens=5)
    lwant = E.reference_outputs(cfg, params, lreqs, max_seq=16)
    lrep = E.Engine(cfg, params, num_slots=8, max_seq=16, prefill_chunk=4,
                    block_size=4, num_blocks=17).serve(
        lreqs, clock="virtual", tick_s=1e-3)
    if lrep.outputs() != lwant or len(lrep.results) != len(lreqs):
        raise AssertionError("blocks-limited paged engine failed to "
                             "complete the trace bit-for-bit")
    if max(lrep.occupancy) <= 4:
        raise AssertionError("blocks-limited trace never exceeded the "
                             "contiguous-equivalent concurrency")
    if lrep.peak_blocks_used > 16:
        raise AssertionError("paged engine overran the block budget")

    # (4) block-gather kernel parity, Pallas interpreter (offline-safe)
    rng = np.random.default_rng(3)
    nb, bs_, bq, mb, kvp, gq, hdp = 5, 128, 2, 2, 2, 2, 64
    pq = jnp.asarray(rng.standard_normal((bq, kvp, gq, hdp)), jnp.float32)
    pk = jnp.asarray(rng.integers(-127, 127, (nb, bs_, kvp, hdp)), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 127, (nb, bs_, kvp, hdp)), jnp.int8)
    pks = jnp.asarray(rng.uniform(.005, .05, (nb, bs_, kvp, 1)), jnp.float32)
    pvs = jnp.asarray(rng.uniform(.005, .05, (nb, bs_, kvp, 1)), jnp.float32)
    pvl = jnp.asarray([200, 130], jnp.int32)
    ptbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pkn = jnp.asarray(rng.standard_normal((bq, kvp, 1, hdp)), jnp.float32)
    pvn = jnp.asarray(rng.standard_normal((bq, kvp, 1, hdp)), jnp.float32)
    pgot = ops.decode_attention(pq, pk, pv, pks, pvs, pvl,
                                block_tables=ptbl, k_new=pkn, v_new=pvn,
                                interpret=True)
    poracle = ref.decode_attention_paged_ref(pq, pk, pv, pks, pvs, pvl,
                                             ptbl, k_new=pkn, v_new=pvn)
    np.testing.assert_allclose(np.asarray(pgot), np.asarray(poracle),
                               rtol=2e-5, atol=2e-5)

    # append-path kernel parity, Pallas interpreter (offline-safe)
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    b, s, kv, g, hd = 1, 128, 2, 2, 64
    q = jax.random.normal(ks[0], (b, kv, g, hd), jnp.float32)
    kc = jax.random.randint(ks[1], (b, s, kv, hd), -127, 127, jnp.int8)
    vc = jax.random.randint(ks[2], (b, s, kv, hd), -127, 127, jnp.int8)
    ksc = jax.random.uniform(ks[3], (b, s, kv, 1), jnp.float32, .005, .05)
    vsc = jax.random.uniform(ks[4], (b, s, kv, 1), jnp.float32, .005, .05)
    kn = jax.random.normal(ks[5], (b, 1, kv, hd), jnp.float32)
    vn = jax.random.normal(ks[6], (b, 1, kv, hd), jnp.float32)
    got = ops.decode_attention(q, kc, vc, ksc, vsc, jnp.int32(77),
                               k_new=kn, v_new=vn, interpret=True)
    oracle = ref.decode_attention_int8_ref(q, kc, vc, ksc, vsc,
                                           jnp.int32(77), k_new=kn,
                                           v_new=vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    return {"requests": len(rep.results), "ticks": rep.ticks,
            "mean_occupancy": rep.mean_occupancy,
            "admissions_while_busy": rep.admissions_while_busy,
            "mean_ttft_s": rep.mean_ttft_s,
            "chunked_mean_ttft_s": repc.mean_ttft_s,
            "paged_requests": len(prep.results),
            "paged_shared_block_hits": prep.shared_block_hits,
            "paged_prefill_tokens_skipped": prep.prefill_tokens_skipped,
            "paged_limited_peak_occupancy": max(lrep.occupancy)}


def chaos_smoke(n_requests: int = 200) -> dict:
    """The overload/fault gate (``benchmarks/run.py --smoke``): a
    bursty MMPP two-class trace with seeded faults and forced
    preemptions through a deliberately under-provisioned paged engine.
    Must complete with zero uncaught exceptions, and the invariants
    must hold:

    - every request gets exactly one typed result (nothing lost);
    - the block pool drains clean (``leaked_blocks == 0``);
    - preemptions and faults actually fired (the run exercised the
      machinery, not an idle pass);
    - every non-failed completed request's output is bit-for-bit its
      sequential reference (exact resume under chaos);
    - the control arm — same trace, no faults, no preemption, ample
      blocks — stays bit-for-bit the reference too (the machinery
      costs nothing when off).
    """
    import jax

    from benchmarks import traces as TR
    from repro import engine as E
    from repro.configs import get_config
    from repro.core import batching as bt
    from repro.models import registry as R

    cfg = dataclasses.replace(
        get_config("starcoder2-3b").reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    # dwell times scaled to the trace's ~0.1 s horizon so the MMPP
    # actually switches states (the 0.5 s defaults model second-scale
    # burst cycles and would look constant-rate here)
    reqs = TR.two_class_trace(n_requests, rate_per_s=2000.0,
                              vocab=cfg.vocab, seed=7,
                              interactive_deadline_s=1e9,
                              batch_deadline_s=1e9,
                              prompt_len=(2, 8), max_new_tokens=(2, 6),
                              arrival=TR.mmpp_process(
                                  dwell_s=(0.05, 0.0125)))
    times = [r.arrival_s for r in reqs]
    if TR.index_of_dispersion(times, window_s=0.01) <= 1.2:
        raise AssertionError("chaos trace is not bursty (IoD <= 1.2); "
                             "MMPP parameters broken?")
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)

    # chaos arm: tight block pool (forces preemption under pressure),
    # seeded fault plan (dispatch + nan + torn-table), class quotas
    policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                max_wait_s=0.0, class_quotas={"batch": 2})
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4,
                   block_size=4, num_blocks=13, policy=policy)
    plan = E.FaultPlan.random(seed=42, n_faults=12, max_tick=300,
                              num_slots=4)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3, preemption=True,
                    fault_plan=plan)
    if len(rep.results) != n_requests:
        raise AssertionError(
            f"chaos arm lost requests: {len(rep.results)}/{n_requests}")
    if rep.leaked_blocks != 0:
        raise AssertionError(f"chaos arm leaked {rep.leaked_blocks} "
                             "KV blocks")
    if rep.preempted <= 0:
        raise AssertionError("chaos arm never preempted: the block pool "
                             "is not tight enough to exercise eviction")
    if not plan.fired:
        raise AssertionError("no scheduled fault fired: the plan's ticks "
                             "miss the run entirely")
    bad = [r.rid for r in rep.results
           if r.status == "ok" and r.tokens != want[r.rid]]
    if bad:
        raise AssertionError(
            f"chaos arm outputs diverge from reference for rids {bad[:8]}"
            " — exact resume is broken")

    # control arm: same trace, machinery off, ample resources —
    # bit-for-bit parity, nothing preempted, nothing failed
    ctl = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4,
                   block_size=4)
    crep = ctl.serve(reqs, clock="virtual", tick_s=1e-3)
    if crep.outputs() != want:
        raise AssertionError("control arm != sequential reference")
    if crep.preempted or crep.failed or crep.dropped:
        raise AssertionError("control arm triggered robustness machinery "
                             "with faults off")
    return {"requests": len(rep.results),
            "preempted": rep.preempted,
            "failed": rep.failed,
            "faults_fired": len(plan.fired),
            "dispatch_retries": rep.dispatch_retries,
            "nonfinite_samples": rep.nonfinite_samples,
            "torn_rows_repaired": rep.torn_rows_repaired,
            "leaked_blocks": rep.leaked_blocks,
            "goodput_tokens_per_s": rep.goodput_tokens_per_s,
            "slo_attainment": rep.slo_attainment}


def spec_smoke(n_requests: int = 60) -> dict:
    """The speculative-decoding gate (``benchmarks/run.py --smoke``):
    per-slot draft-and-verify must be invisible in the tokens.  Three
    arms, all against the same sequential per-token reference:

    - the full-depth self-draft chaos arm: draft == target, so every
      proposal agrees with the verifier, while a tight paged block pool,
      forced preemptions, and a seeded fault plan tear speculation
      mid-flight — in-flight proposals are uncommitted work, so every
      non-failed output must still be bit-for-bit the reference and the
      block pool must drain clean;
    - the garbage-draft arm: a draft initialised from a different seed
      proposes near-random tokens — acceptance collapses toward 1.0 but
      outputs stay exactly the reference (rejected KV writes are dead);
    - the non-spec control arm: ``spec_k=0`` on the same trace —
      ``accepted_per_dispatch`` exactly 1.0 and strictly more decode
      ticks than the clean full-depth run recorded in the BENCH rows.
    """
    import jax

    from repro import engine as E
    from repro.configs import get_config
    from repro.models import registry as R

    cfg = dataclasses.replace(
        get_config("starcoder2-3b").reduced(), kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    reqs = E.synthetic_requests(
        n_requests, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=3,
        max_new_tokens=5,
        priority=lambda rid: "batch" if rid % 3 == 0 else "interactive")
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)

    # chaos arm: full-depth self-draft under a tight block pool with
    # preemption and seeded faults — speculation torn mid-round must
    # leave nothing committed
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=2,
                   block_size=4, num_blocks=9, spec_k=3,
                   draft_layers=cfg.n_layers)
    plan = E.FaultPlan.random(seed=11, n_faults=8, max_tick=250,
                              num_slots=4)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3, preemption=True,
                    fault_plan=plan)
    if len(rep.results) != n_requests:
        raise AssertionError(
            f"spec chaos arm lost requests: {len(rep.results)}/{n_requests}")
    bad = [r.rid for r in rep.results
           if r.status == "ok" and r.tokens != want[r.rid]]
    if bad:
        raise AssertionError(
            f"spec chaos arm outputs diverge from reference for rids "
            f"{bad[:8]} — speculative state leaked across a preemption "
            "or fault")
    if rep.leaked_blocks != 0:
        raise AssertionError(f"spec chaos arm leaked {rep.leaked_blocks} "
                             "KV blocks")
    if rep.preempted <= 0:
        raise AssertionError("spec chaos arm never preempted: speculation "
                             "was not torn mid-flight")
    if not plan.fired:
        raise AssertionError("no scheduled fault fired during the spec "
                             "chaos arm")
    if rep.accepted_per_dispatch <= 1.0:
        raise AssertionError(
            f"full-depth self-draft committed only "
            f"{rep.accepted_per_dispatch:.2f} tokens/dispatch — "
            "acceptance is broken")

    # garbage-draft arm: the draft proposes noise; rejection must be
    # total recovery (dead KV writes, exact outputs)
    gparams = R.init(jax.random.PRNGKey(666), cfg)
    geng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4,
                    block_size=4, spec_k=3, draft=(cfg, gparams))
    grep = geng.serve(reqs, clock="virtual", tick_s=1e-3)
    if grep.outputs() != want:
        raise AssertionError("garbage-draft outputs != sequential "
                             "reference — rejected KV writes are live")
    if grep.accepted_per_dispatch < 1.0:
        raise AssertionError("accepted_per_dispatch < 1.0: dispatch "
                             "accounting is broken")

    # control arm: spec_k=0, same trace — apd is exactly 1.0 and the
    # outputs match (the machinery costs nothing when off)
    ctl = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4,
                   block_size=4)
    crep = ctl.serve(reqs, clock="virtual", tick_s=1e-3)
    if crep.outputs() != want:
        raise AssertionError("spec control arm != sequential reference")
    if crep.accepted_per_dispatch != 1.0:
        raise AssertionError(
            f"non-speculative accepted_per_dispatch is "
            f"{crep.accepted_per_dispatch}, must be exactly 1.0")
    return {"requests": len(rep.results),
            "preempted": rep.preempted,
            "faults_fired": len(plan.fired),
            "failed": rep.failed,
            "leaked_blocks": rep.leaked_blocks,
            "chaos_accepted_per_dispatch": rep.accepted_per_dispatch,
            "garbage_accepted_per_dispatch": grep.accepted_per_dispatch,
            "control_ticks": crep.ticks,
            "latency_per_token_ms": rep.latency_per_token_s * 1e3}


def rows():
    """CSV-style rows for benchmarks/run.py's default suite."""
    out = []
    for r in serving_rows():
        if r["kind"] == "tokens_per_s":
            out.append((f"serving/decode_tps_b{r['batch']}",
                        r["seconds"] * 1e6,
                        f"tokens_per_s={r['tokens_per_s']:.0f}"))
        elif r["kind"] == "service_time":
            out.append((f"serving/service_b{r['batch']}",
                        r["seconds"] * 1e6, "prefill"))
        elif r["kind"] == "engine":
            out.append((f"serving/engine_rate{int(r['rate'])}",
                        r["p99_s"] * 1e6,
                        f"tokens_per_s={r['tokens_per_s']:.0f} "
                        f"occupancy={r['mean_occupancy']:.2f}"))
    return out


ALL = [rows]
