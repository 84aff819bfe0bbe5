"""Serving launcher: the continuous-batching engine, end to end.

The paper's serving story made live: load (or init) a model, post-training
int8 quantization, measure the prefill service-time curve (including
--max-batch, so batch selection interpolates instead of extrapolating),
pick the largest batch meeting the p99 deadline (Table 4 policy), then
size a slot pool at that batch and drive `repro.engine.Engine` against a
pseudo-Poisson request stream under the wall clock: requests are admitted
into free KV-cache slots as they arrive (shared AdmissionPolicy), every
tick advances ALL active slots with one fused slot-masked decode step of
static shape (the deterministic-execution discipline that makes the p99
predictable), and finished slots are reused immediately — no drain
barrier between request generations.  Reports achieved p99, decoded
tokens/s, and slot occupancy.

  python -m repro.launch.serve --arch starcoder2-3b --reduced \
      --deadline-ms 50 --rate 200

EVERY registry family serves through the engine — dense, moe, ssm and
hybrid share the one fused slot step (per-row cache indices), and the
encoder-conditioned families (encdec/vlm) ride the same step behind a
per-slot prime dispatch that writes each request's cross-attention K/V
into its slot row at admission (see docs/serving.md; time-to-first-token
includes the prime cost).  ``--prefill-chunk`` turns on chunked prefill
(admission-to-first-token drops from prompt_len ticks to
``ceil(prompt_len/chunk)``), ``--temperature`` turns on per-row
``fold_in(rng, position)`` sampling, and ``--spec-k`` turns on
draft-and-verify speculative decoding (``--draft-layers n`` drafts with
the target's own first n layers, no second checkpoint; ``--draft ARCH``
uses a separate small model) — committed outputs stay bit-for-bit the
non-speculative stream.  ``--sim`` runs the virtual-time
BatchQueue simulator backend instead (same admission policy, no model
execution) — the Table 4 sanity check.

Overload robustness (docs/serving.md, "Overload & failure semantics"):
``--interactive-frac``/``--batch-quota`` split the trace into SLO
classes under per-class slot quotas, ``--arrival mmpp`` makes arrivals
bursty, ``--preemption`` lets admission evict lower-class slots and
resume them bit-for-bit exactly, and ``--fault-seed`` injects a
deterministic fault plan (dispatch failures, non-finite logits, torn
block-table rows) to exercise the recovery machinery; the report then
adds per-class p99/ttft, goodput-under-SLO, and fault counters.

Multi-model multiplexing (docs/serving.md, "Multi-model multiplexing"):
``--models a,b`` serves several registry archs as lanes of ONE engine —
each lane keeps its own compiled steps, KV cache, and (paged) block
pool, while ``num_slots`` is a single lease budget the lanes share
tick by tick; ``--model-quota TAG=N`` caps one lane's concurrent slots
through the same (model, class) quota keys ``--batch-quota`` uses.
The report adds per-model p99/ttft/goodput/occupancy lines.

  python -m repro.launch.serve --models starcoder2-3b,qwen2-moe-a2.7b \
      --reduced --model-quota starcoder2-3b=4 --rate 200

Scaling out (docs/serving.md, "Scaling out"): ``--tp N`` serves the
slot pool through the tensor-parallel sharded executor — the same
fused steps under ``shard_map`` on an N-way mesh axis, sharded along
the SLOT axis so outputs stay bit-for-bit the single-device engine
(force a CPU mesh offline with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) — and
``--replicas N`` puts N identically-configured engines behind the
:class:`repro.engine.ReplicaRouter` front-end, which places each
request on the lowest-projected-occupancy replica that its own
admission policy would admit.

  python -m repro.launch.serve --arch starcoder2-3b --reduced \
      --replicas 2 --tp 2 --rate 400

The fused multi-token decode
loop is still timed separately (``--decode-tokens``): it remains the
right tool for fixed-length batch completion, while the engine serves
the ragged live stream.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core import batching as bt
from repro.core.qlinear import FP, W8A16, W8A8
from repro.core.quant import tree_weight_bytes
from repro.models import registry as R
from repro.runtime import steps as ST


def measure_service_curve(step_fn, params, cfg, batches=(1, 4, 16),
                          seq=32, iters=3, max_batch=None,
                          return_times=False):
    """Measured service time at several batch sizes -> LatencyModel.

    ``max_batch``: when given, it joins the measured set — the model is
    then an interpolation over the whole batch range ``choose_batch``
    searches, never an extrapolation beyond what was measured.
    """
    if max_batch is not None:
        batches = tuple(sorted(set(batches) | {int(max_batch)}))
    times = {}
    for b in batches:
        # materialize zeros from input_specs so encoder-conditioned
        # families get their stub embeds with the one authoritative
        # shape/dtype (configs/base.py), not a re-implementation here
        spec = ShapeSpec("serve_curve", seq, b, "prefill")
        batch = {k: jnp.zeros(v.shape, v.dtype)
                 for k, v in cfg.input_specs(spec).items()}
        warm = step_fn(params, batch)   # one warmup call, not three
        warm = warm[0] if isinstance(warm, tuple) else warm
        warm.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step_fn(params, batch)
            out = out[0] if isinstance(out, tuple) else out
            out.block_until_ready()
        times[b] = (time.perf_counter() - t0) / iters
    bs = sorted(times)
    b1, b2 = bs[0], bs[-1]
    per_item = max((times[b2] - times[b1]) / (b2 - b1), 1e-9)
    fixed = max(times[b1] - b1 * per_item, 1e-9)
    model = bt.LatencyModel("measured", fixed * 2.0, per_item * 1.5,
                            fixed, per_item)
    return (model, times) if return_times else model


def measure_decode_tps(cfg, params, mode, batch, *, s_max=128,
                       num_tokens=16, iters=3, seed=0):
    """Tokens/s of the fused decode loop for ``batch`` useful requests.

    One jit'd ``lax.scan`` over ``num_tokens`` steps with the KV cache
    donated — the serving hot loop as it actually runs, not a per-token
    Python loop.  The loop executes at the *bucketed* shape (requests are
    padded up to the static ladder), but throughput counts only the
    ``batch`` real requests' tokens, so the reported tok/s is what the
    chosen policy batch actually delivers, padding waste included.
    Returns (bucketed_batch, tokens_per_s, seconds_per_loop).
    """
    b = ST.bucket_batch(batch)
    loop = ST.jit_decode_loop(
        ST.make_decode_loop(cfg, mode=mode, num_tokens=num_tokens))
    tokens = jnp.ones((b, 1), jnp.int32)
    idx = jnp.zeros((), jnp.int32)

    cache = R.init_cache(cfg, b, s_max)
    out, cache = loop(params, tokens, cache, idx)   # compile + warm
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        # cache was donated: reuse the returned buffer, rewound to step 0
        out, cache = loop(params, tokens, cache, idx)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    return b, batch * num_tokens / dt, dt


def _parse_model_quotas(pairs):
    """``--model-quota TAG=N`` occurrences -> ``{tag: n}`` quota keys."""
    quotas = {}
    for p in pairs:
        tag, _, n = p.partition("=")
        if not tag or not n or not n.isdigit() or int(n) < 1:
            raise ValueError(
                f"--model-quota wants TAG=N with N >= 1, got {p!r}")
        quotas[tag] = int(n)
    return quotas


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="single-model serving: one registry arch")
    ap.add_argument("--models", default=None, metavar="A,B",
                    help="multi-model multiplexing: comma-separated "
                         "registry arch names served as lanes of ONE "
                         "engine (each arch name is its lane tag; "
                         "mutually exclusive with --arch).  Every lane "
                         "gets its own --n-requests at --rate; the "
                         "service curve / Table 4 batch choice is "
                         "measured on the FIRST lane")
    ap.add_argument("--model-quota", action="append", default=[],
                    metavar="TAG=N",
                    help="engine: cap one lane at N concurrently leased "
                         "slots (repeatable; composes with "
                         "--batch-quota through the same (model, class) "
                         "quota keys)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="w8a16",
                    choices=["fp", "w8a16", "w8a8"])
    ap.add_argument("--deadline-ms", type=float, default=50.0)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="requests/s for the simulated stream")
    ap.add_argument("--n-requests", type=int, default=200)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--decode-tokens", type=int, default=16,
                    help="steps of the fused decode loop to time "
                         "(0 disables the decode measurement)")
    ap.add_argument("--prompt-len", type=int, default=4,
                    help="engine: synthetic prompt tokens per request")
    ap.add_argument("--gen-tokens", type=int, default=8,
                    help="engine: tokens to generate per request")
    ap.add_argument("--sim", action="store_true",
                    help="run the virtual-time BatchQueue simulator "
                         "backend instead of the live engine")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="engine: chunked-prefill bucket cap (0 = "
                         "per-token prefill)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="engine: paged KV cache block size in positions "
                         "(power of two; 0 = contiguous slot rows)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="engine: physical KV blocks incl. the reserved "
                         "trash block (0 = every slot can hold a full "
                         "row privately)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="engine: identical leading prompt tokens across "
                         "requests (paged mode shares their KV blocks)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="engine: per-row sampling temperature "
                         "(0 = greedy)")
    ap.add_argument("--interactive-frac", type=float, default=1.0,
                    help="engine: fraction of requests in the "
                         "interactive SLO class (rid-hash split; the "
                         "rest are batch class; 1.0 = single-class, "
                         "today's trace byte-identically)")
    ap.add_argument("--batch-quota", type=int, default=0,
                    help="engine: max slots the batch class may hold "
                         "concurrently (0 = no per-class quota)")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "mmpp", "diurnal"],
                    help="engine: arrival process (mmpp = bursty "
                         "2-state Markov-modulated Poisson, diurnal = "
                         "sinusoid-modulated day/night curve, both from "
                         "benchmarks/traces.py; needs the repo root on "
                         "PYTHONPATH)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="engine: speculative decoding proposal depth "
                         "(0 = off); needs --draft or --draft-layers")
    ap.add_argument("--draft", default=None,
                    help="engine: draft arch name (e.g. starcoder2-3b) "
                         "for cross-model speculative decoding; "
                         "inherits --reduced, init'd from --seed+2")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="engine: truncated-layer self-draft depth (uses "
                         "the target's own first n layers, no second "
                         "checkpoint; 0 = off)")
    ap.add_argument("--preemption", action="store_true",
                    help="engine: evict strictly-lower-class slots "
                         "under admission pressure and resume them "
                         "with bit-for-bit exact outputs")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="engine: seed a deterministic FaultPlan "
                         "(dispatch failures, non-finite logits, torn "
                         "block-table rows) to exercise recovery")
    ap.add_argument("--n-faults", type=int, default=8,
                    help="engine: faults in the seeded plan")
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet: serve through a ReplicaRouter over N "
                         "identically-configured engine replicas (each "
                         "with its own slot pool and device state; "
                         "1 = single engine, today's path byte-"
                         "identically)")
    ap.add_argument("--tp", type=int, default=1,
                    help="engine: tensor-parallel width — run the fused "
                         "steps under shard_map on a tp-way mesh axis, "
                         "sharded along the slot axis (bit-identical to "
                         "tp=1; offline, force devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if (args.models is None) == (args.arch is None):
        print("[serve] need exactly one of --arch or --models")
        return 1
    tags = ([t.strip() for t in args.models.split(",") if t.strip()]
            if args.models else [args.arch])
    if len(set(tags)) != len(tags):
        print(f"[serve] --models tags must be unique: {args.models}")
        return 1
    try:
        model_quotas = _parse_model_quotas(args.model_quota)
    except ValueError as e:
        print(f"[serve] {e}")
        return 1
    if unknown := set(model_quotas) - set(tags):
        print(f"[serve] --model-quota names unknown lanes: "
              f"{sorted(unknown)} (lanes: {tags})")
        return 1
    mode = {"fp": FP, "w8a16": W8A16, "w8a8": W8A8}[args.quant]
    lanes = {}
    for i, tag in enumerate(tags):
        lcfg = get_config(tag)
        if args.reduced:
            lcfg = lcfg.reduced()
        lkey = jax.random.PRNGKey(args.seed + i)
        if mode.enabled:
            fp_bytes = tree_weight_bytes(
                jax.eval_shape(lambda k: R.init(k, lcfg), lkey))
            lparams = R.init_quantized(lkey, lcfg)
            print(f"[quant] {tag} weights {fp_bytes / 1e6:.1f} MB -> "
                  f"{tree_weight_bytes(lparams) / 1e6:.1f} MB "
                  f"({args.quant})")
        else:
            lparams = R.init(lkey, lcfg)
        lanes[tag] = (lcfg, lparams)
    # the Table 4 curve / batch choice is measured on the first lane
    cfg, params = lanes[tags[0]]

    prefill = jax.jit(ST.make_prefill_step(cfg, mode=mode))
    model, curve = measure_service_curve(prefill, params, cfg,
                                         seq=args.seq,
                                         max_batch=args.max_batch,
                                         return_times=True)
    deadline = args.deadline_ms * 1e-3
    # the chosen batch stays inside the measured range: max_batch is in
    # the measured set, so the Table 4 policy never extrapolates.
    batch = min(bt.choose_batch(model, deadline, args.max_batch),
                max(curve))
    if batch == 0:
        print(f"[serve] deadline {args.deadline_ms} ms unattainable "
              f"(p99(1) = {model.p99_latency(1) * 1e3:.1f} ms)")
        return 1
    print(f"[serve] service(1)={model.service_time(1)*1e3:.2f} ms  "
          f"chosen batch={batch}  modeled p99={model.p99_latency(batch)*1e3:.2f} ms"
          f"  modeled IPS={model.ips(batch):,.0f}")

    if args.decode_tokens > 0:
        bb, tps, dt = measure_decode_tps(
            cfg, params, mode, batch, s_max=max(args.seq * 2, 64),
            num_tokens=args.decode_tokens, seed=args.seed)
        print(f"[decode] fused loop batch={batch} (shape bucket {bb}) "
              f"{args.decode_tokens} steps in {dt*1e3:.1f} ms -> "
              f"{tps:,.0f} tok/s")

    if args.sim:
        reqs = bt.poisson_arrivals(args.rate, args.n_requests, deadline,
                                   args.seed)
        q = bt.BatchQueue(model.service_time, max_batch=batch)
        recs = q.run(reqs)
        lat = []
        arrival = {r.rid: r.arrival_s for r in reqs}
        for rec in recs:
            for rid in rec.rids:
                lat.append(rec.finish_s - arrival[rid])
        met = np.mean([rec.deadlines_met for rec in recs])
        print(f"[sim] {len(recs)} batches, mean size "
              f"{np.mean([len(r.rids) for r in recs]):.1f}; "
              f"p99 latency {bt.p99(lat)*1e3:.2f} ms "
              f"(deadline {args.deadline_ms} ms); "
              f"batches meeting deadline: {met:.1%}; "
              f"throughput {len(lat)/max(r.finish_s for r in recs):,.0f} "
              f"req/s")
        return 0

    # ---- the live continuous-batching engine -------------------------
    from repro import engine as E
    num_slots = ST.bucket_batch(max(batch, 1))
    quotas = dict(model_quotas)
    if args.batch_quota:
        quotas["batch"] = args.batch_quota
    policy = bt.AdmissionPolicy(model.service_time, max_batch=num_slots,
                                class_quotas=quotas or None)
    draft = None
    if args.draft:
        # cross-model draft: its own (small) checkpoint, same vocab —
        # quantized like the target so both serve in the same mode
        dcfg = get_config(args.draft)
        if args.reduced:
            dcfg = dcfg.reduced()
        dkey = jax.random.PRNGKey(args.seed + 2)
        dparams = (R.init_quantized(dkey, dcfg) if mode.enabled
                   else R.init(dkey, dcfg))
        draft = (dcfg, dparams)
    if args.replicas < 1 or args.tp < 1:
        print(f"[serve] --replicas and --tp must be >= 1 "
              f"(got {args.replicas}, {args.tp})")
        return 1
    backend = None
    if args.tp > 1:
        try:
            backend = E.ShardedExecutor(tp=args.tp)
        except ValueError as e:
            print(f"[serve] --tp rejected: {e}")
            return 1
        print(f"[serve] sharded executor: tp={args.tp} across "
              f"{len(jax.devices())} visible device(s), slot-axis "
              f"sharding (bit-identical to tp=1)")
    eng_kw = dict(mode=mode, num_slots=num_slots,
                  max_seq=args.prompt_len + args.gen_tokens,
                  policy=policy,
                  prefill_chunk=args.prefill_chunk or None,
                  block_size=args.block_size or None,
                  num_blocks=args.num_blocks or None,
                  temperature=args.temperature,
                  rng=(jax.random.PRNGKey(args.seed + 1)
                       if args.temperature > 0 else None),
                  spec_k=args.spec_k, draft=draft,
                  draft_layers=args.draft_layers or None,
                  backend=backend)

    def build_engine(name=None, replica=0):
        kw = dict(eng_kw, name=name)
        if backend is None and args.replicas > 1:
            # one-chip replicas: replica i keeps its params and caches on
            # device i (round-robin when replicas outnumber devices)
            devs = jax.devices()
            kw["backend"] = E.SingleDeviceExecutor(
                device=devs[replica % len(devs)])
        return (E.Engine(models=lanes, **kw) if args.models
                else E.Engine(cfg, params, **kw))

    try:
        eng = build_engine("replica0" if args.replicas > 1 else None)
    except ValueError as e:
        print(f"[engine] config rejected: {e}")
        return 1
    max_seq = eng.max_seq
    arrival_process = None
    if args.arrival != "poisson":
        try:
            from benchmarks import traces as TR
        except ImportError:
            print(f"[engine] --arrival {args.arrival} needs "
                  "benchmarks/traces.py on PYTHONPATH (run from the "
                  "repo root with PYTHONPATH=src:.)")
            return 1
        arrival_process = (TR.mmpp_process() if args.arrival == "mmpp"
                           else TR.diurnal_process())
    frac = args.interactive_frac
    if not 0.0 <= frac <= 1.0:
        print(f"[engine] --interactive-frac must be in [0, 1]: {frac}")
        return 1
    # rid-hash class split, stable under any n (same rule as
    # benchmarks/traces.py::two_class_trace)
    priority = ("interactive" if frac >= 1.0 else
                (lambda rid: "interactive"
                 if (rid * 2654435761) % 1000 < frac * 1000 else "batch"))
    # one sub-trace per lane (each lane draws prompts in its OWN vocab
    # and carries its lane tag; rids offset per lane so the merged
    # trace keys uniquely), merged by arrival — the single-model path
    # is the one-lane case of the same loop, byte-identical to before
    reqs = []
    for i, tag in enumerate(tags):
        lcfg, _ = lanes[tag]
        sub = E.synthetic_requests(
            args.n_requests, rate_per_s=args.rate, vocab=lcfg.vocab,
            prompt_len=args.prompt_len, max_new_tokens=args.gen_tokens,
            deadline_s=deadline, seed=args.seed + i,
            shared_prefix_len=args.shared_prefix_len,
            source_shape=R.source_shape(lcfg),
            priority=priority, arrival_process=arrival_process,
            model=tag if args.models else None)
        reqs.extend(dataclasses.replace(r, rid=r.rid + i * args.n_requests)
                    for r in sub)
    reqs.sort(key=lambda r: r.arrival_s)
    plan = (E.FaultPlan.random(args.fault_seed, n_faults=args.n_faults,
                               num_slots=num_slots)
            if args.fault_seed is not None else None)
    if args.replicas > 1:
        # ---- the replica fleet behind the router front-end ----------
        if plan is not None:
            print("[serve] --fault-seed wants a single engine "
                  "(--replicas 1): a shared FaultPlan would replay the "
                  "same fired list on every replica")
            return 1
        try:
            fleet = [eng] + [build_engine(f"replica{i}", i)
                             for i in range(1, args.replicas)]
        except ValueError as e:
            print(f"[engine] config rejected: {e}")
            return 1
        router = E.ReplicaRouter(fleet)
        for member in fleet:     # compile BEFORE the wall clock starts
            member.warmup()
        rrep = router.serve(reqs, clock="wall",
                            preemption=args.preemption)
        print(f"[router] {args.replicas} replicas x {num_slots} slots "
              f"x {max_seq} positions (tp={args.tp}); "
              f"{len(rrep.results)} requests, {rrep.refused} refused")
        occ = "  ".join(f"{n}={rrep.replica_occupancy[n]:.1%}"
                        f"({rrep.replica_requests[n]} reqs)"
                        for n in rrep.replica_names)
        print(f"[router] fleet p99 {rrep.p99_latency_s*1e3:.2f} ms "
              f"(deadline {args.deadline_ms} ms); "
              f"{rrep.tokens_per_s:,.0f} tok/s decoded, goodput "
              f"{rrep.goodput_tokens_per_s:,.0f} tok/s; "
              f"ttft {rrep.mean_ttft_s*1e3:.2f} ms mean")
        print(f"[router] per-replica occupancy: {occ}")
        if rrep.leaked_blocks:
            print(f"[router] WARNING: {rrep.leaked_blocks} KV blocks "
                  f"leaked across the fleet")
        return 0
    eng.warmup()         # compile before the clock starts: the measured
    try:                                      # p99 is serving, not tracing
        rep = eng.serve(reqs, clock="wall", preemption=args.preemption,
                        fault_plan=plan)
    except E.RequestTooLong as e:
        print(f"[engine] request rejected at admission: {e}")
        return 1
    deadline_of = {r.rid: r.deadline_s for r in reqs}
    met = np.mean([r.finish_s <= deadline_of[r.rid]
                   for r in rep.results]) if rep.results else 0.0
    print(f"[engine] {rep.num_slots} slots x {max_seq} positions; "
          f"{len(rep.results)} requests in {rep.ticks} ticks "
          f"({rep.wall_s:.2f} s wall)")
    print(f"[engine] achieved p99 {rep.p99_latency_s*1e3:.2f} ms "
          f"(deadline {args.deadline_ms} ms, met {met:.1%}); "
          f"{rep.tokens_per_s:,.0f} tok/s decoded; "
          f"slot occupancy {rep.mean_occupancy:.1%} mean / "
          f"{max(rep.occupancy) if rep.occupancy else 0} peak; "
          f"{rep.admissions_while_busy} admissions while mid-generation "
          f"(no drain barrier)")
    print(f"[engine] time-to-first-token {rep.mean_ttft_s*1e3:.2f} ms mean "
          f"/ {rep.p99_ttft_s*1e3:.2f} ms p99 "
          f"(prefill chunk {rep.prefill_chunk or 'off'})")
    if rep.spec_k:
        print(f"[engine] speculative: k={rep.spec_k} "
              f"({eng.dcfg.name} draft), "
              f"{rep.accepted_per_dispatch:.2f} tokens committed per "
              f"dispatch, {rep.latency_per_token_s*1e3:.2f} ms/token "
              f"mean (outputs bit-for-bit the non-speculative stream)")
    if rep.block_size:
        print(f"[engine] paged KV: {rep.num_blocks} blocks x "
              f"{rep.block_size} positions, {rep.kv_hbm_bytes/1e6:.2f} MB "
              f"resident; peak {rep.peak_blocks_used} blocks used "
              f"({rep.mean_block_util:.1%} mean util); "
              f"{rep.shared_block_hits} shared-prefix block hits "
              f"({rep.shared_hit_rate:.1%} of demand, "
              f"{rep.prefill_tokens_skipped} prefill tokens skipped); "
              f"effective concurrency {rep.effective_concurrency:.1f}")
    if len(rep.class_p99_latency_s) > 1:
        print(f"[engine] goodput {rep.goodput_tokens_per_s:,.0f} tok/s "
              f"({rep.slo_attainment:.1%} of requests made their "
              f"deadline)")
        for cls in bt.PRIORITY_CLASSES:
            if cls not in rep.class_p99_latency_s:
                continue
            print(f"[engine]   {cls:11s} "
                  f"p99 {rep.class_p99_latency_s[cls]*1e3:8.2f} ms, "
                  f"ttft {rep.class_mean_ttft_s[cls]*1e3:.2f} ms mean / "
                  f"{rep.class_p99_ttft_s[cls]*1e3:.2f} ms p99")
    if rep.model_p99_latency_s:
        for tag in tags:
            if tag not in rep.model_p99_latency_s:
                continue
            print(f"[engine]   model {tag}: "
                  f"p99 {rep.model_p99_latency_s[tag]*1e3:8.2f} ms, "
                  f"ttft {rep.model_mean_ttft_s[tag]*1e3:.2f} ms mean / "
                  f"{rep.model_p99_ttft_s[tag]*1e3:.2f} ms p99, "
                  f"goodput {rep.model_goodput_tokens_per_s[tag]:,.0f} "
                  f"tok/s, occupancy "
                  f"{rep.model_mean_occupancy[tag]:.1%} of the shared "
                  f"lease"
                  + (f" (quota {quotas[tag]})" if tag in quotas else ""))
    if rep.preempted or rep.dropped or rep.failed or rep.unfinished:
        print(f"[engine] retirement: {rep.preempted} preemptions "
              f"(exact resume), {rep.dropped} dropped, {rep.failed} "
              f"failed, {rep.unfinished} unfinished")
    if plan is not None:
        print(f"[engine] faults: {len(plan.fired)} fired "
              f"({rep.dispatch_retries} dispatch retries, "
              f"{rep.nonfinite_samples} non-finite samples caught, "
              f"{rep.torn_rows_repaired} torn rows repaired, "
              f"{rep.leaked_blocks} leaked blocks, "
              f"{rep.stuck_ticks} stuck ticks)")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
