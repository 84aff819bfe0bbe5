# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# --smoke: fast post-refactor sanity gate (cost pipeline + kernel bench).
# --bench-out PATH: write the serving perf trajectory (tokens/s,
#   service-time curve, autotuned tiles, kernel bench) as schema'd JSON —
#   the BENCH_serving.json every future perf PR has to beat.
import argparse
import json
import sys


BENCH_SCHEMA_VERSION = 1


def _kernel_bench_rows():
    """kernel_bench CSV rows, also printed by --smoke (perf guard)."""
    from benchmarks import kernel_bench
    rows = []
    for fn in kernel_bench.ALL:
        rows.extend(fn())
    return rows


def write_bench_json(path: str, kernel_rows=None) -> None:
    """Emit the serving benchmark JSON (schema asserted by tests)."""
    import jax

    from benchmarks import serving_bench

    rows = serving_bench.serving_rows()
    if kernel_rows is None:
        kernel_rows = _kernel_bench_rows()
    for name, us, derived in kernel_rows:
        rows.append({"kind": "kernel_bench", "name": name,
                     "us_per_call": us, "derived": derived})
    doc = {"schema_version": BENCH_SCHEMA_VERSION,
           "backend": jax.default_backend(),
           "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    kinds = sorted({r["kind"] for r in rows})
    print(f"[bench] wrote {len(rows)} rows ({', '.join(kinds)}) -> {path}")


def smoke(kernel_rows=None) -> int:
    """Fast post-refactor sanity gate: compile ONE reduced config, derive
    its roofline cell through `core.roofline` (structural hlo_cost under the
    hood), render it through the roofline report, assert nonzero
    flops/bytes, and print the kernel micro-bench rows (timed here unless
    the caller already ran them)."""
    import json
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core import roofline as RL

    batch, d, layers = 16, 128, 4       # reduced scan-over-layers config

    def stack(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        return jax.lax.scan(body, x, w)[0]

    compiled = jax.jit(stack).lower(
        jax.ShapeDtypeStruct((batch, d), jnp.float32),
        jax.ShapeDtypeStruct((layers, d, d), jnp.float32)).compile()
    terms = RL.from_compiled("smoke/scan_stack/single", compiled, chips=1,
                             model_flops=2 * batch * d * d * layers)

    with tempfile.TemporaryDirectory() as tmp:
        cell = terms.to_dict()
        cell.update({"status": "ok", "arch": "smoke", "shape": "scan_stack",
                     "mesh": "single"})
        with open(os.path.join(tmp, "smoke.json"), "w") as f:
            json.dump(cell, f)
        from benchmarks import roofline_report
        print(roofline_report.markdown_table(results_dir=tmp))
        print()
        print("Per-op breakdown (from hlo_cost CostTotals.by_op):")
        print(roofline_report.breakdown_table(results_dir=tmp))

    assert terms.hlo_flops > 0, "smoke: zero FLOPs from hlo_cost"
    assert terms.hlo_bytes > 0, "smoke: zero bytes from hlo_cost"
    assert terms.hlo_flops == 2 * batch * d * d * layers, \
        f"smoke: flops {terms.hlo_flops} != model {2 * batch * d * d * layers}"
    assert terms.by_op and terms.by_op.get("dot", {}).get("flops", 0) > 0, \
        "smoke: per-op breakdown missing dot flops"

    print("\nKernel micro-bench (name,us_per_call,derived):")
    for name, us, derived in (kernel_rows if kernel_rows is not None
                              else _kernel_bench_rows()):
        print(f"{name},{us:.2f},{derived}")

    # continuous-batching engine: short CPU run, outputs must match the
    # sequential per-token reference bit-for-bit; append-path kernel
    # parity under the Pallas interpreter rides along (offline-safe)
    from benchmarks import serving_bench
    eng = serving_bench.engine_smoke()
    print(f"\n[engine] smoke: {eng['requests']} requests in "
          f"{eng['ticks']} ticks, occupancy {eng['mean_occupancy']:.1%}, "
          f"{eng['admissions_while_busy']} mid-flight admissions, "
          f"ttft {eng['mean_ttft_s']*1e3:.2f} -> "
          f"{eng['chunked_mean_ttft_s']*1e3:.2f} ms chunked; "
          f"sequential-reference parity (dense + ssm + encdec primed "
          f"cross-K/V, per-token + chunked prefill) + append-path "
          f"kernel parity OK; paged KV: {eng['paged_requests']}-request "
          f"shared-prefix trace parity OK "
          f"({eng['paged_shared_block_hits']} shared block hits, "
          f"{eng['paged_prefill_tokens_skipped']} prefill tokens "
          f"skipped), blocks-limited admission served "
          f"{eng['paged_limited_peak_occupancy']} concurrent requests "
          f"from a 4-row block budget, block-gather kernel parity OK")

    # chaos gate: a bursty two-class trace with seeded faults and forced
    # preemptions must complete with zero uncaught exceptions, no leaked
    # KV blocks, and bit-for-bit parity on every non-failed output (plus
    # a no-fault control arm matching the sequential reference)
    chaos = serving_bench.chaos_smoke()
    print(f"[chaos] smoke: {chaos['requests']} requests survived "
          f"{chaos['faults_fired']} injected faults "
          f"({chaos['dispatch_retries']} dispatch retries, "
          f"{chaos['nonfinite_samples']} non-finite samples caught, "
          f"{chaos['torn_rows_repaired']} torn block-table rows "
          f"repaired) and {chaos['preempted']} preemptions with "
          f"{chaos['failed']} typed failures, {chaos['leaked_blocks']} "
          f"leaked blocks, exact-resume parity on every non-failed "
          f"output; goodput {chaos['goodput_tokens_per_s']:.0f} tok/s "
          f"at {chaos['slo_attainment']:.1%} SLO attainment; no-fault "
          f"control arm bit-for-bit OK")

    # speculative gate: full-depth self-draft under chaos, a garbage
    # draft, and the non-spec control must all stay bit-for-bit the
    # sequential reference (acceptance is exact, rejected KV is dead)
    spec = serving_bench.spec_smoke()
    print(f"[spec] smoke: {spec['requests']} requests through "
          f"draft-and-verify — full-depth self-draft committed "
          f"{spec['chaos_accepted_per_dispatch']:.2f} tokens/dispatch "
          f"under {spec['preempted']} preemptions and "
          f"{spec['faults_fired']} injected faults "
          f"({spec['leaked_blocks']} leaked blocks), garbage draft "
          f"held exact outputs at "
          f"{spec['garbage_accepted_per_dispatch']:.2f} tokens/dispatch, "
          f"non-spec control at exactly 1.00; bit-for-bit parity OK")

    # multi-model gate: two families multiplexed on one engine under
    # chaos (preemption + seeded cross-lane faults + tight per-lane
    # block pools) must hold per-model bit-for-bit parity, drain both
    # block pools clean, and consolidate occupancy past either
    # dedicated engine at the same offered rates
    mux = serving_bench.multiplex_smoke()
    print(f"[multiplex] smoke: {mux['requests']} two-model requests "
          f"survived {mux['faults_fired']} cross-lane faults and "
          f"{mux['preempted']} preemptions with {mux['failed']} typed "
          f"failures, {mux['leaked_blocks']} leaked blocks; per-model "
          f"sequential-reference parity OK; model-fingerprinted prefix "
          f"keys OK; multiplexed occupancy "
          f"{mux['multiplexed_occupancy']:.1%} beats both dedicated "
          f"engines (per-model occupancy "
          f"{ {t: round(v, 3) for t, v in mux['model_mean_occupancy'].items()} })")

    # fleet gate: 2 replicas x 2 model lanes behind the replica router
    # on a bursty trace with preemption — routed outputs bit-for-bit
    # each lane's sequential reference, zero leaked blocks fleet-wide,
    # both replicas loaded
    rt = serving_bench.router_smoke()
    print(f"[router] smoke: {rt['requests']} two-model requests across "
          f"{rt['replicas']} replicas "
          f"({rt['replica_requests']}, occupancy "
          f"{rt['replica_occupancy']}), {rt['preempted']} preemptions, "
          f"{rt['leaked_blocks']} leaked blocks; per-model "
          f"sequential-reference parity OK; goodput "
          f"{rt['goodput_tokens_per_s']:.0f} tok/s")

    # tensor-parallel gate: sharded executor vs single-device engine,
    # bit-for-bit on the same trace (tp=1 conformance always; the
    # multi-device pair needs a forced host mesh and skips gracefully)
    sh = serving_bench.sharded_smoke()
    if "skipped" in sh:
        print(f"[sharded] smoke: skipped ({sh['skipped']})")
    elif sh["multi_device"]:
        print(f"[sharded] smoke: tp={sh['tp']} across {sh['devices']} "
              f"devices, {sh['requests']} requests bit-identical to the "
              f"single-device engine; parity OK")
    else:
        print(f"[sharded] smoke: tp=1 conformance parity OK "
              f"({sh['requests']} requests); multi-device pair skipped "
              f"({sh['skipped_multi']})")

    print("\nsmoke OK: flops/bytes nonzero, scan trip count exact")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="compile one reduced config and sanity-check the "
                         "roofline/cost pipeline end to end")
    ap.add_argument("--bench-out", metavar="PATH", default=None,
                    help="write serving perf rows (tokens/s, service-time "
                         "curve, chosen tiles, kernel bench) as JSON")
    args = ap.parse_args()
    if args.smoke:
        kernel_rows = _kernel_bench_rows() if args.bench_out else None
        rc = smoke(kernel_rows)
        if args.bench_out:
            write_bench_json(args.bench_out, kernel_rows)
        sys.exit(rc)
    if args.bench_out:
        write_bench_json(args.bench_out)
        sys.exit(0)

    from benchmarks import kernel_bench, paper_tables, roofline_report
    from benchmarks import serving_bench
    print("name,us_per_call,derived")
    failures = 0
    suites = list(paper_tables.ALL) + list(kernel_bench.ALL) + \
        list(serving_bench.ALL) + [roofline_report.rows]
    for fn in suites:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:  # keep the suite running; report at the end
            failures += 1
            print(f"{fn.__name__},0.00,ERROR {type(e).__name__}: {e}",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
