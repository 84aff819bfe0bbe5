import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks the device count on first use.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  1. abstract params / optimizer state / cache via jax.eval_shape — zero
     allocation (ShapeDtypeStruct stand-ins, the shannon/kernels pattern);
  2. jit(step, in_shardings=..., out_shardings=...).lower(...).compile()
     under the production mesh — any sharding mismatch, OOM-at-compile, or
     unsupported collective fails the cell (it is a bug in the framework);
  3. record memory_analysis / cost_analysis / collective schedule and the
     three roofline terms to results/dryrun/<cell>.json.

Serving cells (prefill/decode) run the paper's technique: int8-quantized
weights (w8a16 baseline).  Training cells run bf16 params + fp32 AdamW.

Usage:
  python -m repro.launch.dryrun --mesh both --arch all --shape all
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape decode_32k \
      --mesh single --quant w8a16 --rules baseline
"""
import argparse
import gc
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.core import roofline as RL
from repro.core.qlinear import FP, QuantMode, W8A16, W8A8
from repro.core.quant import quantize_tree
from repro.launch.mesh import make_production_mesh
from repro.models import registry as R
from repro.optim import make_optimizer, cosine_schedule
from repro.runtime import sharding as S
from repro.runtime import steps as ST

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


def _abstract(fn, *args, **kw):
    return jax.eval_shape(fn, *args, **kw)


def _batch_shardings(specs: dict, mesh):
    out = {}
    for k, v in specs.items():
        if k == "cache_index" or v.ndim == 0:
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = NamedSharding(mesh, S.batch_spec(mesh, v.ndim, v.shape))
    return out


def build_cell(arch: str, shape_name: str, mesh, rules,
               quant: str = "w8a16", optimizer: str = "adamw",
               kv_quant: bool = False, grad_compression=None):
    """Returns (lowered, model_flops, peak_flops) for one cell."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if kv_quant:
        cfg = _dc.replace(cfg, kv_quant=True)
    shape = SHAPES[shape_name]
    ok, why = cfg.supports(shape)
    if not ok:
        return None, why, None
    key = jax.random.PRNGKey(0)

    with S.use_rules(mesh, rules):
        if shape.kind == "train":
            params = _abstract(lambda k: R.init(k, cfg, dtype=jnp.bfloat16),
                               key)
            opt = make_optimizer(optimizer,
                                 lr=cosine_schedule(3e-4, 100, 10000))
            opt_state = _abstract(opt.init, params)
            step_fn = ST.make_train_step(cfg, opt, mode=FP, remat=True,
                                         mesh=mesh,
                                         grad_compression=grad_compression)
            p_sh = S.tree_shardings(params, mesh, rules)
            o_sh = S.tree_shardings(opt_state, mesh, rules)
            b_specs = cfg.input_specs(shape)
            b_sh = _batch_shardings(b_specs, mesh)
            r_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_sh, o_sh, b_sh, None),
                             out_shardings=(p_sh, o_sh, None))
            with mesh:
                lowered = jitted.lower(params, opt_state, b_specs, r_spec)
            peak = RL.V5E.bf16_flops
        else:
            mode = {"w8a16": W8A16, "w8a8": W8A8, "fp": FP}[quant]
            def qinit():
                p = R.init(key, cfg, dtype=jnp.bfloat16)
                return quantize_tree(p) if mode.enabled else p
            params = _abstract(qinit)
            p_sh = S.tree_shardings(params, mesh, rules)
            b_specs = cfg.input_specs(shape)
            b_sh = _batch_shardings(b_specs, mesh)
            if shape.kind == "prefill":
                step_fn = ST.make_prefill_step(cfg, mode=mode)
                out_shape = (shape.global_batch, shape.seq_len, cfg.vocab)
                jitted = jax.jit(
                    step_fn, in_shardings=(p_sh, b_sh),
                    out_shardings=NamedSharding(
                        mesh, S.spec_for("logits", 3, mesh, rules,
                                         out_shape)))
                with mesh:
                    lowered = jitted.lower(params, b_specs)
            else:  # decode
                cache = _abstract(lambda: R.init_cache(
                    cfg, shape.global_batch, shape.seq_len))
                c_sh = S.cache_shardings(cache, mesh, rules)
                step_fn = ST.make_decode_step(cfg, mode=mode)
                jitted = jax.jit(step_fn,
                                 in_shardings=(p_sh, b_sh, c_sh),
                                 out_shardings=(None, c_sh),
                                 donate_argnums=(2,))
                with mesh:
                    lowered = jitted.lower(params, b_specs, cache)
            peak = RL.V5E.int8_ops if mode.w8a8 else RL.V5E.bf16_flops
        return lowered, cfg.model_flops(shape), peak


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             rules_name: str = "baseline", quant: str = "w8a16",
             optimizer: str = "adamw", out_dir: str = RESULTS_DIR,
             tag: str = "", kv_quant: bool = False,
             grad_compression=None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rules = S.RULE_SETS[rules_name]
    cell = f"{arch}/{shape_name}/{mesh_name}" + (f"/{tag}" if tag else "")
    t0 = time.time()
    result = {"cell": cell, "arch": arch, "shape": shape_name,
              "mesh": mesh_name, "rules": rules_name, "quant": quant,
              "status": "ok"}
    try:
        lowered, mf_or_why, peak = build_cell(
            arch, shape_name, mesh, rules, quant=quant,
            optimizer=optimizer, kv_quant=kv_quant,
            grad_compression=grad_compression)
        if lowered is None:
            result["status"] = "skipped"
            result["reason"] = mf_or_why
            return result
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        terms = RL.from_compiled(cell, compiled, chips=mesh.devices.size,
                                 model_flops=mf_or_why, peak_flops=peak)
        result.update(terms.to_dict())   # includes by_op + per-collective
        result["top_ops"] = " ".join(
            f"{op}:f={flops:.2e},b={byts:.2e}"
            for op, flops, byts, _ in terms.op_rows(limit=3))
        result["lower_s"] = round(t_lower, 1)
        result["compile_s"] = round(t_compile, 1)
        try:
            result["memory_analysis"] = str(compiled.memory_analysis())
        except Exception:
            pass
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    finally:
        os.makedirs(out_dir, exist_ok=True)
        fname = cell.replace("/", "__") + ".json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
        gc.collect()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--rules", default="baseline")
    ap.add_argument("--quant", default="w8a16",
                    choices=["w8a16", "w8a8", "fp"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    ap.add_argument("--tag", default="")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--grad-compression", default=None,
                    choices=[None, "int8"])
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                cell = f"{arch}/{shape}/{mesh_name}" + \
                    (f"/{args.tag}" if args.tag else "")
                fpath = os.path.join(args.out_dir,
                                     cell.replace("/", "__") + ".json")
                if args.skip_existing and os.path.exists(fpath):
                    with open(fpath) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached ] {cell}: {prev['status']}")
                        continue
                res = run_cell(arch, shape, mesh_name,
                               rules_name=args.rules, quant=args.quant,
                               optimizer=args.optimizer,
                               out_dir=args.out_dir, tag=args.tag,
                               kv_quant=args.kv_quant,
                               grad_compression=args.grad_compression)
                if res["status"] == "ok":
                    print(f"[ok     ] {cell}: compute={res['compute_s']:.4e}s "
                          f"memory={res['memory_s']:.4e}s "
                          f"coll={res['collective_s']:.4e}s "
                          f"bound={res['bound']} "
                          f"(lower {res['lower_s']}s compile "
                          f"{res['compile_s']}s)")
                    if res.get("top_ops"):
                        print(f"          {res['top_ops']}")
                elif res["status"] == "skipped":
                    print(f"[skipped] {cell}: {res['reason']}")
                else:
                    failures += 1
                    print(f"[ERROR  ] {cell}: {res['error']}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
