"""Regenerate the golden HLO fixtures from the installed JAX/XLA.

Run from the repo root:  PYTHONPATH=src python tests/fixtures/hlo/regen.py

The fixtures pin the *text shape* of post-SPMD HLO that
``repro.core.hlo_cost`` must parse (scan, nested scan, fusion-with-dot,
psum, donated dynamic-update-slice).  The expected cost numbers asserted in
``tests/test_hlo_cost.py`` are functions of the program, not the XLA
version, so regenerated fixtures must keep passing the same assertions.

The psum fixture needs 4 devices, so this script re-executes itself in a
subprocess with XLA_FLAGS set before jax is imported.
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _write(name, text):
    with open(os.path.join(HERE, name), "w") as f:
        f.write(text)
    print(f"wrote {name}: {len(text)} bytes")


def main():
    import jax
    import jax.numpy as jnp

    def compiled(f, *specs, **jit_kw):
        return jax.jit(f, **jit_kw).lower(*specs).compile()

    # scan of (64,128)@(128,128) over 8 layers -> 2*64*128*128*8 flops
    def scan_f(x, w):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        return jax.lax.scan(body, x, w)[0]
    _write("scan_matmul.hlo", compiled(
        scan_f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)).as_text())

    # nested scan: inner length=3 over outer 8 -> 24 matmuls
    def nested_f(x, w):
        def outer(x, wi):
            def inner(x, _):
                return jnp.tanh(x @ wi), None
            return jax.lax.scan(inner, x, None, length=3)[0], None
        return jax.lax.scan(outer, x, w)[0]
    _write("nested_scan.hlo", compiled(
        nested_f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)).as_text())

    # fusion with dot: matmul + bias + gelu fuses the pointwise tail
    def fused_f(a, b, c):
        return jax.nn.gelu(a @ b + c)
    _write("fusion_dot.hlo", compiled(
        fused_f, jax.ShapeDtypeStruct((32, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 16), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.float32)).as_text())

    # donated KV-cache style dynamic-update-slice
    def dus_f(cache, new):
        return jax.lax.dynamic_update_slice(cache, new, (0, 5, 0))
    _write("dus_donated.hlo", compiled(
        dus_f, jax.ShapeDtypeStruct((4, 1024, 64), jnp.float32),
        jax.ShapeDtypeStruct((4, 1, 64), jnp.float32),
        donate_argnums=(0,)).as_text())


def psum_main():
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((4,), ("x",))
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    sa = NamedSharding(mesh, P(None, "x"))
    sb = NamedSharding(mesh, P("x", None))
    with mesh:
        c = jax.jit(lambda a, b: a @ b, in_shardings=(sa, sb),
                    out_shardings=NamedSharding(mesh, P())) \
            .lower(a, b).compile()
    _write("psum.hlo", c.as_text())

    # all-reduce INSIDE a scanned while: collective bytes/counts must be
    # multiplied by the 8-iteration trip count.
    def scan_psum(x, w):
        @partial(jax.shard_map, mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
                 out_specs=P(None, None))
        def mm(xs, ws):
            return jax.lax.psum(xs @ ws, "x")
        return jax.lax.scan(lambda c, wi: (mm(c, wi), None), x, w)[0]
    c = jax.jit(scan_psum).lower(
        jax.ShapeDtypeStruct((16, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)).compile()
    _write("scan_psum.hlo", c.as_text())


if __name__ == "__main__":
    if "--psum" in sys.argv:
        psum_main()
    else:
        main()
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        subprocess.run([sys.executable, __file__, "--psum"], env=env,
                       check=True)
