"""HLO cost analyzer: loop trip-count multiplication correctness."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_plain_matmul():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze(_hlo(lambda a, b: a @ b, x, x))
    expect = 2 * 128 ** 3
    assert abs(r["flops"] - expect) / expect < 0.05


def test_scan_multiplies_body():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, a, None, length=9)
        return y

    r = analyze(_hlo(f, x, x))
    expect = 9 * 2 * 128 ** 3
    assert abs(r["flops"] - expect) / expect < 0.05


def test_nested_scans():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, a, None, length=5)
        return y

    r = analyze(_hlo(f, x, x))
    expect = 15 * 2 * 64 ** 3
    assert abs(r["flops"] - expect) / expect < 0.06


def test_xla_cost_analysis_undercounts_scans():
    """The reason this module exists: XLA visits while bodies once."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, a, None, length=9)
        return y

    c = jax.jit(f).lower(x, x).compile()
    xla_flops = c.cost_analysis()["flops"]
    ours = analyze(c.as_text())["flops"]
    assert ours > 5 * xla_flops  # XLA reports ~1 body; we report 9


def test_collectives_counted(tmp_path):
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.analysis.hlo_cost import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        sh_w = NamedSharding(mesh, P("d", None))
        sh_x = NamedSharding(mesh, P(None, None))
        def f(a, b):
            return jnp.sum(a @ b)  # contract sharded dim -> all-reduce
        c = jax.jit(f, in_shardings=(sh_x, sh_w)).lower(x, w).compile()
        r = analyze(c.as_text())
        assert r["collectives"]["total"] > 0, r["collectives"]
        print("colls ok", r["collectives"]["total"])
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
