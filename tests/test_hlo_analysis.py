"""Validate the trip-count-aware HLO analyzer against known computations."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_hlo, parse_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_single_matmul_flops():
    xs = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    c = _compile(lambda x, w: x @ w, xs, ws)
    stats = analyze_hlo(c.as_text())
    want = 2 * 128 * 256 * 64
    assert stats.flops == pytest.approx(want, rel=0.01)


def test_scan_multiplies_flops_by_trip_count():
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = _compile(f, xs, ws)
    stats = analyze_hlo(c.as_text())
    one = 2 * 128 * 128 * 128
    assert stats.flops == pytest.approx(10 * one, rel=0.05)
    # XLA's own cost_analysis undercounts (body visited once) — that is the
    # reason this analyzer exists
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns one dict per device
        ca = ca[0]
    assert ca["flops"] < 2 * one


def test_nested_scan_trip_counts():
    xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = _compile(f, xs, ws)
    stats = analyze_hlo(c.as_text())
    one = 2 * 64 * 64 * 64
    assert stats.flops == pytest.approx(12 * one, rel=0.05)


def test_collective_bytes_with_groups():
    import subprocess, sys, textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        def f(x):
            return jax.shard_map(lambda a: jax.lax.psum(a, "model"),
                                 mesh=mesh, in_specs=P("model", None),
                                 out_specs=P(), check_vma=False)(x)
        xs = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
        c = jax.jit(f).lower(xs).compile()
        st = analyze_hlo(c.as_text(), total_devices=8)
        # all-reduce of a (1, 1024) f32 shard -> 4096 operand bytes
        assert st.collective_bytes == 4096, st
        assert "all-reduce" in st.per_collective, st.per_collective
        print("OK")
    """)
    import os
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                          "HOME": os.environ.get("HOME", str(repo)),
                                          # pin CPU: with libtpu installed,
                                          # TPU plugin init can block on the
                                          # libtpu lockfile in a bare env
                                          "JAX_PLATFORMS": "cpu"},
                         cwd=repo)
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_hbm_bytes_scale_with_scan():
    xs = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f1(x, w):
        return x @ w

    def f10(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    s1 = analyze_hlo(_compile(f1, xs, ws).as_text())
    s10 = analyze_hlo(_compile(f10, xs, ws).as_text())
    assert s10.hbm_bytes > 5 * s1.hbm_bytes
