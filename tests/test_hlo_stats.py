"""HLO analyzer validation against analytically-known graphs."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.launch.hlo_stats import analyze


def test_plain_matmul_flops():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    txt = jax.jit(f).lower(a, b).compile().as_text()
    st = analyze(txt)
    expect = 2 * 128 * 256 * 64
    assert abs(st.flops - expect) / expect < 0.01, (st.flops, expect)
    assert st.collective_bytes == 0


def test_scan_trip_count_multiplies():
    L, D = 7, 64

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return jnp.sum(y)

    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((32, D), jnp.float32)
    txt = jax.jit(f).lower(w, x).compile().as_text()
    st = analyze(txt)
    expect = 2 * 32 * D * D * L
    assert abs(st.flops - expect) / expect < 0.05, (st.flops, expect)
    # HBM traffic must also scale with L (weights streamed every step)
    assert st.hbm_bytes > L * D * D * 4


def test_collective_bytes_sharded_matmul():
    # runs under the default single device: simulate with 4 via subprocess?
    # here: spot-check that an explicit psum shows up.
    from repro.compat import shard_map

    mesh = make_mesh((1,), ("model",))

    def f(x):
        return shard_map(
            lambda a: jax.lax.psum(a, "model"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("model"),
            out_specs=jax.sharding.PartitionSpec(),
            check=True,
        )(x)

    x = jax.ShapeDtypeStruct((64,), jnp.float32)
    with mesh:
        txt = jax.jit(f).lower(x).compile().as_text()
    st = analyze(txt)
    # single device: XLA may elide the all-reduce; just assert no crash
    assert st.flops >= 0.0


def test_nested_scan():
    Lo, Li, D = 3, 5, 32

    def f(w, x):
        def outer(x, wo):
            def inner(x, _):
                return jnp.tanh(x @ wo), None
            x, _ = jax.lax.scan(inner, x, None, length=Li)
            return x, None
        y, _ = jax.lax.scan(outer, x, w)
        return jnp.sum(y)

    w = jax.ShapeDtypeStruct((Lo, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((8, D), jnp.float32)
    txt = jax.jit(f).lower(w, x).compile().as_text()
    st = analyze(txt)
    expect = 2 * 8 * D * D * Lo * Li
    assert abs(st.flops - expect) / expect < 0.1, (st.flops, expect)
