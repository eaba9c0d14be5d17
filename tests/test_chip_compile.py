"""Compile the serving path for a TPU v5e that is described, not attached.

The only test file that knows about the chip.  Interpret-mode tests
check what the kernels compute; these check that the TPU compiler
accepts them at the widths the store serves (SIFT1M shape: n=1M, d=128,
B=64, K=10, L=5, M=5, steps=8; and SIFT10M whole on one chip, n=10M,
M=20, one copy of the vectors), and that one call fits a v5e's HBM.
Nothing runs: a compile that passes here is not a chip run.

The topology is described inside a module-scoped fixture, never at
import, so every pytest worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core import DBLSHParams, build, search_batch_fixed
from repro.core.index import _hash_and_pack

N, D, B, K, L, M, STEPS, k = 1_000_000, 128, 64, 10, 5, 5, 8, 10
N10, M10 = 10_000_000, 20  # SIFT10M whole on one chip, one copy
LNB = L * -(-N // B)
HBM_LIMIT = 15 * 2**30  # a v5e holds 16 GB; leave room for the runtime
BATCHES = (1, 8, 32)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, *, kernel: bool):
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_LIMIT, f"{used / 2**30:.2f} GiB of HBM"
    if kernel:  # compiled to Mosaic, not lowered through the interpreter
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("Qn", BATCHES)
@pytest.mark.parametrize("mode", ["norm", "exact", "int8", "bf16"])
def test_fused_window_search_compiles(one_chip, mode, Qn):
    quant = mode in ("int8", "bf16")
    ks = 4 * k if quant else k
    x_dtype = {"int8": jnp.int8, "bf16": jnp.bfloat16}.get(mode, jnp.float32)
    S = lambda shape, dt=jnp.float32: _sds(one_chip, shape, dt)  # noqa: E731
    args = [
        S((Qn, L * M), jnp.int32), S((STEPS,)), S((LNB, B, K)),
        S((LNB, B, D), x_dtype), S((LNB, B)), S((LNB, B), jnp.int32),
        S((Qn, L, K)), S((Qn, D)),
    ]
    scale = S((LNB, B)) if quant else None

    def f(*a, x_scale=None):
        return kernels.fused_window_search(
            *a, M=M, ks=ks, n=N, mode=mode, interpret=False, x_scale=x_scale
        )

    _check(jax.jit(f).lower(*args, x_scale=scale).compile(), kernel=True)


@pytest.mark.parametrize("Qn", BATCHES)
def test_fused_cand_search_compiles(one_chip, Qn):
    Ct = M * B
    S = lambda shape, dt=jnp.float32: _sds(one_chip, shape, dt)  # noqa: E731
    args = [
        S((Qn, L, Ct, K)), S((Qn, L, Ct, D)), S((Qn, L, Ct)),
        S((Qn, L, Ct), jnp.int32), S((STEPS,)), S((Qn, L, K)), S((Qn, D)),
    ]

    def f(*a):
        return kernels.fused_cand_search(
            *a, ks=k, n=N, mode="norm", interpret=False
        )

    _check(jax.jit(f).lower(*args).compile(), kernel=True)


@pytest.mark.parametrize("engine,n,max_blocks,inline", [
    pytest.param("jnp", N, 0, True, id="jnp"),
    pytest.param("inline", N, 0, True, id="inline"),
    # the one-copy layout whole on one chip (sift10m-1chip)
    pytest.param("kernel", N10, M10, False, id="kernel-onecopy-10M"),
])
def test_search_step_compiles(one_chip, engine, n, max_blocks, inline):
    """The whole served step (project, select, verify, merge): at n=1M
    (SIFT1M, inline layout), and at n=10M on the one-copy layout, whose
    step must copy no (L, nb, ...) index array: its temporaries stay
    small beside the 7.1 GiB of data and index."""
    params = DBLSHParams.derive(
        n=n, d=D, c=1.5, t=64, k=k, K=K, L=L, inline_vectors=inline,
        max_blocks=max_blocks,
    )
    assert params.max_blocks == (max_blocks or M)
    shapes = jax.eval_shape(
        lambda key, x: build(key, x, params),
        jax.random.key(0), jax.ShapeDtypeStruct((n, D), jnp.float32),
    )
    index = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), shapes)
    compiled = search_batch_fixed.lower(
        index, _sds(one_chip, (BATCHES[-1], D)), k=k, r0=1.0, steps=STEPS,
        engine=engine, interpret=False, with_stats=True,
    ).compile()
    _check(compiled, kernel=engine != "jnp")
    if not inline:
        assert compiled.memory_analysis().temp_size_in_bytes <= 2**30
        nb = -(-n // B)
        entry = compiled.as_text().split("ENTRY", 1)[1]
        assert not re.search(rf"= \S+\[{L},{nb}[,\]]\S* copy\(", entry)


def test_local_build_compiles(one_chip):
    """The local build's hashing and packing at n=10M, as one program:
    data, temporaries and the index fit one chip together."""
    params = DBLSHParams.derive(
        n=N10, d=D, c=1.5, t=64, k=k, K=K, L=L, inline_vectors=False,
        max_blocks=M10,
    ).resolve()
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = _hash_and_pack.lower(
        _sds(one_chip, key.shape, key.dtype), _sds(one_chip, (N10, D)), params,
    ).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < HBM_LIMIT, f"{used / 2**30:.2f} GiB of HBM"
