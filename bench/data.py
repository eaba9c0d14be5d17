"""Seeded vectors for the benchmark: data rows and query pools, made on
the devices that will hold them.

The distribution is the clustered mixture of ``repro.data.make_clustered``
(Gaussian pancakes of rank ``d // 8`` around uniform centers in
[-1, 1]^d), copied here so that the yardstick does not move when the
program's own generator does.  Two changes of mechanics, none of
distribution: rows are drawn in chunks (the program's version gathers a
(n, d/8, d) basis per point, which does not fit one chip at n = 1M), and
each device draws its own rows, so a row-sharded collection never sits
whole on one device.

``normalize_scale`` is copied too: every vector is scaled so that the
median nearest-neighbour distance of the first 512 queries is 1, the
paper's r0 = 1 without loss of generality (DB-LSH §III-A).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16  # rows per chunk: a (CHUNK, d/8, d) f32 gather is 512 MiB at d = 128


def root_key(seed: int) -> jax.Array:
    """A jax key from any non-negative seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def mixture(key, d: int, n_clusters: int):
    """Cluster centers (C, d) and bases (C, d/8, d) of the mixture."""
    kid = max(2, d // 8)
    kc, kb = jax.random.split(key)
    centers = jax.random.uniform(kc, (n_clusters, d), jnp.float32, -1.0, 1.0)
    basis = jax.random.normal(kb, (n_clusters, kid, d), jnp.float32) / jnp.sqrt(d)
    return centers, basis


@partial(jax.jit, static_argnames=("n", "spread"))
def _draw(key, centers, basis, *, n: int, spread: float):
    """``n`` rows of the mixture, drawn CHUNK rows at a time."""
    chunk = min(n, CHUNK)
    n_chunks = -(-n // chunk)
    kid, d = basis.shape[1], basis.shape[2]

    def one(k):
        ka, kx = jax.random.split(k)
        assign = jax.random.randint(ka, (chunk,), 0, centers.shape[0])
        coeff = jax.random.normal(kx, (chunk, kid), jnp.float32) * spread * jnp.sqrt(d)
        pts = centers[assign] + jnp.einsum(
            "nk,nkd->nd", coeff, basis[assign],
            precision=jax.lax.Precision.HIGHEST)
        return pts.astype(jnp.float32)

    keys = jax.random.split(key, n_chunks)
    return jax.lax.map(one, keys).reshape(n_chunks * chunk, d)[:n]


def draw_rows(key, centers, basis, n: int, spread: float, device) -> jax.Array:
    """``n`` mixture rows on ``device``."""
    c, b = jax.device_put((centers, basis), device)
    return _draw(jax.device_put(key, device), c, b, n=n, spread=spread)


@jax.jit
def _nn_d2(sample, rows):
    """Smallest squared distance from each sample row to ``rows``, over
    row blocks so that no (sample, n) matrix is held."""
    n = rows.shape[0]
    s2 = jnp.sum(jnp.square(sample), -1, keepdims=True)
    n_blk = -(-n // CHUNK)
    pad = jnp.pad(rows, ((0, n_blk * CHUNK - n), (0, 0)))

    def blk(best, i):
        x = jax.lax.dynamic_slice_in_dim(pad, i * CHUNK, CHUNK)
        d2 = (s2 - 2.0 * jnp.matmul(sample, x.T, precision=jax.lax.Precision.HIGHEST)
              + jnp.sum(jnp.square(x), -1))
        real = i * CHUNK + jnp.arange(CHUNK) < n
        return jnp.minimum(best, jnp.min(jnp.where(real, d2, jnp.inf), axis=-1)), None

    best, _ = jax.lax.scan(blk, jnp.full((sample.shape[0],), jnp.inf),
                           jnp.arange(n_blk))
    return best


def make_vectors(seed: int, *, n: int, d: int, n_clusters: int, spread: float,
                 pools: dict[str, int], devices) -> tuple[list, dict, float, jax.Array]:
    """Data shards, query pools and the build key, all from ``seed``.

    Returns ``(shards, pools, scale, build_key)``: one (n / P, d) array
    per device of ``devices``, each pool as a host (m, d) float32 array,
    the scale applied to both, and the key the index is built from.
    """
    kmix, kdata, kpool, kbuild = jax.random.split(root_key(seed), 4)
    centers, basis = mixture(kmix, d, n_clusters)
    per = n // len(devices)
    if per * len(devices) != n:
        raise ValueError(f"n={n} does not split over {len(devices)} devices")
    shards = [draw_rows(jax.random.fold_in(kdata, r), centers, basis, per,
                        spread, dev) for r, dev in enumerate(devices)]
    out_pools = {}
    for i, (name, m) in enumerate(sorted(pools.items())):
        out_pools[name] = draw_rows(jax.random.fold_in(kpool, i), centers,
                                    basis, m, spread, devices[0])
    # normalize_scale, over the shards: median NN distance of the first
    # 512 rows of the first pool
    first = out_pools[sorted(pools)[0]]
    sample = first[: min(512, first.shape[0])]
    nn2 = np.min(np.stack([np.asarray(_nn_d2(jax.device_put(sample, s.devices().pop()), s))
                           for s in shards]), axis=0)
    scale = float(1.0 / np.median(np.sqrt(np.maximum(nn2, 1e-12))))
    shards = [s * scale for s in shards]
    out_pools = {k: np.asarray(v * scale, np.float32) for k, v in out_pools.items()}
    return shards, out_pools, scale, kbuild
