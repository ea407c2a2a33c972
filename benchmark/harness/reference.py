"""Plain references and seeded inputs of the benchmark's configurations.

jax/numpy only: nothing here imports the program under test, and nothing
here takes a value the program made.  Copied from ``chip_smoke.py`` (PR 21
proved them on the chip) and seeded; the program may change, these may not.
"""

from __future__ import annotations

import functools


def seed_words(seed: int, n: int = 4) -> tuple:
    """``n`` 32-bit words drawn from ``jax.random.key(seed)`` (any
    non-negative whole number: without x64 jax keeps a seed's low 32 bits
    only, so the bits above them are folded in)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    bits = jax.random.bits(key, (n,), jnp.uint32)
    return tuple(int(v) for v in np.asarray(bits))


def seeded_field(words, q=0):
    """``f(x, y, z)`` -> f32 in [0, 1): a 32-bit mix of the global integer
    coordinate and the seed's words (``q`` separates quantities).  A function
    of the global coordinate only, so a sharded fill and a global one agree
    bit for bit, and every value is a multiple of 2^-24 (exact in f32).
    ``words`` and ``q`` may be traced, so one compiled program serves every
    seed and quantity."""
    import jax.numpy as jnp
    import numpy as np

    u = jnp.uint32
    if isinstance(words, (tuple, list)):
        words = np.asarray(words, dtype=np.uint32)

    def f(x, y, z):
        w = jnp.asarray(words, dtype=u)
        salt = w[3] ^ (jnp.asarray(q).astype(u) * u(0x9E3779B9))
        h = (
            x.astype(u) * (w[0] | u(1))
            + y.astype(u) * (w[1] | u(1))
            + z.astype(u) * (w[2] | u(1))
            + salt
        )
        h = (h ^ (h >> 16)) * u(0x7FEB352D)
        h = (h ^ (h >> 15)) * u(0x846CA68B)
        h = h ^ (h >> 16)
        return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))

    return f


def ripple(q: int, phase: int):
    """Analytic integer-valued field of quantity ``q`` (exact in f32 below
    2^24), distinct per quantity and per coordinate triple modulo 2^20;
    ``phase`` (from the seed) shifts it."""

    def f(x, y, z):
        import jax.numpy as jnp

        v = (x * 1009 + y * 31 + z + q * 7919 + phase % (1 << 20)) % (1 << 20)
        return v.astype(jnp.float32)

    return f


def _global_coords(shape):
    import jax.numpy as jnp

    X, Y, Z = shape
    return (
        jnp.arange(X)[:, None, None],
        jnp.arange(Y)[None, :, None],
        jnp.arange(Z)[None, None, :],
    )


@functools.lru_cache(maxsize=None)
def _ref_jacobi(shape, steps: int, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax

    X, Y, Z = shape

    def run(words, q):
        x, y, z = _global_coords(shape)
        yz = (y - Y // 2) ** 2 + (z - Z // 2) ** 2
        in_r2 = (X // 10 + 1) ** 2
        hot = (x - X // 3) ** 2 + yz < in_r2
        cold = (x - X * 2 // 3) ** 2 + yz < in_r2

        def body(_, u):
            v = (
                jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)
                + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2)
            ) / 6.0
            return jnp.where(cold, 0.0, jnp.where(hot, 1.0, v))

        u0 = jnp.broadcast_to(seeded_field(words, q)(x, y, z), shape)
        return lax.fori_loop(0, steps, body, u0)

    return jax.jit(run, out_shardings=sharding)


def ref_jacobi(shape, steps: int, sharding, words, q: int = 0):
    """``steps`` jacobi3d updates (reference bin/jacobi3d.cu) restated on
    the global array from ``seeded_field(words, q)``: each step the mean of
    the six face neighbours (lower neighbour first along each axis), then
    the hot sphere (centre X/3, Y/2, Z/2, radius X/10, membership
    floor(dist) <= r, i.e. d^2 < (r+1)^2) clamped to 1 and the cold one
    (centre 2X/3) to 0."""
    return _ref_jacobi(tuple(shape), steps, sharding)(*_traced(words, q))


@functools.lru_cache(maxsize=None)
def _ref_mean6(shape, steps: int, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(words, q):
        u0 = jnp.broadcast_to(seeded_field(words, q)(*_global_coords(shape)), shape)

        def body(_, u):
            return (
                jnp.roll(u, 1, 0) + jnp.roll(u, 1, 1) + jnp.roll(u, 1, 2)
                + jnp.roll(u, -1, 0) + jnp.roll(u, -1, 1) + jnp.roll(u, -1, 2)
            ) / 6.0

        return lax.fori_loop(0, steps, body, u0)

    return jax.jit(run, out_shardings=sharding)


def ref_mean6(shape, steps: int, sharding, words, q: int = 0):
    """``steps`` periodic mean-of-6 updates of ``seeded_field(words, q)`` on
    the global array, summed in the astaroth proxy kernel's own order (-x,
    -y, -z, +x, +y, +z neighbours: ``src.sh(-1,0,0)`` reads the lower
    neighbour).  One compiled program serves every field and seed."""
    return _ref_mean6(tuple(shape), steps, sharding)(*_traced(words, q))


def _traced(words, q):
    """Seed words and quantity index as arguments of a compiled reference."""
    import numpy as np

    return np.asarray(words, dtype=np.uint32), np.uint32(q)


@functools.lru_cache(maxsize=None)
def _jitted(which: str, lo=None, hi=None):
    import jax
    import jax.numpy as jnp

    def err(a, b):
        d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        return jnp.max(jnp.where(jnp.isfinite(d), d, jnp.inf))

    def bad(a):
        a = a.astype(jnp.float32)
        out = ~jnp.isfinite(a)
        if lo is not None:
            out = out | (a < lo) | (a > hi)
        return jnp.sum(out.astype(jnp.int32))

    return jax.jit({"err": err, "bad": bad}[which])


def max_abs_err(got, want) -> float:
    """max |got - want| of two equally sharded device arrays, in f32 whatever
    either is stored in; non-finite differences read as inf."""
    if got.shape != want.shape:
        return float("inf")
    return float(_jitted("err")(got, want))


def bad_cells(arr, lo: float = None, hi: float = None) -> int:
    """Cells of ``arr`` that are not finite or (when given) lie outside
    [lo, hi] -- counted on the device."""
    return int(_jitted("bad", lo, hi)(arr))


def ripple_mismatches(arr, mesh, interior, lo, gsize, f) -> int:
    """Cells of the shell-carrying sharded array ``arr`` (interior AND
    shell, every shard) that differ, read as f32, from the analytic f32
    field ``f`` at their periodically wrapped global coordinate -- counted
    on the device.  Comparing in f32 is what makes a narrower storage type
    fail: 20-bit integers do not survive bf16."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    raw = tuple(s // mesh.shape[a] for s, a in zip(arr.shape, names))

    def per_shard(block):
        c = [
            (lax.axis_index(a) * interior[i] - lo[i] + jnp.arange(raw[i])) % gsize[i]
            for i, a in enumerate(names)
        ]
        want = f(c[0][:, None, None], c[1][None, :, None], c[2][None, None, :])
        bad = jnp.sum(block.astype(jnp.float32) != jnp.broadcast_to(want, raw))
        return lax.psum(bad, names)

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=P(*names), out_specs=P())
    return int(jax.jit(fn)(arr))


def check(name: str, value, limit, what: str = "") -> dict:
    """One compared number beside its limit; ``ok`` iff value <= limit."""
    ok = value == value and value <= limit  # NaN fails
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok), "what": what}
