"""Problem generators of the benchmark, kept apart from the program.

``random_feasible_lp`` is the paper's section 4 generator (a copy of the
program's ``repro.core.lp.random_feasible_lp`` as of the benchmark's
first version), made on the device in one jitted call.  ``ladder`` is a
vectorised form of the served traffic's request generator
(``repro.serve_lp.bench``), made on the device: every request is
feasible, infeasible or degenerate, with its constraint count drawn
from a ladder of m.
"""
from __future__ import annotations

import functools

import numpy as np

KINDS = ("feasible", "infeasible", "degenerate")


def random_feasible_lp(key, batch: int, m: int, *, radius: float = 100.0,
                       slack: float = 5.0):
    """Random feasible LPs in float32: an interior point per problem,
    unit normals uniform on the circle, offsets that leave the point
    strictly feasible, a unit objective.  Returns ``(A, b, c)`` of
    shapes (batch, m, 2), (batch, m), (batch, 2)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.float32
    k1, k2, k3, k4 = jax.random.split(key, 4)
    xstar = jax.random.uniform(k1, (batch, 1, 2), dt, -radius / 2,
                               radius / 2)
    theta = jax.random.uniform(k2, (batch, m), dt, 0.0, 2.0 * np.pi)
    A = jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)
    s = jax.random.uniform(k3, (batch, m), dt, 0.1, slack)
    b = jnp.einsum("bmd,bmd->bm", A, jnp.broadcast_to(xstar, A.shape)) + s
    phi = jax.random.uniform(k4, (batch,), dt, 0.0, 2.0 * np.pi)
    c = jnp.stack([jnp.cos(phi), jnp.sin(phi)], axis=-1)
    return A, b, c


def jax_key(seed: int):
    """A JAX key for any non-negative seed, 64-bit ones included."""
    import jax
    lo, hi = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(lo)), int(hi))


def stratified(rng: np.random.Generator, n: int, shares) -> np.ndarray:
    """``n`` category indices whose counts follow ``shares`` as closely as
    whole numbers allow (largest remainders win), in a random order: every
    seed gets the same multiset, shuffled."""
    shares = np.asarray(shares, np.float64)
    shares = shares / shares.sum()
    counts = np.floor(shares * n).astype(np.int64)
    rest = n - counts.sum()
    if rest:
        order = np.argsort(-(shares * n - counts), kind="stable")
        counts[order[:rest]] += 1
    out = np.repeat(np.arange(len(shares)), counts)
    rng.shuffle(out)
    return out


def ladder(key, kinds: np.ndarray, m: int):
    """``len(kinds)`` requests of ``m`` constraints, built as
    serve_lp.bench builds them one at a time: unit normals on the circle,
    slack in [0.1, 5) around a point in [-50, 50)^2; ``degenerate`` moves
    every constraint to pass through a second point; ``infeasible``
    replaces the first two by x <= -1 and -x <= -1.  ``kinds`` indexes
    KINDS.  Made on the default device in float32 from ``key``, in
    chunks of LADDER_CHUNK requests of one shape.  Returns host float32
    ``(A (k, m, 2), b (k, m), c (k, 2))`` and the points ``(k, 2)``."""
    import jax
    k = len(kinds)
    out = [np.empty((k, m, 2), np.float32), np.empty((k, m), np.float32),
           np.empty((k, 2), np.float32), np.empty((k, 2), np.float32)]
    for n, lo in enumerate(range(0, k, LADDER_CHUNK)):
        part = np.zeros(LADDER_CHUNK, np.int32)
        hi = min(k, lo + LADDER_CHUNK)
        part[:hi - lo] = kinds[lo:hi]
        got = _ladder_jit()(jax.random.fold_in(key, n), part, m)
        for o, g in zip(out, got):
            o[lo:hi] = np.asarray(g)[:hi - lo]
    return tuple(out)


LADDER_CHUNK = 2048


@functools.cache
def _ladder_jit():
    import jax
    return jax.jit(_ladder_body, static_argnums=(2,))


def _ladder_body(key, kinds, m):
    import jax
    import jax.numpy as jnp
    k = kinds.shape[0]
    f32 = jnp.float32
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    xstar = jax.random.uniform(k1, (k, 2), f32, -50.0, 50.0)
    theta = jax.random.uniform(k2, (k, m), f32, 0.0, 2.0 * np.pi)
    A = jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)
    s = jax.random.uniform(k3, (k, m), f32, 0.1, 5.0)
    phi = jax.random.uniform(k4, (k,), f32, 0.0, 2.0 * np.pi)
    c = jnp.stack([jnp.cos(phi), jnp.sin(phi)], axis=-1)
    x2 = jax.random.uniform(k5, (k, 2), f32, -50.0, 50.0)
    deg = (kinds == KINDS.index("degenerate"))[:, None]
    point = jnp.where(deg, x2, xstar)
    b = A[..., 0] * point[:, None, 0] + A[..., 1] * point[:, None, 1]
    b = jnp.where(deg, b, b + s)
    if m >= 2:
        bad = (kinds == KINDS.index("infeasible"))[:, None]
        first = jnp.arange(m)[None, :] < 2
        pair_a = jnp.where(jnp.arange(m)[None, :, None] == 0,
                           jnp.asarray([1.0, 0.0], f32),
                           jnp.asarray([-1.0, 0.0], f32))
        A = jnp.where((bad & first)[..., None], pair_a, A)
        b = jnp.where(bad & first, -1.0, b)
    return A, b, c, point
