"""Packed struct-of-arrays constraint layout — the canonical device form.

The paper's central memory claim is that "combining the information
into one extended set of data ensures scattered reads use as much of
each cache line as possible".  :class:`PackedLPBatch` is that layout as
a first-class pytree: constraints live in one block ``L (B, 4, m_pad)``
with rows ``(a_x, a_y, b, 0)`` and the constraint index on the minor
axis (the 128-lane axis on TPU), objectives in ``c (B, 2)`` and the
ragged valid counts in ``m_valid (B, 1)`` (kept 2-D so every kernel
intermediate stays >= 2-D).

``pack``/``unpack`` convert losslessly to and from the AoS
:class:`~repro.core.lp.LPBatch`; every batch utility in ``lp`` has a
packed-native twin here (``normalize_packed``, ``shuffle_packed``,
``pad_packed``, ``pad_packed_batch_dim``, ``concat_packed``,
``split_packed``) computing the *same scalar pipeline*, so a batch
packs once and solves bit-identically to the AoS path — without ever
round-tripping back to AoS.  (For ``shuffle=True`` solves the
bit-identity needs the default ``m_pad == m`` pack: extra constraint
padding — in either layout — changes the shuffle's score-draw shape,
leaving results equal only to the usual order-invariance tolerance.)

``pack`` is the only AoS -> SoA conversion in the tree and counts its
invocations (:func:`pack_call_count`); the serving layer's zero-repack
guarantee is asserted against that counter.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.lp import PAD_B, LPBatch, _row_norms

# AoS -> SoA conversion counter.  Incremented by ``pack`` only (at trace
# time under jit): a hot path that never repacks leaves it untouched.
_PACK_CALLS = 0


def pack_call_count() -> int:
    """Total ``pack`` invocations in this process (trace-time under
    jit).  Diff around a code path to prove it does no AoS -> SoA
    repacking."""
    return _PACK_CALLS


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedLPBatch:
    """A batch of 2-D LPs in the packed struct-of-arrays layout.

    ``L[b, 0, h]``/``L[b, 1, h]`` are the constraint normal components,
    ``L[b, 2, h]`` the offset, ``L[b, 3, h]`` zero padding (keeps the
    sublane count a power of two).  Columns ``h >= m_valid[b, 0]`` are
    the neutral constraint ``0*x <= 1``.
    """

    L: jax.Array        # (B, 4, m_pad) packed (a_x, a_y, b, 0)
    c: jax.Array        # (B, 2) objective directions (maximize)
    m_valid: jax.Array  # (B, 1) int32 valid (non-padding) rows

    @property
    def batch(self) -> int:
        return self.L.shape[0]

    @property
    def m_pad(self) -> int:
        return self.L.shape[2]

    # Row views (no copies: slices of L).
    @property
    def ax(self) -> jax.Array:
        return self.L[:, 0, :]

    @property
    def ay(self) -> jax.Array:
        return self.L[:, 1, :]

    @property
    def b(self) -> jax.Array:
        return self.L[:, 2, :]

    def unpack(self) -> LPBatch:
        return unpack(self)


def pack(batch: LPBatch, m_pad: int | None = None) -> PackedLPBatch:
    """AoS -> SoA: the one conversion point (counted).

    ``m_pad`` pads the constraint axis with neutral rows; the default
    (``m``) makes ``unpack(pack(batch))`` exactly lossless.  Layout
    consumers with alignment needs (the Pallas kernel wants 128-lane
    multiples) pad further via :func:`pad_packed`.
    """
    global _PACK_CALLS
    _PACK_CALLS += 1
    B, m = batch.batch, batch.m
    if m_pad is None:
        m_pad = m
    if m_pad < m:
        raise ValueError(f"m_pad={m_pad} < m={m}")
    dt = batch.A.dtype
    ax = batch.A[..., 0]
    ay = batch.A[..., 1]
    bb = batch.b
    zeros = jnp.zeros_like(ax)
    L = jnp.stack([ax, ay, bb, zeros], axis=1)  # (B, 4, m)
    pb = PackedLPBatch(L=L, c=batch.c.astype(dt),
                       m_valid=batch.m_valid.reshape(B, 1))
    return pad_packed(pb, m_pad)


def unpack(pb: PackedLPBatch) -> LPBatch:
    """SoA -> AoS (padding columns kept as neutral rows)."""
    A = jnp.stack([pb.L[:, 0, :], pb.L[:, 1, :]], axis=-1)  # (B, m_pad, 2)
    return LPBatch(A=A, b=pb.L[:, 2, :], c=pb.c,
                   m_valid=pb.m_valid.reshape(-1).astype(jnp.int32))


def _with_pad_b(L: jax.Array, *, axis: int, start: int) -> jax.Array:
    """``L`` with the offset row set to ``PAD_B`` at indices >= ``start``
    of ``axis``: the neutral ``b`` of zero-padded columns or problems.

    A select, not a scatter, so XLA fuses it with the ``jnp.pad`` that
    made ``L`` into one elementwise pass.  ``.at[...].set`` into the
    padded buffer stays a separate dynamic-update-slice at an unaligned
    lane offset (3.4 ms for 16384 x 4 x 128 float32 on a TPU v5e).
    """
    row = jax.lax.broadcasted_iota(jnp.int32, L.shape, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, L.shape, axis)
    return jnp.where((row == 2) & (idx >= start),
                     jnp.asarray(PAD_B, L.dtype), L)


def pad_packed(pb: PackedLPBatch, m_pad: int) -> PackedLPBatch:
    """Pad the constraint axis up to ``m_pad`` with neutral columns
    (a = 0, b = 1) — the packed twin of ``lp.pad_batch``."""
    m = pb.m_pad
    if m_pad < m:
        raise ValueError(f"m_pad={m_pad} < m_pad={m}")
    if m_pad == m:
        return pb
    L = _with_pad_b(jnp.pad(pb.L, ((0, 0), (0, 0), (0, m_pad - m))),
                    axis=2, start=m)
    return PackedLPBatch(L=L, c=pb.c, m_valid=pb.m_valid)


def pad_packed_batch_dim(pb: PackedLPBatch, b_pad: int) -> PackedLPBatch:
    """Pad the batch axis up to ``b_pad`` with neutral problems
    (m_valid=0, c=(1,0)) — the packed twin of ``lp.pad_batch_dim``."""
    B = pb.batch
    if b_pad < B:
        raise ValueError(f"b_pad={b_pad} < batch={B}")
    if b_pad == B:
        return pb
    pad = b_pad - B
    L = _with_pad_b(jnp.pad(pb.L, ((0, pad), (0, 0), (0, 0))),
                    axis=0, start=B)
    c = jnp.concatenate(
        [pb.c, jnp.broadcast_to(jnp.asarray([1.0, 0.0], pb.c.dtype),
                                (pad, 2))])
    mv = jnp.concatenate(
        [pb.m_valid, jnp.zeros((pad, 1), pb.m_valid.dtype)])
    return PackedLPBatch(L=L, c=c, m_valid=mv)


def concat_packed(pbs: list[PackedLPBatch]) -> PackedLPBatch:
    """Fuse packed batches along the batch axis (members padded with
    neutral columns to the largest ``m_pad``) — twin of
    ``lp.concat_batches``."""
    if not pbs:
        raise ValueError("concat_packed of empty list")
    m_max = max(pb.m_pad for pb in pbs)
    padded = [pad_packed(pb, m_max) for pb in pbs]
    return PackedLPBatch(
        L=jnp.concatenate([pb.L for pb in padded]),
        c=jnp.concatenate([pb.c for pb in padded]),
        m_valid=jnp.concatenate([pb.m_valid for pb in padded]),
    )


def split_packed(pb: PackedLPBatch, sizes: list[int],
                 *, allow_remainder: bool = False) -> list[PackedLPBatch]:
    """Inverse of :func:`concat_packed` — twin of ``lp.split_batch``
    (same remainder policy)."""
    total = sum(sizes)
    if total > pb.batch:
        raise ValueError(f"split sizes {sizes} exceed batch {pb.batch}")
    if total < pb.batch and not allow_remainder:
        raise ValueError(
            f"split sizes {sizes} sum to {total} < batch {pb.batch}; "
            "pass allow_remainder=True to drop the trailing problems")
    out, lo = [], 0
    for s in sizes:
        out.append(PackedLPBatch(L=pb.L[lo:lo + s], c=pb.c[lo:lo + s],
                                 m_valid=pb.m_valid[lo:lo + s]))
        lo += s
    return out


def normalize_packed(pb: PackedLPBatch, eps: float = 1e-30
                     ) -> PackedLPBatch:
    """Scale every constraint column so ||a_h|| = 1 — the packed twin of
    ``lp.normalize_batch``, computing the identical scalar pipeline so
    packed and AoS solves stay bit-identical.  Zero-norm (padding)
    columns keep scale 1; the zero sublane rides along (0 * s = 0)."""
    n = _row_norms(pb.ax, pb.ay)  # (B, m_pad)
    is_pad = n < eps
    scale = jnp.where(is_pad, 1.0, 1.0 / jnp.maximum(n, eps))
    return PackedLPBatch(L=pb.L * scale[:, None, :], c=pb.c,
                         m_valid=pb.m_valid)


def shuffle_packed(key: jax.Array, pb: PackedLPBatch) -> PackedLPBatch:
    """Random per-problem constraint order (the R in RGB) — the packed
    twin of ``lp.shuffle_batch``: same score draw, same masking, same
    argsort, so the permutation (and therefore the solve) is
    bit-identical to shuffling the AoS batch when ``m_pad`` matches its
    constraint count.  Padding columns stay at the tail."""
    B, m_pad = pb.batch, pb.m_pad
    scores = jax.random.uniform(key, (B, m_pad))
    idx = jnp.arange(m_pad)[None, :]
    scores = jnp.where(idx < pb.m_valid, scores, jnp.inf)
    order = jnp.argsort(scores, axis=-1)  # (B, m_pad)
    return PackedLPBatch(
        L=jnp.take_along_axis(pb.L, order[:, None, :], axis=2),
        c=pb.c, m_valid=pb.m_valid)
