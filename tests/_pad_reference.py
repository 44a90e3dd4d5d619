"""The packed padding as a pad followed by a scatter of the neutral
offsets: the form ``core.packed`` computed before it wrote ``b`` in the
pad's own pass.  Tests hold today's padding bit-identical to it."""
import jax.numpy as jnp

from repro.core import PackedLPBatch
from repro.core.lp import PAD_B


def scatter_pad_packed(pb: PackedLPBatch, m_pad: int) -> PackedLPBatch:
    m = pb.m_pad
    if m_pad == m:
        return pb
    L = jnp.pad(pb.L, ((0, 0), (0, 0), (0, m_pad - m)))
    L = L.at[:, 2, m:].set(jnp.asarray(PAD_B, L.dtype))
    return PackedLPBatch(L=L, c=pb.c, m_valid=pb.m_valid)


def scatter_pad_packed_batch_dim(pb: PackedLPBatch,
                                 b_pad: int) -> PackedLPBatch:
    B = pb.batch
    if b_pad == B:
        return pb
    pad = b_pad - B
    L = jnp.pad(pb.L, ((0, pad), (0, 0), (0, 0)))
    L = L.at[B:, 2, :].set(jnp.asarray(PAD_B, L.dtype))
    c = jnp.concatenate(
        [pb.c, jnp.broadcast_to(jnp.asarray([1.0, 0.0], pb.c.dtype),
                                (pad, 2))])
    mv = jnp.concatenate(
        [pb.m_valid, jnp.zeros((pad, 1), pb.m_valid.dtype)])
    return PackedLPBatch(L=L, c=c, m_valid=mv)
