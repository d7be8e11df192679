"""Attention under the block-diffusion training mask.

Block-diffusion language models (BD3-LMs, arXiv:2503.09573; SDAR trains this
way) run the model once on ``[x_t ; x_0]``: ``2L`` positions, the first ``L``
the noised sequence, the last ``L`` the clean one, both cut into blocks of
``b`` tokens.  Position ``p`` has half ``H(p) = p // L`` (0 noisy, 1 clean),
index ``i(p) = p mod L`` and block ``B(p) = i(p) // b``.  Query ``p`` may see
key ``r`` iff

    (H(p)=0 and H(r)=0 and B(r) == B(p))   a noisy block sees itself,
 or (H(p)=0 and H(r)=1 and B(r) <  B(p))   and the clean blocks before it;
 or (H(p)=1 and H(r)=1 and B(r) <= B(p))   a clean block sees itself and
                                           the clean blocks before it.

``L*b + L(L-b)/2 + L(L+b)/2 = L**2 + L*b`` of the ``4 L**2`` pairs are
allowed: a quarter of the square.  :func:`block_diffusion_mask` is that rule,
on numpy or JAX integers, and :class:`BlockDiffusion` the rule in the form
``kernels/masked_attention.py`` takes: its two kernels compute the mask
from one code a position, visit only the tiles the rule allows (80 of 256 at
``L`` = 8192 with tiles of 1024), keep no ``[2L, 2L]`` table anywhere, and
serve grouped KV heads without repeating them.
``masked_attention.attention(q, k, v, BlockDiffusion(block))`` is those
kernels under this rule; the calls lie under
``jax.named_scope("hvd.attn.blockdiff")``.
"""

from __future__ import annotations

import dataclasses

from . import masked_attention
from .masked_attention import BLOCK, OP_LINE_NAMES  # noqa: F401

SCOPE = "hvd.attn.blockdiff"


def block_diffusion_mask(q_ids, kv_ids, half_len: int, block: int):
    """The three-clause rule above: a boolean array, True where the query
    may see the key.  ``q_ids`` and ``kv_ids`` are integer positions in
    ``[0, 2 * half_len)`` that broadcast against each other, numpy or JAX."""
    hq, hk = q_ids >= half_len, kv_ids >= half_len
    bq = (q_ids - hq * half_len) // block
    bk = (kv_ids - hk * half_len) // block
    return (~hq & ~hk & (bk == bq)) | (~hq & hk & (bk < bq)) \
        | (hq & hk & (bk <= bq))


def allowed_pairs(half_len: int, block: int) -> int:
    """How many (query, key) pairs the rule allows: ``L**2 + L*b``."""
    return half_len * half_len + half_len * block


def takes(seq_len: int, head_dim: int, block: int) -> bool:
    """Whether the kernel takes this shape (``seq_len`` = 2L positions);
    otherwise, and off the TPU, the same mask goes through the einsum."""
    return masked_attention.takes(BlockDiffusion(block), seq_len, head_dim)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The rule over ``[x_t ; x_0]`` with blocks of ``block`` tokens, for
    ``masked_attention``; the sequence it is given is the ``2L`` positions.
    A tile never straddles the two halves, and the block length is a power
    of two that divides a tile."""

    block: int
    scope = SCOPE

    def allowed(self, q_ids, kv_ids, seq_len):
        """:func:`block_diffusion_mask` by one number a position,
        ``2 * B(p) + H(p)``: the key's equals the query's (the same block of
        the same half), or it is odd (a clean key) and less (an earlier
        block; for a clean query that leaves out its own block, which the
        equality lets in).  The number is made by a division, so that any
        block length does, and on the positions as they come, so ids that
        broadcast against each other (the kernels', a row against a column)
        cost two comparisons a pair."""
        half_len = seq_len // 2

        def code(ids):
            clean = ids >= half_len
            return (ids - clean * half_len) // self.block * 2 + clean

        q_code, kv_code = code(q_ids), code(kv_ids)
        # An even code (a noisy key) is never less than a query's.
        earlier_clean = kv_code + (1 - (kv_code & 1)) * (1 << 30)
        return (kv_code == q_code) | (earlier_clean < q_code)

    def allowed_pairs(self, seq_len: int) -> int:
        return allowed_pairs(seq_len // 2, self.block)

    def takes(self, seq_len: int) -> bool:
        return seq_len % 2 == 0 and (seq_len // 2) % BLOCK == 0 \
            and self.block & (self.block - 1) == 0 \
            and BLOCK % self.block == 0
