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
on numpy or JAX integers.  :func:`blockdiff_attention` is the kernel: JAX's
pallas splash attention (``jax.experimental.pallas.ops.tpu.splash_attention``)
over a mask it computes from the rule inside the kernel, so it visits only
the tiles the rule allows (80 of 256 at ``L`` = 8192 with tiles of 1024),
keeps no ``[2L, 2L]`` table anywhere, and serves grouped KV heads without
repeating them.  On the device's op line its three kernels are
``splash_mha_fwd_residuals``, ``splash_mha_dq_no_residuals`` and
``splash_mha_dkv_no_residuals`` (:data:`OP_LINE_NAMES`); the calls lie under
``jax.named_scope("hvd.attn.blockdiff")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SCOPE = "hvd.attn.blockdiff"
# A regular expression for the kernels' names on the device's op line.
OP_LINE_NAMES = r"^splash_mha_(fwd|dq|dkv)"

# The kernels' tiles (splash attention's ``BlockSizes``: queries x keys of the
# forward, the dkv and the dq kernel, and the keys the forward and the dkv
# kernel multiply at a time), and with them the shortest half the kernel
# takes: a tile never straddles the two halves.  Measured on a v5e at
# L = 8192, b = 4, 32 query heads on 4 KV heads of 128, forward + backward
# (PERF.md, PR 31): tiles of 256 94.4 ms, of 512 46.1, of 1024 42.2, these
# 40.7; keys or queries of 2048 are slower or do not fit the fast memory.
BLOCK = 1024
_TILES = dict(block_q=BLOCK, block_kv=BLOCK, block_kv_compute=BLOCK // 2,
              block_q_dkv=BLOCK, block_kv_dkv=BLOCK,
              block_kv_dkv_compute=BLOCK // 2, block_q_dq=BLOCK,
              block_kv_dq=BLOCK)


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
    half = seq_len // 2
    return seq_len % 2 == 0 and half % BLOCK == 0 and head_dim % 128 == 0 \
        and block & (block - 1) == 0 and BLOCK % block == 0


def _code(ids, half_len: int, block: int):
    """``2 * B(p) + H(p)``: one number a position that decides the rule.  For
    a key code ``c`` and a query code ``r``: allowed iff ``c == r`` (same
    block of the same half) or ``c`` is odd (a clean key) and ``c < r`` (an
    earlier block; for a clean query ``c < r`` also excludes its own block,
    which ``c == r`` lets in)."""
    clean = ids >= half_len
    shift = block.bit_length() - 1
    return (((ids - clean * half_len) >> shift) << 1) | clean


@functools.lru_cache(maxsize=None)
def _mask_class():
    """The mask's class, made once: the library is imported only where the
    kernel is used."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib,
    )

    class BlockDiffusionMask(mask_lib._ComputableMask):
        """The rule as a mask splash attention computes inside its kernels.
        The kernel hands ``mask_function`` the rows' entries of
        ``q_sequence``, here already the queries' codes, and the keys' plain
        positions."""

        def __init__(self, half_len: int, block: int):
            def mask_function(q_codes, kv_ids):
                c = _code(kv_ids, half_len, block)
                return (c == q_codes) | (((c & 1) == 1) & (c < q_codes))

            super().__init__(shape=(2 * half_len, 2 * half_len),
                             mask_function=mask_function)
            self.q_sequence = _code(np.arange(2 * half_len, dtype=np.int32),
                                    half_len, block).astype(np.int32)
            self.rule = (half_len, block)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.rule == other.rule

        def __hash__(self):
            return hash((type(self).__name__, self.rule))

    return BlockDiffusionMask


def _make_mask(half_len: int, block: int):
    return _mask_class()(half_len, block)


@functools.lru_cache(maxsize=8)
def _kernel(half_len: int, block: int, heads: int, interpret: bool):
    """The splash kernel for one shape; building it walks the rule tile by
    tile on the host, once."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )

    mask = mask_lib.MultiHeadMask([_make_mask(half_len, block)] * heads)
    # Mask information is made of numpy arrays here, whatever trace is open.
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=splash.BlockSizes(**_TILES), head_shards=1,
            q_seq_shards=1, interpret=interpret)


def blockdiff_attention(q, k, v, *, block: int, interpret: bool = False):
    """Softmax attention of ``q [b, 2L, h, d]`` on ``k, v [b, 2L, h_kv, d]``
    under the block-diffusion mask with blocks of ``block`` tokens, scores
    scaled by ``d ** -0.5``; ``h_kv`` divides ``h`` and KV head ``j`` serves
    query heads ``j*h/h_kv`` to ``(j+1)*h/h_kv - 1``.  Returns ``[b, 2L, h,
    d]``.  Differentiable (the library's dq and dkv kernels)."""
    _, s, h, d = q.shape
    if not takes(s, d, block):
        raise ValueError(f"no block-diffusion kernel for {s} positions, "
                         f"head width {d}, blocks of {block}")
    kernel = _kernel(s // 2, block, h, interpret)
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    with jax.named_scope(SCOPE):
        out = jax.vmap(kernel)(hsd(q * jnp.asarray(d ** -0.5, q.dtype)),
                               hsd(k), hsd(v))
    return out.transpose(0, 2, 1, 3)
