"""Softmax attention under a mask that is a rule, not a table.

One wrapper for every mask the models here state as a rule over (query
position, key position): the forward is JAX's pallas splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``), the backward one
kernel of this repo (``kernels/masked_attention_bwd.py``: dq, dk and dv from
a single pass).  Both compute the mask from the rule, so they visit only the
tiles it allows, no ``[s, s]`` table exists anywhere, and grouped KV heads are
served without repeating them.  A rule is a small hashable object with

- ``scope``: the ``jax.named_scope`` its kernel calls lie under;
- ``allowed(q_ids, kv_ids, seq_len)``: the rule itself, a boolean array, on
  numpy or JAX integers that broadcast against each other (the backward
  kernel's table of tiles and its mask in a partial tile come from it);
- ``allowed_pairs(seq_len)``: how many pairs it allows in one sequence;
- ``takes(seq_len)``: whether the kernels' tiles fit the rule at this length;
- ``mask(seq_len)``: the rule as a mask the library computes in its kernel.

The rules: :class:`Causal` (key <= query; ``hvd.attn.causal``),
:class:`Window` (causal, and the key inside the last ``size`` positions:
``hvd.attn.window``) and ``kernels/blockdiff_attention.py``'s
``BlockDiffusion``.  At 16,384 positions and tiles of 1024 a causal layer
visits 136 of 256 tiles and a window of 4096 visits 70.  :func:`attention` is
the kernel, :func:`einsum` the same mask through a grouped einsum (off the
TPU, and for shapes the kernel does not take).  On the device's op line the
two kernels are ``splash_mha_fwd_residuals`` and ``splash_mha_dkv_dq``
(:data:`OP_LINE_NAMES`) whatever the rule.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.timeline import scope
from . import masked_attention_bwd

# A regular expression for the kernels' names on the device's op line.
OP_LINE_NAMES = r"^splash_mha_(fwd|dq|dkv)"

# The forward kernel's tiles (splash attention's ``BlockSizes``: queries x
# keys, and the keys it multiplies at a time).  Measured on a v5e under the
# block-diffusion mask at 16,384 positions, 32 query heads on 4 KV heads of
# 128, forward + the library's backward (PERF.md, PR 31): tiles of 256 94.4
# ms, of 512 46.1, of 1024 42.2, these 40.7; keys or queries of 2048 are
# slower or do not fit the fast memory.
BLOCK = 1024
_TILES = dict(block_q=BLOCK, block_kv=BLOCK, block_kv_compute=BLOCK // 2)
# Float32 operands wider than a lane group (the float32 twin of latent
# attention, 192 wide, which holds a bf16 program's logits to a reference):
# at tiles of 1024 the library's forward asks the compiler for 16.9 MiB of
# its 16 of scoped fast memory (my chip run, PR 47); at 512 it fits.
_TILES_WIDE_FLOAT32 = dict(block_q=BLOCK // 2, block_kv=BLOCK // 2,
                           block_kv_compute=BLOCK // 2)
# The backward kernel's: queries x keys, and the keys multiplied at a time.
# At the same shape (PERF.md, PR 44; the backward alone, ms a layer, with the
# block rule still in three clauses): these 23.96, the keys 256 or 1024 at a
# time 23.88 and 23.93, queries of 512 25.12, of 2048 30.53 (fewer, larger
# tiles hold more forbidden pairs); by codes these read 20.35.
BWD_TILES = (BLOCK, BLOCK, BLOCK // 2)


def _mask_lib():
    """The library is imported only where a kernel is built."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask,
    )

    return splash_attention_mask


@dataclasses.dataclass(frozen=True)
class Causal:
    """Query i sees key j iff ``j <= i``."""

    scope = "hvd.attn.causal"

    def allowed(self, q_ids, kv_ids, seq_len=None):
        return kv_ids <= q_ids

    def allowed_pairs(self, seq_len: int) -> int:
        return seq_len * (seq_len + 1) // 2

    def takes(self, seq_len: int) -> bool:
        return seq_len % BLOCK == 0

    def mask(self, seq_len: int):
        return _mask_lib().CausalMask((seq_len, seq_len))


@dataclasses.dataclass(frozen=True)
class Window:
    """Query i sees key j iff ``j <= i`` and ``i - j < size``: itself and the
    ``size - 1`` positions before it."""

    size: int
    scope = "hvd.attn.window"

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"a window of {self.size} positions")

    def allowed(self, q_ids, kv_ids, seq_len=None):
        return (kv_ids <= q_ids) & (q_ids - kv_ids < self.size)

    def allowed_pairs(self, seq_len: int) -> int:
        beyond = max(seq_len - self.size, 0)
        return seq_len * (seq_len + 1) // 2 - beyond * (beyond + 1) // 2

    def takes(self, seq_len: int) -> bool:
        return seq_len % BLOCK == 0

    def mask(self, seq_len: int):
        return _mask_lib().LocalMask((seq_len, seq_len),
                                     window_size=(self.size - 1, 0), offset=0)


def takes(rule, seq_len: int, head_dim: int, head_dim_v=None) -> bool:
    """Whether the kernel takes this shape under ``rule``; otherwise, and off
    the TPU, the same mask goes through :func:`einsum`.  Heads of 64 go in
    as they are (LFM2-8B-A1B: 32 query heads on 8 KV heads): the library's
    kernels take half a lane group, and the chip's compiler pads it.
    ``head_dim_v``: the values' width where it is not the keys'; the one
    such pair taken is latent attention's 192 over 128 (JoyAI-LLM-Flash), a
    lane group and a half that the compiler pads likewise."""
    if head_dim_v not in (None, head_dim):
        return (head_dim, head_dim_v) == (192, 128) and rule.takes(seq_len)
    return (head_dim % 128 == 0 or head_dim == 64) and rule.takes(seq_len)


def _wide_float32(q) -> bool:
    """Whether operands like ``q [..., d]`` take the forward kernel's smaller
    tiles (:data:`_TILES_WIDE_FLOAT32`)."""
    return q.dtype.itemsize > 2 and q.shape[-1] > 128


@functools.lru_cache(maxsize=8)
def _kernel(rule, seq_len: int, heads: int, interpret: bool,
            wide_float32: bool = False):
    """The library's forward kernel for one rule and shape, which also
    returns the rows' log-sum-exp; building it walks the rule tile by tile
    on the host, once."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    mask = _mask_lib().MultiHeadMask([rule.mask(seq_len)] * heads)
    # Mask information is made of numpy arrays here, whatever trace is open.
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=splash.BlockSizes(
                **(_TILES_WIDE_FLOAT32 if wide_float32 else _TILES)),
            head_shards=1, q_seq_shards=1, save_residuals=True,
            interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, rule, interpret):
    """``[b, h, s, d]`` in and out, ``q`` scaled."""
    return _attend_fwd(q, k, v, rule, interpret)[0]


def _attend_fwd(q, k, v, rule, interpret):
    kernel = _kernel(rule, q.shape[2], q.shape[1], interpret,
                     _wide_float32(q))
    with scope(rule.scope.removeprefix("hvd.")):
        out, (logsumexp,) = jax.vmap(kernel)(q, k, v)
    return out, (q, k, v, out, logsumexp)


def _attend_bwd(rule, interpret, kept, do):
    q, k, v, out, logsumexp = kept
    with scope(rule.scope.removeprefix("hvd.")):
        di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                        do.astype(jnp.float32))
        return tuple(masked_attention_bwd.dq_dk_dv(
            q, k, v, logsumexp, di, do, rule=rule, tiles=BWD_TILES,
            interpret=interpret))


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention(q, k, v, rule, *, interpret: bool = False, scale=None):
    """Softmax attention of ``q [b, s, h, d]`` on ``k [b, s, h_kv, d]`` and
    ``v [b, s, h_kv, dv]`` under ``rule``, scores scaled by ``d ** -0.5``
    (by ``scale`` where one is given: Granite's ``attention_multiplier``);
    ``h_kv`` divides ``h`` and KV head ``j`` serves query heads ``j*h/h_kv``
    to ``(j+1)*h/h_kv - 1``.  Returns ``[b, s, h, dv]``.  Differentiable: the
    forward is the library's kernel, the backward
    ``kernels/masked_attention_bwd.py``'s one, both of which take ``q``
    already scaled and so know nothing of the scale."""
    _, s, h, d = q.shape
    if not takes(rule, s, d, v.shape[3]):
        raise ValueError(f"no kernel under {rule} for {s} positions, head "
                         f"width {d} over values of {v.shape[3]}")
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    # The copies into and out of the kernels' [heads, positions, width]
    # layout apart from the kernels, which alone lie under the rule's scope.
    if scale is None:
        scale = d ** -0.5
    with scope("attn.layout"):
        q, k, v = hsd(q * jnp.asarray(scale, q.dtype)), hsd(k), hsd(v)
    out = _attend(q, k, v, rule, interpret)
    with scope("attn.layout"):
        return out.transpose(0, 2, 1, 3)


def attention_hsd(q, k, v, rule):
    """:func:`attention` for a caller that builds its operands in the
    kernels' layout: ``q [b, h, s, d]`` already scaled by ``d ** -0.5``,
    ``k [b, h_kv, s, d]`` and ``v [b, h_kv, s, dv]``.  Returns
    ``[b, h, s, dv]``: no copy on either side of the kernels."""
    _, _, s, d = q.shape
    if not takes(rule, s, d, v.shape[3]):
        raise ValueError(f"no kernel under {rule} for {s} positions, head "
                         f"width {d} over values of {v.shape[3]}")
    return _attend(q, k, v, rule, False)


def _probabilities(scores, rule, dtype):
    """The softmax of ``scores [..., s, s]`` over the keys ``rule`` allows,
    the mask from iota comparisons."""
    s = scores.shape[-1]
    mask = rule.allowed(lax.broadcasted_iota(jnp.int32, (s, s), 0),
                        lax.broadcasted_iota(jnp.int32, (s, s), 1), s)
    scores = jnp.where(mask[(None,) * (scores.ndim - 2)], scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def einsum(q, k, v, rule, scale=None):
    """:func:`attention` through the einsum, KV heads grouped, the mask from
    iota comparisons: below the kernel's smallest shape, and off the TPU."""
    b, s, h, dh = q.shape
    h_kv = k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    with scope("attn.einsum"):
        q = q.reshape(b, s, h_kv, h // h_kv, dh)
        scores = jnp.einsum("bqngd,bknd->bngqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        return jnp.einsum("bngqk,bknd->bqngd",
                          _probabilities(scores, rule, q.dtype), v) \
            .reshape(b, s, h, v.shape[3])


def einsum_hsd(q, k, v, rule):
    """:func:`attention_hsd` through the einsum (``h_kv = h``): off the TPU,
    and for shapes the kernel does not take.  ``q`` comes scaled, as the
    kernels take it, where :func:`einsum` scales the fp32 scores: in bf16 the
    two round at different points and agree to bf16's rounding, in float32
    to float32's."""
    with scope("attn.einsum"):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          _probabilities(scores, rule, q.dtype), v)
