"""Compile the expert layer at the sparse cells' published widths for a TPU
v5e that is described, not attached (``tests/test_tpu_compile.py`` says how
and why): the layer whole and as a share of held experts, the share's
rows-to-tokens kernel alone and inside the share at the three cells that run
``moe_ffn(held=)``, and Nemotron's gateless latent share.  Nothing runs, so
nothing here is a result or a time.

In a file of its own: the eighteen cases are two and a half minutes, and a
file is what a test worker takes.
"""

import re

import jax
import jax.numpy as jnp
import pytest

# The fixtures that describe the chip and switch the compile cache off are
# that file's; pytest makes a module-scoped one anew for this module.
from .test_tpu_compile import (  # noqa: F401
    _shape,
    no_compile_cache,
    one_chip,
    topo,
)


def test_expert_layer_compiles_at_published_widths(one_chip,
                                                   no_compile_cache):
    """8192 tokens through 64 experts of 2048 x 1024, 8 a token: the grouped
    products are XLA's grouped-matmul kernels and their work is the routed
    rows, not 64 experts a token."""
    from horovod_tpu.parallel.moe import moe_ffn

    d, f, e, k = 2048, 1024, 64, 8
    args = [_shape((2, 4096, d), jnp.bfloat16, one_chip),
            _shape((d, e), jnp.float32, one_chip),
            _shape((e, d, f), jnp.float32, one_chip),
            _shape((e, d, f), jnp.float32, one_chip),
            _shape((e, f, d), jnp.float32, one_chip)]

    def loss(*a):
        y, stats = moe_ffn(*a, k=k)
        return jnp.sum(y.astype(jnp.float32)) \
            + jnp.sum(stats.load_balancing_loss)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* =", text)) == 9
    # Dispatch and combine are gathers in both directions: no scatter of
    # 4 KB rows (top-k's own cotangent is a scatter of 65536 scalars).
    assert not re.findall(r"= \w+\[\d+,2048\]\S* scatter\(", text)
    routed = 9 * 2 * (8192 * k) * d * f
    flops = compiled.cost_analysis()["flops"]
    assert routed < flops < 1.15 * routed, (flops, routed)


def test_expert_share_compiles_at_published_widths(one_chip,
                                                   no_compile_cache):
    """16384 positions through the 16 held of 128 experts of 2048 x 768, 8 a
    token: the first chunk's nine grouped products over 20,480 places (five
    quarters of the mean share), the chunks of 4096 behind it in a loop
    whose trip count follows the rows, and on the way back to token order
    nothing the size of every routed slot."""
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    d, f, e, held, k = 2048, 768, 128, 16, 8
    args = [_shape((1, 16384, d), jnp.bfloat16, one_chip),
            _shape((d, e), jnp.float32, one_chip),
            _shape((held, d, f), jnp.float32, one_chip),
            _shape((held, d, f), jnp.float32, one_chip),
            _shape((held, f, d), jnp.float32, one_chip)]
    assert row_buffer(16384 * k, held, e) == (28, 20480)
    assert row_quantum(16384 * k, held, e) == 4096

    def loss(*a):
        # Not linear in y, so that the combine's forward stays in the program.
        y, stats = moe_ffn(*a, k=k, held=tuple(range(held)),
                           norm_topk_prob=True)
        return jnp.sum(y.astype(jnp.float32) ** 2) \
            + jnp.sum(stats.load_balancing_loss)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    # 9 of the first chunk, 6 over its rows and 3 that give the weights'
    # gradients; the loops behind it add their 3 forward and, in the
    # backward pass, the same 3 again (recomputed, not kept) and 6 more.
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    assert len(products) == 21, products
    assert products.count("20480") == 6 and products.count("4096") == 9
    assert products.count("16") == 6 and "[32768," not in text
    # Two loops, forward and backward, where the parent scanned over three
    # conditionals in each direction.
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    # Rows are fetched for a chunk's 20480 places and added up by token into
    # [16384, 2048], in both directions: no gather, fusion or anything else
    # has a row for each of the 131072 routed slots (PR 32; the parent
    # gathered [131072, 2048] twice a chunk).  The row scatter-adds are the
    # measured choice (PERF.md, PR 32): 3.0-3.2 ms for 32768 rows on a v5e
    # against 5.6 for the gather of 131072 and its sum over k.
    assert not re.findall(r"= \(?\w+\[131072,2048\]", text)
    scatters = re.findall(r"= \w+\[(\d+),2048\]\S* scatter\(", text)
    assert scatters and set(scatters) == {"16384"}, scatters
    # The parent's (2f6b8c4) count for this program, a first chunk of 32768
    # places, was 1,734,507,520 bytes.
    # PR 39's was 780,872,704; since PR 45 (the router's logits as bf16
    # products over the split weights and their cotangents' pieces)
    # 781,324,288.
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_000_000_000


# (tokens, d, k, held, experts, width, activation, the most temporary bytes:
# what the parent, 2f6b8c4, took with first chunks of twice the mean share;
# this tree takes 743,271,936, 695,154,688 and 882,345,984): the three cells
# that run moe_ffn(held=); LFM2's by its sizes alone, the router's scoring
# changes nothing here.
_SHARE_CELLS = {
    "smallthinker-21b-a3b": (16384, 2560, 6, 8, 64, 768, "relu",
                             1_337_387_520),
    "sdar-30b-a3b": (16384, 2048, 8, 16, 128, 768, "silu", 1_783_124_480),
    "lfm2-8b-a1b": (16384, 2048, 4, 8, 32, 1792, "silu", 1_833_361_920),
}


@pytest.mark.parametrize("weighted", [False, True], ids=["ones", "weighted"])
@pytest.mark.parametrize("chunk", ["first", "quarter"])
@pytest.mark.parametrize("cell", sorted(_SHARE_CELLS))
def test_rows_to_tokens_compiles_at_the_cells_shapes(cell, chunk, weighted,
                                                     one_chip,
                                                     no_compile_cache):
    """``kernels/rows_to_tokens.py`` for the first chunk of each cell (15,360
    rows of 2560 in 8 runs, 20,480 of 2048 in 16 and in 8) and for a quarter
    of the mean share behind it (3072, 4096), with the router's weights and
    without: the chip's compiler takes the copies of 16-row pieces, the
    transposes of the tokens and weights and the scalars it prefetches."""
    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import row_buffer, row_quantum

    tokens, d, k, held, experts = _SHARE_CELLS[cell][:5]
    cap = row_buffer(tokens * k, held, experts)[1] if chunk == "first" \
        else row_quantum(tokens * k, held, experts)
    assert rt.takes(cap, d, tokens)
    args = [_shape((cap, d), jnp.bfloat16, one_chip),
            _shape((cap,), jnp.int32, one_chip),
            _shape((held,), jnp.int32, one_chip)]
    if weighted:
        args.append(_shape((cap,), jnp.float32, one_chip))
    text = jax.jit(lambda r, t, g, w=None: rt.rows_to_tokens(
        r, t, g, tokens, w)).lower(*args).compile().as_text()
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 1
    assert f"f32[{tokens},{d}]" in text and " scatter(" not in text


@pytest.mark.parametrize("cell", sorted(_SHARE_CELLS))
def test_expert_share_through_the_rows_kernel_compiles(cell, topo,
                                                       no_compile_cache,
                                                       monkeypatch):
    """The share of a layer as the cells run it on the chip (under the one
    device's mesh, so inside ``moe_ffn``'s shard_map), with the way back to
    token order through the kernel: four calls (the first chunk's combine and
    dispatch cotangent, and those of the chunks behind it inside their
    loops), no scatter of rows left, grouped products over the first chunk's
    places and a quarter's and none over twice the mean, a ``while`` in each
    direction and no conditional, and less temporary memory than the
    parent's."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    tokens, d, k, held, experts, width, act, most = _SHARE_CELLS[cell]
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def shape(dims, dtype, spec=P()):
        return _shape(dims, dtype, NamedSharding(mesh, spec))

    args = [shape((1, tokens, d), jnp.bfloat16, P("data")),
            shape((d, experts), jnp.float32),
            shape((held, d, width), jnp.float32),
            shape((held, d, width), jnp.float32),
            shape((held, width, d), jnp.float32)]

    def loss(*a):
        y, stats = moe_ffn(*a, k=k, held=tuple(range(held)),
                           norm_topk_prob=True, activation=act,
                           data_axis="data")
        return jnp.sum(y.astype(jnp.float32) ** 2) \
            + jnp.sum(stats.load_balancing_loss)

    # The program asks which backend it runs on; here that is the CPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    text = compiled.as_text()
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 4
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    assert not re.findall(rf"= \w+\[\d+,{d}\]\S* scatter\(", text)
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    first = row_buffer(tokens * k, held, experts)[1]
    quantum = row_quantum(tokens * k, held, experts)
    assert len(products) == 21, products
    assert products.count(str(first)) == 6
    assert products.count(str(quantum)) == 9
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * most


def test_gateless_latent_expert_share_compiles_at_nemotrons_widths(
        topo, no_compile_cache, monkeypatch):
    """8192 positions, 22 of 512 experts a token, 8 held, rows of the latent
    1024 against experts of width 2688 without a gate, the router reading the
    model's 4096: a first chunk of 5120 places (the quarter of 704 rows
    rounded up to 1024) through the rows kernel, two grouped products forward
    where a gated expert has three, a ``while`` in each direction."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    tokens, d, latent, k, held, experts, width = 8192, 4096, 1024, 22, 8, \
        512, 2688
    assert row_buffer(tokens * k, held, experts) == (172, 5120)
    assert row_quantum(tokens * k, held, experts) == 1024
    assert rt.takes(5120, latent, tokens) and rt.takes(1024, latent, tokens)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def shape(dims, dtype, spec=P()):
        return _shape(dims, dtype, NamedSharding(mesh, spec))

    args = [shape((1, tokens, latent), jnp.bfloat16, P("data")),
            shape((1, tokens, d), jnp.float32, P("data")),
            shape((d, experts), jnp.float32),
            shape((held, latent, width), jnp.float32),
            shape((held, width, latent), jnp.float32),
            shape((experts,), jnp.float32)]

    def loss(rows, seen, router, up, down, bias):
        y, _ = moe_ffn(rows, router, None, up, down, k=k,
                       held=tuple(range(held)), norm_topk_prob=True,
                       router_input=seen, activation="relu2",
                       scoring="sigmoid", bias=bias, scale=5.0,
                       data_axis="data")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    text = compiled.as_text()
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    # 6 of the first chunk (2 forward, 2 over its rows and 2 that give the
    # stacks' gradients), and the loops' 2 forward, the same 2 recomputed
    # and 4 more backward.
    assert len(products) == 14, products
    assert products.count("5120") == 4 and products.count("1024") == 6
    assert products.count("8") == 4
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 4
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    assert not re.findall(r"= \(?\w+\[180224,1024\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29
