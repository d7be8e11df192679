"""The router's logits of a bf16 stream (PR 45): ``parallel/moe.py::_rows_dot``
takes rows that are a bfloat16 array as one bf16 product over the split
weights (three passes where the highest precision makes six), hands the
norm's two factors through so that the rows are the stream and not its
normalised copy in fp32, and leaves every other dtype the line it had.  The
routers here are LFM2's and the siblings' shapes; ``tests/test_nemotron.py``
runs the same checks over 512 outputs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from . import test_lfm2
from .test_olmoe import layer_inputs, rel_err

def router_case(name, seed=0, factors=True, n=256, d=64):
    """(bf16 rows [n, d], router, the norm's two factors or None twice,
    bias or None, what ``_route`` takes besides) of a router like a
    sibling's: ``softmax`` 64 outputs, ``sigmoid_bias`` 32 with a bias,
    ``sigmoid`` 512 with the top 22 renormalised and scaled."""
    experts, k, options = {
        "softmax": (64, 8, dict(norm_topk_prob=False)),
        "sigmoid_bias": (32, 4, dict(norm_topk_prob=True,
                                     scoring="sigmoid")),
        "sigmoid": (512, 22, dict(norm_topk_prob=True, scoring="sigmoid",
                                  scale=5.0))}[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d)).astype(jnp.bfloat16)
    router = 0.3 * jax.random.normal(ks[1], (d, experts))
    r = jnp.exp(0.3 * jax.random.normal(ks[2], (n,))) if factors else None
    g = 1.0 + 0.2 * jax.random.normal(ks[3], (d,)) if factors else None
    bias = 0.05 * jax.random.normal(ks[4], (experts,)) \
        if name == "sigmoid_bias" else None
    return x, router, r, g, bias, k, options


def logical_rows(x, r, g):
    """``x * r * g`` in fp32: what the router read before PR 45."""
    rows = x.astype(jnp.float32)
    if r is not None:
        rows = rows * r[:, None]
    return rows if g is None else rows * g


def check_three_pass_logits(name, factors):
    from horovod_tpu.parallel.moe import _logits, _route

    x, router, r, g, bias, k, options = router_case(name, factors=factors)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    want = f64(logical_rows(x, None, None))
    if factors:
        want = want * f64(r)[:, None] * f64(g)
    want = want @ f64(router)
    new = _logits(x, router, r, g)
    old = jnp.dot(logical_rows(x, r, g), router,
                  precision=lax.Precision.HIGHEST)
    assert new.dtype == jnp.float32
    error = lambda z: np.abs(f64(z) - want).max()  # noqa: E731
    # The old line rounds x * r * g to fp32 twice before it multiplies;
    # without factors both sum exact products in fp32, in another order
    # (measured here: 0.2 to 0.3 of the old line's error with factors, 0.6
    # to 1.1 without).
    assert error(new) <= (1.0 if factors else 1.5) * error(old)
    # The experts chosen are float64's wherever its scores leave fp32 room.
    scores = 1 / (1 + np.exp(-want)) if options.get("scoring") \
        else np.exp(want - want.max(-1, keepdims=True))
    if not options.get("scoring"):
        scores = scores / scores.sum(-1, keepdims=True)
    chosen_by = scores if bias is None else scores + f64(bias)
    ranked = np.sort(chosen_by, axis=-1)[:, ::-1]
    clear = ranked[:, k - 1] - ranked[:, k] > 1e-5 * ranked[:, :1].max()
    assert clear.mean() > 0.9
    experts = _route(x, router, k, bias=bias, row_scale=r, col_scale=g,
                     **options)[1]
    want_experts = np.argsort(-chosen_by, axis=-1, kind="stable")[:, :k]
    np.testing.assert_array_equal(
        np.sort(np.asarray(experts), axis=-1)[clear],
        np.sort(want_experts, axis=-1)[clear])


@pytest.mark.parametrize("name,factors", [
    ("softmax", True), ("softmax", False), ("sigmoid_bias", True),
    ("sigmoid_bias", False)])
def test_three_pass_logits_are_the_float64_product(name, factors):
    """The logits of bf16 rows, three bf16 products over the split weights,
    lie no further from a float64 product of ``x * r * g`` and the router
    than the highest-precision line's, and choose float64's experts."""
    check_three_pass_logits(name, factors)


def test_factors_beside_float32_rows_are_refused():
    """``RouterRows`` are a bfloat16 stream's: rows of another dtype run the
    highest-precision line on plain rows and take no factors."""
    from horovod_tpu.parallel.moe import RouterRows, moe_ffn

    x, router, gate, up, down = layer_inputs(6, rows=4, experts=8)
    factors = jnp.ones(x.shape[:2]), jnp.ones(x.shape[2:])
    with pytest.raises(ValueError, match="bfloat16 stream"):
        moe_ffn(x, router, gate, up, down, k=2, dtype=jnp.float32,
                router_input=RouterRows(x.astype(jnp.float32), *factors))


def test_the_model_hands_the_router_its_norms_own_rows(monkeypatch):
    """What ``Block`` hands ``moe_ffn`` in a bf16 model: the stream and the
    two factors whose product is the rows the experts take, ``ln2``'s output
    in fp32, to fp32's rounding; under an eps and scales loud enough that
    another eps, or a scale left out, would show."""
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel.moe import RouterRows

    model, sizes = test_lfm2.tiny_model(jnp.bfloat16)
    model = transformer.Transformer(
        dataclasses.replace(model.cfg, norm_eps=0.05))
    params = test_lfm2.seeded(model)
    for layer, key in zip(sorted(params), jax.random.split(
            jax.random.PRNGKey(4), len(params))):
        if "ln2" in params[layer]:
            scale = params[layer]["ln2"]["scale"]
            params[layer]["ln2"]["scale"] = scale * (
                1.0 + 0.3 * jax.random.normal(key, scale.shape))
    handed = []

    def spy(x, *args, router_input=None, **options):
        handed.append((x, router_input))
        return transformer.moe_ffn.__wrapped__(
            x, *args, router_input=router_input, **options)

    spy.__wrapped__ = transformer.moe_ffn
    monkeypatch.setattr(transformer, "moe_ffn", spy)
    model.apply({"params": params}, test_lfm2.tokens_of(sizes, 1)["tokens"],
                mutable=["moe"])
    assert len(handed) == 4
    for y, read in handed:
        assert isinstance(read, RouterRows)
        rows, r, g = read
        assert rows.dtype == jnp.bfloat16 and y.dtype == jnp.float32
        assert r.dtype == g.dtype == jnp.float32
        logical = rows.astype(jnp.float32) * r[..., None] * g
        np.testing.assert_allclose(logical, y, rtol=5e-7, atol=0)
        # The eps and the scale both weigh in these rows.
        mean2 = jnp.mean(jnp.square(rows.astype(jnp.float32)), axis=-1)
        assert rel_err(lax.rsqrt(mean2 + sizes["norm_eps"]), r) > 1e-3
        assert rel_err(rows.astype(jnp.float32) * r[..., None], y) > 1e-1


def route_loss(name, old, seed=0):
    """(loss of (x, router, r, g) through ``_route``, its operands): through
    the three-pass product, or with ``old`` through the line fp32 rows run;
    the router's bias and options are the case's."""
    from horovod_tpu.parallel.moe import _route

    x, router, r, g, bias, k, options = router_case(name, seed)

    def loss(x, router, r, g):
        if old:
            out = _route(logical_rows(x, r, g), router, k, bias=bias,
                         **options)
        else:
            out = _route(x, router, k, bias=bias, row_scale=r, col_scale=g,
                         **options)
        weights, experts, _, balance, z = out
        return jnp.sum(weights ** 2 * (1 + experts % 3)) + balance + z, \
            experts

    return loss, (x, router, r, g)


def check_gradients_are_the_old_lines(name):
    new, operands = route_loss(name, old=False)
    old, _ = route_loss(name, old=True)
    grad = lambda f: jax.value_and_grad(  # noqa: E731
        f, argnums=(0, 1, 2, 3), has_aux=True)(*operands)
    (value, experts), got = grad(new)
    (old_value, old_experts), want = grad(old)
    np.testing.assert_array_equal(experts, old_experts)
    assert abs(float(value) - float(old_value)) < 1e-6 * abs(float(old_value))
    # x's gradient is bf16 either way: within one of its roundings.  The
    # others are fp32 sums in another order (measured 2e-7 to 9e-7).
    for g, w, limit in zip(got, want, (2 ** -7, 5e-6, 5e-6, 5e-6)):
        assert g.dtype == w.dtype and rel_err(g, w) < limit


@pytest.mark.parametrize("name", ["softmax", "sigmoid_bias"])
def test_three_pass_gradients_are_the_old_lines(name):
    """The gradients of the rows, the router and both factors through the
    split product against those through the highest-precision line on the
    same operands."""
    check_gradients_are_the_old_lines(name)


@pytest.mark.parametrize("low", [2, 3])
def test_dx_keeps_the_terms_six_passes_keep(low):
    """``u`` and ``w`` built from their bf16 pieces so that the products of
    the leading pieces cancel (and with ``low`` 3 those of a leading and a
    second piece too): the answer is carried by the low pieces alone, 1x2
    and 2x1 (or 1x3, 2x2 and 3x1), which a product left to the default
    precision would drop and the rows' cotangent keeps."""
    from horovod_tpu.parallel.moe import _bf16_pieces, _rows_dot

    q, p = 2.0 ** -10, 2.0 ** -21
    a, A = np.array([1.0, -1.0]), np.array([1.0, 1.0])
    if low == 2:
        b, B = np.array([3 * q, -2 * q]), np.array([3 * q, q])
        c = C = np.zeros(2)
    else:
        b, B = np.array([2 * q, -2 * q]), np.array([3 * q, 3 * q])
        c, C = np.array([5 * p, -2 * p]), np.array([3 * p, p])
    u = jnp.asarray((a + b + c)[None], jnp.float32)
    w = jnp.asarray((A + B + C)[None], jnp.float32)
    for whole, pieces in ((u, (a, b, c)), (w, (A, B, C))):
        np.testing.assert_array_equal(
            np.asarray(_bf16_pieces(whole), np.float64)[0],
            np.concatenate(pieces))
    assert a @ A == 0 and (low == 2 or a @ B + b @ A == 0)
    kept = a @ A + (a @ B + b @ A) + (a @ C + b @ B + c @ A)
    assert abs(kept) >= 3 * (q if low == 2 else p)
    # What the six pairs leave out (2x3, 3x2, 3x3) is 2**-10 of the answer.
    assert abs(kept - (a + b + c) @ (A + B + C)) <= 4e-3 * abs(kept)
    dx, dw = jax.vjp(_rows_dot, jnp.ones((1, 1), jnp.bfloat16), w)[1](u)
    assert dx.shape == (1, 1) and dx.dtype == jnp.bfloat16
    assert dw.shape == (1, 2) and dw.dtype == jnp.float32
    # Rounded to the rows' bf16; the leading pieces alone would give 0.
    assert abs(float(dx[0, 0]) - kept) <= 2.0 ** -8 * abs(kept)


@pytest.mark.parametrize("held", [None, (0, 5)])
def test_the_streams_factors_go_through_the_steps_shard_map(held):
    """``RouterRows`` under a mesh that binds ``data_axis``: every member
    routes its own rows of the stream by its own row factors, the scale is
    one for all, and the router's and the scale's gradients are the sums of
    the members'."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.moe import RouterRows, moe_ffn

    x, router, gate, up, down = layer_inputs(6, rows=4, experts=8)
    pick = np.arange(8) if held is None else np.asarray(held)
    stacks = (gate[pick], up[pick], down[pick])
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    stream = jax.random.normal(ks[0], x.shape).astype(jnp.bfloat16)
    r = jnp.exp(0.3 * jax.random.normal(ks[1], x.shape[:2]))
    g = 1.0 + 0.2 * jax.random.normal(ks[2], x.shape[2:])

    def loss(x, stream, r, router, g, data_axis=None):
        y, stats = moe_ffn(x, router, *stacks, k=2, dtype=jnp.float32,
                           held=held, data_axis=data_axis,
                           router_input=RouterRows(stream, r, g))
        return jnp.sum(y ** 2), stats.tokens_per_expert

    grad = jax.value_and_grad(loss, argnums=(3, 4), has_aux=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("proc",))
    with jax.set_mesh(mesh):
        put = lambda a: jax.device_put(  # noqa: E731
            a, NamedSharding(mesh, P("proc")))
        (value, counts), (d_router, d_g) = jax.jit(
            lambda *a: grad(*a, data_axis="proc"))(
                put(x), put(stream), put(r), router, g)
    assert counts.shape == (2, 8)
    members = [grad(x[rows], stream[rows], r[rows], router, g)
               for rows in (slice(0, 2), slice(2, 4))]
    for member, ((_, own_counts), _) in enumerate(members):
        np.testing.assert_array_equal(counts[member], own_counts[0])
    np.testing.assert_allclose(
        value, sum(v for (v, _), _ in members), rtol=1e-5)
    assert rel_err(d_router, sum(d[0] for _, d in members)) < 1e-5
    assert rel_err(d_g, sum(d[1] for _, d in members)) < 1e-5
    # The router followed the stream and its factors, not the rows.
    assert (np.asarray(counts.sum(0)) != np.asarray(
        moe_ffn(x, router, *stacks, k=2, dtype=jnp.float32, held=held)[1]
        .tokens_per_expert[0])).any()


def _dot_generals(jaxpr):
    from .test_sdar import equations_of

    return equations_of(jaxpr, "dot_general")


@pytest.mark.parametrize("name", ["softmax", "sigmoid_bias", "sigmoid"])
def test_the_bf16_path_holds_no_product_at_the_default_precision(name):
    """Forward one bf16 product against the three pieces (3 e columns),
    backward one for ``dw`` over the cotangent's three pieces, and ``dx``,
    whose two operands are fp32, at the highest precision by name: no
    product of an fp32 operand is left to the default precision."""
    loss, operands = route_loss(name, old=False)
    n, d = operands[0].shape
    e = operands[1].shape[1]
    forward = list(_dot_generals(jax.make_jaxpr(loss)(*operands).jaxpr))
    both = list(_dot_generals(jax.make_jaxpr(jax.grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(*operands).jaxpr))
    assert [eqn.outvars[0].aval.shape for eqn in forward] == [(n, 3 * e)]
    assert sorted(eqn.outvars[0].aval.shape for eqn in both) \
        == sorted([(n, 3 * e), (d, 3 * e), (n, d)])
    for eqn in both:
        dtypes = [v.aval.dtype for v in eqn.invars]
        if eqn.outvars[0].aval.shape == (n, d):
            assert dtypes == [jnp.float32] * 2
            assert eqn.params["precision"] == (lax.Precision.HIGHEST,) * 2
        else:
            assert dtypes == [jnp.bfloat16] * 2
            assert eqn.params["preferred_element_type"] == jnp.float32
    # The line that other dtypes run names its precision.
    old, _ = route_loss(name, old=True)
    for eqn in _dot_generals(jax.make_jaxpr(jax.grad(
            old, argnums=(1,), has_aux=True))(*operands).jaxpr):
        assert eqn.params["precision"] is not None


# -- the k chosen scores, read and differentiated by a compare (PR 48) --------

# (k, E) of the six sparse cells' routers.
CHOSEN_SHAPES = {"nemotron": (22, 512), "joyai": (8, 256), "lfm2": (4, 32),
                 "sdar": (8, 128), "smallthinker": (6, 64), "olmoe": (8, 64)}


def gathered(probs, experts):
    """The line ``_chosen`` took the place of; its transpose is a scatter."""
    return jnp.take_along_axis(probs, experts, axis=-1)


def chosen_case(cell, kind, rows, n=48, d=32):
    """(rows [n, d] of dtype ``rows``, router, bias or None, k, what
    ``_route`` takes besides) at a cell's k of E: ``softmax`` without a bias
    and the top k's sum left alone, ``sigmoid_bias`` renormalised."""
    k, experts = CHOSEN_SHAPES[cell]
    ks = jax.random.split(jax.random.PRNGKey(k + experts), 3)
    x = jax.random.normal(ks[0], (n, d)).astype(rows)
    router = 0.3 * jax.random.normal(ks[1], (d, experts))
    if kind == "softmax":
        return x, router, None, k, {}
    return x, router, 0.05 * jax.random.normal(ks[2], (experts,)), k, dict(
        scoring="sigmoid", norm_topk_prob=True, scale=2.5)


@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("cell", list(CHOSEN_SHAPES))
def test_chosen_scores_are_the_gathers_to_the_bit(cell, kind, rows,
                                                  monkeypatch):
    """``_chosen`` and its cotangent equal ``take_along_axis`` and its
    scatter with ``==``, on the scores of float32 rows and of a bf16 stream;
    so do the weights of ``_route`` and, op by op, the gradients of the rows
    and the router through it."""
    from horovod_tpu.parallel import moe

    x, router, bias, k, options = chosen_case(cell, kind, rows)
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits) if kind == "softmax" \
        else jax.nn.sigmoid(logits)
    experts = lax.top_k(probs if bias is None else probs + bias, k)[1]
    g = jax.random.normal(jax.random.PRNGKey(7), experts.shape)
    got, pull = jax.vjp(lambda p: moe._chosen(p, experts), probs)
    want, pull_gathered = jax.vjp(lambda p: gathered(p, experts), probs)
    assert got.dtype == want.dtype and (got == want).all()
    (d_got,), (d_want,) = pull(g), pull_gathered(g)
    assert d_got.dtype == d_want.dtype and (d_got == d_want).all()
    assert (np.asarray(d_got) != 0).sum() == experts.size

    def loss(x, router):
        weights, chosen, *_ = moe._route(x, router, k, bias=bias, **options)
        return jnp.sum(weights ** 2 * (1 + chosen % 3)), (weights, chosen)

    with jax.disable_jit():
        grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        new = grad(x, router)
        monkeypatch.setattr(moe, "_chosen", gathered)
        old = grad(x, router)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype and (a == b).all()


@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["softmax", "sigmoid_bias"])
def test_the_routers_gradient_holds_no_gather_and_no_scatter(kind, rows,
                                                             monkeypatch):
    """The lowered gradient of ``_route`` at Nemotron's 22 of 512: not one
    gather or scatter of any kind, where the line before it had both."""
    from horovod_tpu.parallel import moe

    x, router, bias, k, options = chosen_case("nemotron", kind, rows)

    def text():
        def loss(x, router):
            weights, _, _, balance, z = moe._route(x, router, k, bias=bias,
                                                   **options)
            return jnp.sum(weights ** 2) + balance + z
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            x, router).as_text()

    new = text()
    assert "gather" not in new and "scatter" not in new
    assert "top_k" in new
    monkeypatch.setattr(moe, "_chosen", gathered)
    old = text()
    assert "gather" in old and "scatter" in old


@pytest.mark.parametrize("cell", list(CHOSEN_SHAPES))
def test_the_backward_keeps_the_experts_alone(cell):
    """What ``_chosen``'s cotangent is computed from: the ``[n, k]`` indices
    and nothing else: no mask of n k E elements waits from forward to
    backward (92 MB a layer at Nemotron's sizes)."""
    from horovod_tpu.parallel import moe

    n = 48
    k, n_experts = CHOSEN_SHAPES[cell]
    probs = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1),
                                             (n, n_experts)))
    experts = lax.top_k(probs, k)[1]
    kept = jax.tree_util.tree_leaves(
        jax.vjp(lambda p: moe._chosen(p, experts), probs)[1])
    assert [(a.shape, a.dtype) for a in kept] == [((n, k), jnp.int32)]
