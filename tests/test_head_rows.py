"""``kernels/head_rows.py``: the row work each side of Kimi Delta Attention's
rule on the flat layout: the four kernels in interpret mode against their
``jax.numpy`` form (``models/kda.py``'s lines until PR 67, the heads as an
axis), values and every cotangent, on fresh decays and on fast ones, beside
five planted faults; what ``takes`` refuses and that a refused layer is the
program it was; the mixer through the kernels against itself through
``jax.numpy``; and the counters' files.
"""

import functools
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.kernels import head_rows as hr
from horovod_tpu.kernels import kda as rule
from horovod_tpu.models import kda as mixer
from horovod_tpu.models import transformer as tr

from .helpers import REPO_ROOT

S, HEADS, TILE, COLS = 256, 4, 128, 256
INNER = HEADS * 128
SCALE = 128 ** -0.5
# A difference's norm as a share of the value's.  The two forms round at the
# same points: a bf16 output that rounds the other way on one element in a
# thousand reads 1e-4, the fp32 rows 1e-6; a fault leaves most of the value.
RTOL = 1e-3
FAULT = 50 * RTOL
GATE = dict(f_at=3 * INNER, scale=SCALE, lower=rule.LOWER_BOUND)
NORM = dict(z_at=4 * INNER, eps=1e-6)
BLOCK = dict(tile=TILE, cols=COLS, interpret=True)


def share(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def operands(decays="fresh", faint=False):
    """The convolution's output, the projection's row, the gate's two rows
    and the norm's weight, with cotangents for every output.  ``fresh``: the
    mixer's initialisers, under which most channels hardly decay (``g`` near
    0); ``fast``: ``dt_bias`` raised by 3 and ``f`` twice as large, ``g``
    spread down to the bound.  ``faint``: every other position of q and k a
    ten-thousandth as large, a sum of squares about ``1e-6``."""
    keys = jax.random.split(jax.random.PRNGKey(7), 10)
    bf16, f32 = jnp.bfloat16, jnp.float32
    conv = jax.random.normal(keys[0], (1, S, 3 * INNER), f32)
    if faint:
        conv = jnp.where(jnp.arange(S)[:, None] % 2 == 0, conv, 1e-4 * conv)
    row = jax.random.normal(keys[1], (1, S, 5 * INNER), f32)
    dt_bias = mixer._dt_bias_init(keys[3], (1, INNER))
    if decays == "fast":
        row, dt_bias = 2.0 * row, dt_bias + 3.0
    a = jnp.repeat(jnp.exp(mixer._a_log_init(keys[2], (HEADS,))), 128)[None]
    w = 1.0 + 0.1 * jax.random.normal(keys[4], (1, INNER), f32)
    o, dq, dk, dy = (jax.random.normal(key, (1, S, INNER), bf16)
                     for key in keys[5:9])
    dg = jax.random.normal(keys[9], (1, S, INNER), f32)
    return dict(conv=conv.astype(bf16), row=row.astype(bf16), a=a,
                dt_bias=dt_bias, w=w, o=o, d_gate=(dq, dk, dg), dy=dy)


def gate_by_reference(conv, row, a, dt_bias):
    return hr.gate_reference(
        conv[..., :INNER], conv[..., INNER:2 * INNER],
        row[..., 3 * INNER:4 * INNER], a, dt_bias, scale=SCALE,
        lower=rule.LOWER_BOUND)


def norm_by_reference(o, row, w):
    return hr.norm_reference(o, row[..., 4 * INNER:], w, eps=NORM["eps"])


def gate_read(fn, x):
    """{name: value} of the gate's three outputs and four cotangents."""
    out, back = jax.vjp(fn, x["conv"], x["row"], x["a"], x["dt_bias"])
    return dict(zip(("q", "k", "g", "d_conv", "d_row", "d_a", "d_dt_bias"),
                    tuple(out) + back(x["d_gate"])))


def norm_read(fn, x):
    out, back = jax.vjp(fn, x["o"], x["row"], x["w"])
    return dict(zip(("y", "d_o", "d_row", "d_w"), (out,) + back(x["dy"])))


def differences(got, want):
    return {name: share(got[name], want[name]) for name in want}


@pytest.mark.parametrize("decays", ["fresh", "fast"])
def test_the_gates_kernels_are_the_reference(decays):
    """q, k and g and the cotangents of the convolution's output (``dq`` and
    ``dk`` in its first two thirds, nothing in v's), of the row (``df`` in
    f's columns alone), of ``a`` and of ``dt_bias``, each by itself."""
    x = operands(decays)
    got = gate_read(functools.partial(hr.gate, **GATE, **BLOCK), x)
    want = gate_read(gate_by_reference, x)
    assert got["q"].dtype == got["k"].dtype == jnp.bfloat16
    assert got["g"].dtype == jnp.float32 and got["g"].shape == (1, S, INNER)
    assert got["d_conv"].shape == x["conv"].shape
    assert got["d_conv"].dtype == got["d_row"].dtype == jnp.bfloat16
    assert not jnp.any(got["d_conv"][..., 2 * INNER:])
    assert not jnp.any(got["d_row"][..., :3 * INNER]) \
        and not jnp.any(got["d_row"][..., 4 * INNER:])
    assert got["d_a"].shape == got["d_dt_bias"].shape == (1, INNER)
    assert rule.LOWER_BOUND <= float(jnp.min(got["g"])) \
        and float(jnp.max(got["g"])) <= 0.0
    # Fresh decays leave g near 0 and fast ones reach down to the bound.
    assert (float(jnp.mean(got["g"])) < -1.0) == (decays == "fast")
    read = differences(got, want)
    assert max(read.values()) < RTOL, read


def test_the_norms_kernels_are_the_reference():
    """y and the cotangents of o, of the row (``dz`` in z's columns alone)
    and of the weight."""
    x = operands()
    got = norm_read(functools.partial(hr.norm, **NORM, **BLOCK), x)
    want = norm_read(norm_by_reference, x)
    assert got["y"].dtype == got["d_o"].dtype == jnp.bfloat16
    assert not jnp.any(got["d_row"][..., :4 * INNER])
    assert got["d_w"].shape == (1, INNER) and got["d_w"].dtype == jnp.float32
    read = differences(got, want)
    assert max(read.values()) < RTOL, read


@pytest.fixture
def planted(monkeypatch):
    """Plant a fault in the module and trace the kernels anew: a jitted
    direction keeps its trace by shape and static arguments."""
    def clear():
        for fn in (hr._gate_forward, hr._gate_backward, hr._norm_forward,
                   hr._norm_backward):
            fn.clear_cache()

    def plant(name, value):
        monkeypatch.setattr(hr, name, value)
        clear()

    yield plant
    monkeypatch.undo()
    clear()


def the_fault(name, plant):
    """-> (the gate's arguments, the block's, forward values alone)."""
    gate, block = dict(GATE), dict(BLOCK)
    if name == "no_1e-6":
        plant("L2_EPS", 0.0)
    elif name == "no_q_scale":
        gate["scale"] = 1.0
    elif name == "no_bound":
        gate["lower"] = -1.0
    elif name == "mean_for_sum":
        head_sum = hr._head_sum
        plant("_head_sum", lambda x: head_sum(x) / 128)
    elif name == "neighbours_lanes":
        # Two heads' lanes under one sum (the partial sums of a block 256
        # wide do not fit their rows: forward alone).
        plant("_heads", lambda ref: [slice(h, h + 256) for h in range(
            0, ref.shape[-1], 256)])
        return gate, block, True
    return gate, block, False


@pytest.mark.parametrize("fault,moved", [
    ("no_1e-6", {"q", "k", "d_conv"}),
    ("no_q_scale", {"q", "d_conv"}),
    ("no_bound", {"g", "d_row", "d_a", "d_dt_bias"}),
    ("mean_for_sum", {"q", "k", "d_conv", "y", "d_o", "d_row_norm", "d_w"}),
    ("neighbours_lanes", {"q", "k", "y"}),
])
def test_a_planted_fault_fails_the_same_comparison(fault, moved, planted):
    """Each fault leaves the readings it should move far outside the limit
    and every other one where it was (a faint position's sum of squares is
    about the ``1e-6`` beside it, so leaving that out shows)."""
    x = operands("fast", faint=True)
    # What is wanted first: the reference reads the module's ``1e-6`` too.
    want = {**gate_read(gate_by_reference, x),
            **{"d_row_norm" if name == "d_row" else name: value
               for name, value in norm_read(norm_by_reference, x).items()}}
    gate, block, forward_alone = the_fault(fault, planted)
    if forward_alone:
        q, k, g = hr.gate(x["conv"], x["row"], x["a"], x["dt_bias"], **gate,
                          **block)
        got = dict(q=q, k=k, g=g, y=hr.norm(x["o"], x["row"], x["w"], **NORM,
                                            **block))
    else:
        norm = norm_read(functools.partial(hr.norm, **NORM, **block), x)
        got = {**gate_read(functools.partial(hr.gate, **gate, **block), x),
               "d_row_norm": norm.pop("d_row"), **norm}
    read = {name: share(value, want[name]) for name, value in got.items()}
    assert {name for name, r in read.items() if r > FAULT} == moved, read
    assert all(r < RTOL for name, r in read.items()
               if name not in moved), read


TAKEN = dict(seq_len=8192, head_dim=128, dtype=jnp.bfloat16)


@pytest.mark.parametrize("change,takes", [
    ({}, True),                                  # Ling-3.0-flash-VL
    ({"seq_len": 512}, True),
    ({"dtype": jnp.float32}, False),             # every float32 twin
    ({"head_dim": 64}, False),
    ({"head_dim": 256}, False),
    ({"seq_len": 8192 + 64}, False),             # whole chunks, no whole tile
    ({"seq_len": 0}, False),
    ({"backend": "cpu"}, False),
], ids=lambda x: x if isinstance(x, bool) else "_".join(x) or "ling")
def test_takes(change, takes, monkeypatch):
    change = dict(change)
    backend = change.pop("backend", "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert hr.takes(**{**TAKEN, **change}) is takes


def test_misplaced_operands_are_refused():
    x = operands()
    with pytest.raises(ValueError, match="columns from"):
        hr.gate(x["conv"], x["row"], x["a"], x["dt_bias"],
                **{**GATE, "f_at": 3 * INNER + 128}, **BLOCK)
    with pytest.raises(ValueError, match="positions in tiles"):
        hr.norm(x["o"], x["row"], x["w"], **NORM, tile=96, interpret=True)
    with pytest.raises(ValueError, match="w "):
        hr.norm(x["o"], x["row"], x["w"][:, :128], **NORM, **BLOCK)
    with pytest.raises(ValueError, match="conv "):
        hr.gate(x["conv"][..., :INNER], x["row"], x["a"], x["dt_bias"],
                **GATE, **BLOCK)


# -- the mixer ----------------------------------------------------------------


def tiny(**overrides):
    return tr.tiny_config(**{**dict(
        d_model=64, num_heads=2, kda_head_dim=128, conv_taps=4,
        dtype=jnp.bfloat16), **overrides})


def mixer_jaxpr(cfg, s):
    """The program of one ``KimiDeltaAttention`` on ``[1, s, d_model]``,
    traced (no kernel is lowered)."""
    layer = mixer.KimiDeltaAttention(cfg)
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), cfg.dtype)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    return str(jax.make_jaxpr(layer.apply)(params, x))


def test_the_mixer_calls_the_kernels_where_they_take_the_layer(monkeypatch):
    """On a TPU in bf16 at heads of 128: the convolution's kernel, the gate's,
    the rule's, the norm's, and between them no array with the heads as an
    axis and no split of the convolution's output."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = mixer_jaxpr(tiny(), 512)
    names = re.findall(r"name=((?:hvd)\w+)", text)
    assert names == ["hvd_causal_conv_fwd", hr.GATE_FWD_NAME, rule.FWD_NAME,
                     hr.NORM_FWD_NAME], names
    assert "512,2,128" not in text
    # The one split left is the row's into [q ; k ; v], f and z, which
    # nothing reads: the kernels take the row itself.
    assert len(re.findall(r"split\[", text)) == 1


@pytest.mark.parametrize("refused_for,cfg,backend,s", [
    ("float32", dict(dtype=jnp.float32), "tpu", 512),
    ("width_64", dict(kda_head_dim=64), "tpu", 512),
    ("no_whole_tile", {}, "tpu", 576),
    ("not_a_tpu", {}, "cpu", 512),
])
def test_a_refused_layer_is_the_program_it_was(refused_for, cfg, backend, s,
                                               monkeypatch):
    """What ``takes`` refuses is, equation for equation, the program of a
    tree in which the kernels take nothing: the split into q, k and v, the
    heads as an axis, ``_l2_normed`` and the norm's lines, no row spread for
    a kernel that is not called (``tests/test_pinned_programs.py`` holds
    the float32 model's text to the digest of PR 67's parent)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    text = mixer_jaxpr(tiny(**cfg), s)
    assert "hvd_head_rows" not in text
    monkeypatch.setattr(hr, "takes", lambda *a, **k: False)
    assert text == mixer_jaxpr(tiny(**cfg), s)
    width = tiny(**cfg).kda_head_dim
    assert f"{s},2,{width}" in text
    assert len(re.findall(r"split\[", text)) == 2


@pytest.fixture(scope="module")
def both_mixers():
    """One mixer's output and the gradients of its seven leaves and of its
    input through the kernels (interpret mode; the rule itself in
    ``jax.numpy`` either way) and through the ``jax.numpy`` lines, on decays
    made fast."""
    cfg = tiny()
    layer = mixer.KimiDeltaAttention(cfg)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (1, S, cfg.d_model), cfg.dtype)
    probe = jax.random.normal(keys[1], (1, S, cfg.d_model), jnp.float32)
    params = nn.meta.unbox(layer.init(keys[2], x))
    leaves = params["params"]
    leaves["in_proj"]["kernel"] = 4.0 * leaves["in_proj"]["kernel"]
    leaves["dt_bias"] = leaves["dt_bias"] + 3.0

    def loss(params, x):
        y = layer.apply(params, x)
        return jnp.sum(y.astype(jnp.float32) * probe), y

    def run():
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True)(params, x)
        return y, grads

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hr, "takes", lambda *a, **k: True)
        patch.setattr(hr, "gate", functools.partial(
            hr.gate, tile=TILE, interpret=True))
        patch.setattr(hr, "norm", functools.partial(
            hr.norm, tile=TILE, interpret=True))
        through_kernels = run()
    return through_kernels, run()


MIXER_LEAVES = ("in_proj", "beta_proj", "conv", "A_log", "dt_bias", "norm",
                "out_proj")


@pytest.mark.parametrize("what", ("y", "x") + MIXER_LEAVES)
def test_the_mixer_is_itself_through_jax_numpy(what, both_mixers):
    """The output, ``dx`` and each leaf's gradient (``A_log``'s summed a
    head by XLA's transpose of the spread, ``dt_bias``'s a channel, the
    norm's weight's over the heads) within the kernels' rounding: the two
    paths round ``q``, ``k`` and ``y`` to bf16 at the same points."""
    (y, (by_leaf, dx)), (y_ref, (by_leaf_ref, dx_ref)) = both_mixers
    if what == "y":
        got, want = y, y_ref
    elif what == "x":
        got, want = dx, dx_ref
    else:
        got, want = (jax.tree_util.tree_leaves(t["params"][what])[0]
                     for t in (by_leaf, by_leaf_ref))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.linalg.norm(want.astype(jnp.float32))) > 0
    assert share(got, want) < 2e-2, share(got, want)


# -- the counters -------------------------------------------------------------


@pytest.mark.parametrize("name,reduction,unit,better", [
    ("head_rows_ms_step", "trace_op_ms_per_step", "ms/step", "lower"),
    ("head_rows_calls_step", "trace_op_count_per_step", "count/step",
     "higher"),
])
def test_the_counters_read_the_four_kernels_by_their_names(name, reduction,
                                                           unit, better):
    """Data alone, over reductions the benchmark had: the op line's names
    under the module's own pattern, nothing of the rule's kernels (whose
    metrics read ``^hvd_kda_``) and nothing of another kernel's."""
    with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    (reader,) = metric["readers"]
    assert reader == {"reduction": reduction, "pattern": hr.OP_LINE_NAMES}
    assert metric["name"] == name and metric["ranks"] == "rank0"
    names = (hr.GATE_FWD_NAME, hr.GATE_BWD_NAME, hr.NORM_FWD_NAME,
             hr.NORM_BWD_NAME)
    for op in names + (hr.GATE_BWD_NAME + ".5",):
        assert re.search(reader["pattern"], op)
        assert not re.search(rule.OP_LINE_NAMES, op)
    for op in (rule.FWD_NAME, rule.BWD_NAME, "hvd_causal_conv_fwd",
               "multiply_reduce_fusion.12"):
        assert not re.search(reader["pattern"], op)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "kernel",
                     "moves": "samples_per_s_chip",
                     "workloads": ["ling-3.0-flash-vl-wfbp-1chip"]}
