"""``kernels/rope_operands.py``: q and k turned by their rotary tables, q
scaled, both in the attention kernels' layout: the two kernels in interpret
mode against ``_rope`` + the scale + the transposition differentiated by JAX
(what ``models/transformer.py`` and ``masked_attention.attention`` ran until
PR 65), at the head counts and tables of the three cells that run them,
beside two planted faults; what ``takes`` refuses; and that ``Attention``
calls the kernels where it takes a layer and is the program it was where it
does not.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.kernels import masked_attention as ma
from horovod_tpu.kernels import rope_operands as ro
from horovod_tpu.models import transformer as tr

from .helpers import REPO_ROOT

S, TILE, D = 256, 128, 128
SCALE = D ** -0.5
# A difference's norm as a share of the value's.  The two forms round at the
# same points (q, k and dk agree to 1e-7), but inside one fusion XLA's CPU
# compiler keeps ``dq * scale`` unrounded where the kernel rounds it as the
# rows' dtype does: one rounding to bf16, 2.5e-3 of the norm.  A fault leaves
# most of the value.
RTOL = 5e-3

LAGUNA = tr.laguna_s_2_1_config()
# name: (query heads, KV heads, theta, the layer's Rotary, positions given).
CASES = {
    "laguna_sliding_72_on_8": (72, 8, 0.0, LAGUNA.layer_kind(1).rotary,
                               False),
    "laguna_full_48_on_8_half_yarn": (48, 8, 0.0,
                                      LAGUNA.layer_kind(0).rotary, False),
    "smallthinker_28_on_4": (28, 4, 1.5e6, None, False),
    "sdar_32_on_4_positions": (32, 4, 1e6, None, True),
}


def the_case(name):
    """q, k as the projections write them, cotangents in the kernels'
    layout, and ``_rope``'s arguments behind ``x``."""
    h, h_kv, theta, rotary, given = CASES[name]
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    q = jax.random.normal(keys[0], (1, S, h * D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, S, h_kv * D), jnp.bfloat16)
    dq = jax.random.normal(keys[2], (1, h, S, D), jnp.bfloat16)
    dk = jax.random.normal(keys[3], (1, h_kv, S, D), jnp.bfloat16)
    positions = jnp.arange(S) % (S // 2) if given else None
    return (q, k), (dq, dk), (theta, positions, 1.0, rotary)


def until_pr65(q, k, rope_args):
    """``_rope`` on ``[b, s, h, d]``, the scale in the rows' dtype, the
    copies into ``[b, h, s, d]``."""
    q, k = (tr._rope(t.reshape(1, S, -1, D), *rope_args) for t in (q, k))
    return (q * jnp.asarray(SCALE, q.dtype)).transpose(0, 2, 1, 3), \
        k.transpose(0, 2, 1, 3)


def kernels(q, k, rope_args, fault=None):
    cos, sin, half = tr._rope_tables(S, D, *rope_args)
    if fault == "sine_sign":
        sin = -sin
    if fault == "half_64_for_32":
        half = 64
    return ro.operands(q, k, cos, sin, SCALE, half=half, tile=TILE,
                       interpret=True)


def outputs_and_cotangents(fn, operands, cotangents):
    @jax.jit
    def both(operands, cotangents):
        out, back = jax.vjp(fn, *operands)
        return tuple(out) + back(cotangents)

    return dict(zip(("q", "k", "dq", "dk"), both(operands, cotangents)))


def differences(got, want):
    """Each of the four as a share of its norm."""
    def share(a, b):
        a, b = (jnp.asarray(t, jnp.float32) for t in (a, b))
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))

    return {name: share(got[name], want[name]) for name in want}


@pytest.fixture(scope="module")
def wanted():
    """A case's outputs and cotangents by the form until PR 65, once."""
    made = {}

    def of(name):
        if name not in made:
            operands, cotangents, rope_args = the_case(name)
            made[name] = outputs_and_cotangents(
                lambda q, k: until_pr65(q, k, rope_args), operands,
                cotangents)
        return made[name]

    return of


@pytest.mark.parametrize("name", list(CASES))
def test_kernels_are_rope_scale_and_transposition(name, wanted):
    """q, k and both cotangents, each by itself; outputs in the kernels'
    layout, cotangents flat as the projections' backward takes them."""
    operands, cotangents, rope_args = the_case(name)
    got = outputs_and_cotangents(
        lambda q, k: kernels(q, k, rope_args), operands, cotangents)
    h, h_kv = CASES[name][:2]
    assert got["q"].shape == (1, h, S, D) and got["q"].dtype == jnp.bfloat16
    assert got["k"].shape == (1, h_kv, S, D)
    assert got["dq"].shape == (1, S, h * D)
    assert got["dk"].shape == (1, S, h_kv * D)
    read = differences(got, wanted(name))
    assert max(read.values()) < RTOL, read


@pytest.mark.parametrize("fault,name", [
    ("sine_sign", "laguna_sliding_72_on_8"),
    ("sine_sign", "sdar_32_on_4_positions"),
    ("half_64_for_32", "laguna_full_48_on_8_half_yarn"),
])
def test_a_planted_fault_fails_the_same_comparison(fault, name, wanted):
    """The other angle, and pairs a whole half head apart where YaRN's share
    pairs column l with l + 32: every one of the four reads far outside."""
    operands, cotangents, rope_args = the_case(name)
    got = outputs_and_cotangents(
        lambda q, k: kernels(q, k, rope_args, fault), operands, cotangents)
    read = differences(got, wanted(name))
    assert min(read.values()) > 50 * RTOL, read


def test_the_columns_a_share_leaves_alone_pass_unturned():
    """Laguna's global layers: the second half of a head is the projection's,
    times the scale for q, bit for bit."""
    operands, _, rope_args = the_case("laguna_full_48_on_8_half_yarn")
    q, k = kernels(*operands, rope_args)
    flat_q, flat_k = (t.reshape(1, S, -1, D).transpose(0, 2, 1, 3)
                      for t in operands)
    assert jnp.array_equal(k[..., 64:], flat_k[..., 64:])
    assert jnp.array_equal(
        q[..., 64:], flat_q[..., 64:] * jnp.asarray(SCALE, jnp.bfloat16))
    assert not jnp.array_equal(k[..., :64], flat_k[..., :64])


TAKEN = dict(rule=ma.Window(512), seq_len=8192, head_dim=128, turned=128,
             dtype=jnp.bfloat16)


@pytest.mark.parametrize("change,takes", [
    ({}, True),                                       # Laguna's sliding
    ({"rule": ma.Causal(), "turned": 64}, True),      # Laguna's global
    ({"rule": ma.Window(4096), "seq_len": 16384}, True),    # SmallThinker
    ({"rule": tr.BlockDiffusion(4), "seq_len": 16384}, True),      # SDAR
    ({"head_dim": 64, "turned": 64}, False),          # LFM2, Granite
    ({"head_dim": 256, "turned": 64}, False),         # Qwen3-Next
    ({"dtype": jnp.float32}, False),                  # every float32 twin
    ({"turned": 32}, False),
    ({"rule": None}, False),                          # OLMoE: flash
    ({"seq_len": 8192 + 512}, False),
    ({"backend": "cpu"}, False),
], ids=lambda x: x if isinstance(x, bool) else "_".join(x) or "laguna")
def test_takes(change, takes, monkeypatch):
    change = dict(change)
    backend = change.pop("backend", "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ro.takes(**{**TAKEN, **change}) is takes


def attention_jaxpr(cfg, s, kind=tr.LayerKind()):
    """The program of one ``Attention`` on ``[1, s, d_model]``, traced (no
    kernel is lowered)."""
    layer = tr.Attention(cfg, kind)
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), cfg.dtype)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    return str(jax.make_jaxpr(layer.apply)(params, x))


def tiny(**overrides):
    return tr.tiny_config(**{**dict(
        d_model=256, num_heads=4, num_kv_heads=2, head_width=128,
        max_len=2048, positions="rope", dtype=jnp.bfloat16), **overrides})


@pytest.mark.parametrize("qk_norm", [False, "head"])
def test_attention_calls_the_kernels_where_they_take_the_layer(qk_norm,
                                                               monkeypatch):
    """On a TPU a bf16 layer of 128-wide heads under a window: one call of
    the forward kernel in front of the attention kernel, nothing of
    ``_rope``'s (no split, no concatenation of the rows) and no copy of q or
    k (the one transposition left is v's, the other the output's).  Behind a
    per-head norm (SDAR) the kernel's q and k are pinned before it, so that
    XLA lays out the norm's one bf16 result and not its fp32 rows; straight
    from the products (Laguna, SmallThinker) nothing stands between."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = attention_jaxpr(tiny(qk_norm=qk_norm), 1024,
                           tr.LayerKind(window=512))
    assert ("optimization_barrier" in text) == bool(qk_norm)
    assert len(re.findall(r"name=hvd_rope_operands_fwd", text)) == 1
    assert len(re.findall(r"name=splash_mha_fwd_out_lse", text)) == 1
    assert not re.search(r"split\[\s*axis=3", text)      # a head's halves
    assert len(re.findall(r"= transpose\[permutation=\(0, 2, 1, 3\)",
                          text)) == 2


@pytest.mark.parametrize("refused_for,cfg,backend", [
    ("width_64", dict(head_width=64), "tpu"),
    ("float32", dict(dtype=jnp.float32), "tpu"),
    ("a_quarter_turned", dict(partial_rotary_factor=0.25), "tpu"),
    ("not_a_tpu", {}, "cpu"),
])
def test_a_refused_layer_is_the_program_it_was(refused_for, cfg, backend,
                                               monkeypatch):
    """What ``takes`` refuses is, equation for equation, the program of a
    tree in which the kernels take nothing: ``_rope`` on the rows and the
    wrapper's own copies, no table made for a kernel that is not called
    (``tests/test_pinned_programs.py`` holds the models' texts to their
    digests; no row of it moved with PR 65)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kind = tr.LayerKind(window=512)
    text = attention_jaxpr(tiny(**cfg), 1024, kind)
    assert "hvd_rope_operands" not in text
    monkeypatch.setattr(ro, "takes", lambda *a, **k: False)
    assert text == attention_jaxpr(tiny(**cfg), 1024, kind)
    assert text.count("concatenate") >= 2           # _rope's, on q and on k


def test_the_counter_reads_both_kernels_by_their_names():
    """``rope_operands_calls_step``: the op line's names under the module's
    own pattern in the three cells that turn q and k through the kernels,
    and nothing of another kernel's; data alone, over a reduction the
    benchmark had."""
    name = "rope_operands_calls_step"
    with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    (reader,) = metric["readers"]
    assert reader == {"reduction": "trace_op_count_per_step",
                      "pattern": ro.OP_LINE_NAMES}
    assert metric["name"] == name and metric["ranks"] == "rank0"
    for op in (ro.FWD_NAME, ro.BWD_NAME, ro.BWD_NAME + ".5"):
        assert re.search(reader["pattern"], op)
    for op in ("hvd_mla_operands_fwd", "splash_mha_fwd_out_lse", "fusion.12"):
        assert not re.search(reader["pattern"], op)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry == {"name": name, "unit": "count/step", "better": "higher",
                     "source": "device_trace", "layer": "kernel",
                     "moves": "samples_per_s_chip",
                     "workloads": ["laguna-s-2.1-wfbp-1chip",
                                   "smallthinker-21b-a3b-wfbp-1chip",
                                   "sdar-30b-a3b-wfbp-1chip",
                                   "keye-vl-2.0-30b-a3b-wfbp-1chip"]}
