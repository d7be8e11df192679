"""``kernels/hyper_connection.py``'s two kernels in interpret mode on the CPU,
each against the ``jax.numpy`` form in float32 cotangent by cotangent, a
fault planted in each that the limit refuses, a whole hyper-connection's
gradient through the kernels against plain autodiff, and what ``takes``
takes.  Nothing here is a time: ``benchmarks/hyper_connection_sweep.py``
holds the kernels to float32 on the chip at the timed shape.
"""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import hyper_connection as kernels
from horovod_tpu.models import hyper_connections as hc

from .helpers import REPO_ROOT

LIMIT = 2e-2        # of the norm: benchmarks/hyper_connection_sweep.py's
B, S, N, C = 1, 128, 4, 256
K = N * (N + 2)
CFG = types.SimpleNamespace(hc_mult=N, norm_eps=1e-6, hc_res_clamp=30.0,
                            hc_sinkhorn_iters=20, hc_eps=1e-6,
                            dtype=jnp.bfloat16)
RES = [f"dres_{i}{j}" for i in range(N) for j in range(N)]
POST = [f"dpost_{i}" for i in range(N)]


def share(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(59), 9)
    normal = jax.random.normal
    return dict(
        streams=normal(keys[0], (B, S, N, C), jnp.bfloat16),
        g=normal(keys[1], (B, S, N, C), jnp.bfloat16),
        y=normal(keys[2], (B, S, C), jnp.bfloat16),
        du=normal(keys[3], (B, S, C), jnp.bfloat16),
        dxt=normal(keys[4], (B, S, N, C), jnp.bfloat16),
        dz=normal(keys[5], (K, B, S)),
        phi=0.02 * normal(keys[6], (N * C, K)),
        bias=normal(keys[7], (K,))
        * jnp.where(jnp.arange(K) < 2 * N, 1.0, 4.0),
        alpha=jnp.asarray([0.7, 1.3, 2.0]),
        res=jax.random.uniform(keys[8], (N, N, B, S)),
        post=2.0 * jax.random.uniform(keys[8], (N, B, S)))


def f32(x):
    return x.astype(jnp.float32)


def _post(operands, res=None):
    """The kernel's four cotangents by name, ``dres`` and ``dpost`` a
    coefficient each."""
    o = operands
    dy, dxt, dres, dpost = kernels.post_bwd(
        hc._tokens_minor(o["g"]), hc._tokens_minor(o["streams"]),
        hc._tokens_minor(o["y"]), (o["res"] if res is None else res)[:, :, 0],
        o["post"][:, 0], interpret=True)
    return {"dy": hc._tokens_major(dy, o["y"].shape),
            "dxt": hc._tokens_major(dxt, o["g"].shape),
            **dict(zip(RES, dres.reshape(N * N, S))),
            **dict(zip(POST, dpost))}


@pytest.fixture(scope="module")
def post_pair(operands):
    o = operands
    dres, dpost, dxt, dy = jax.vjp(
        hc.mix_back, o["res"], o["post"], f32(o["streams"]), f32(o["y"])
    )[1](f32(o["g"]))
    want = {"dy": dy, "dxt": dxt,
            **dict(zip(RES, dres.reshape(N * N, S))),
            **dict(zip(POST, dpost.reshape(N, S)))}
    return _post(operands), want


@pytest.mark.parametrize("name", ["dy", "dxt"] + RES + POST)
def test_post_bwd_matches_float32(post_pair, name):
    got, want = post_pair
    assert got[name].size == want[name].size
    assert share(np.asarray(got[name]).ravel(),
                 np.asarray(want[name]).ravel()) < LIMIT


def test_post_bwd_with_h_res_not_transposed_is_refused(operands, post_pair):
    """``dX~ = H_res^T g``: with ``H_res g`` the limit refuses ``dX~``, and
    nothing else moves."""
    wrong = _post(operands, res=operands["res"].swapaxes(0, 1))
    want = post_pair[1]
    assert share(np.asarray(wrong["dxt"]).ravel(),
                 np.asarray(want["dxt"]).ravel()) > 10 * LIMIT
    assert share(wrong["dy"].ravel(), np.asarray(want["dy"]).ravel()) < LIMIT


def _pre_text(streams, phi, bias, alpha):
    """The pre side in ``jax.numpy``: ``(u, z, streams)``."""
    return hc._pre_side_fwd(streams, phi, bias, alpha, N, CFG.norm_eps,
                            False)[0]


def _pre(operands, cotangents):
    """``(dX, dphi, dbias, dalpha)`` through the kernel."""
    o = operands
    out, back = jax.vjp(
        lambda *a: hc._pre_side(*a, N, CFG.norm_eps, True),
        o["streams"], o["phi"], o["bias"], o["alpha"])
    return back(tuple(c.astype(x.dtype) for c, x in zip(cotangents, out)))


@pytest.fixture(scope="module")
def pre_pair(operands):
    o = operands
    cotangents = (o["du"], o["dz"], o["dxt"])
    got = _pre(operands, cotangents)
    want = jax.vjp(_pre_text, f32(o["streams"]), o["phi"], o["bias"],
                   o["alpha"])[1](tuple(f32(c) for c in cotangents))
    names = ("dx", "dphi", "dbias", "dalpha")
    return dict(zip(names, got)), dict(zip(names, want))


@pytest.mark.parametrize("name", ["dx", "dphi", "dbias", "dalpha"])
def test_pre_bwd_matches_float32(pre_pair, name):
    got, want = pre_pair
    assert got[name].shape == want[name].shape
    assert share(got[name], want[name]) < LIMIT


def _pre_kernel(operands, without_norm=False):
    """The kernel itself on the rows ``_pre_side_bwd`` makes for it, ``(dX,
    dH_pre)``; ``without_norm``: ``v`` and ``c0`` zero."""
    o = operands
    _, (_, _, _, pre, product, factor) = hc._pre_side_fwd(
        o["streams"], o["phi"], o["bias"], o["alpha"], N, CFG.norm_eps,
        False)
    rows, p = hc._pre_bwd_rows(o["phi"], o["alpha"], pre.reshape(N, S),
                               product, factor, o["dz"].reshape(K, S), N)
    if without_norm:
        rows = rows[:3] + (jnp.zeros((N, S)), jnp.zeros((1, S)))
    dx, dpre = kernels.pre_bwd(
        hc._tokens_minor(o["du"]), hc._tokens_minor(o["streams"]),
        hc._tokens_minor(o["dxt"]), rows, p, interpret=True)
    return hc._tokens_major(dx, o["streams"].shape), dpre


@pytest.mark.parametrize("j", range(N))
def test_pre_bwd_sums_the_cotangent_of_h_pre(operands, j):
    o = operands
    pre = jax.nn.sigmoid(o["dz"][:N])       # any [n, b, s] will do
    want = jax.vjp(lambda h: hc.mix_down(h, f32(o["streams"]), jnp.float32),
                   pre)[1](f32(o["du"]))[0]
    got = _pre_kernel(operands)[1]
    assert share(got[j], want[j].ravel()) < LIMIT


def test_pre_bwd_without_the_norms_term_is_refused(operands):
    """``dX`` with the norm's factor dropped (``v`` and ``c0`` zero): read on
    what the coefficients alone send back (no ``dX~``, no ``du``) for a
    cotangent along the pre-activations themselves, which the norm's term
    takes out of ``dX`` (``z`` does not see the streams' scale)."""
    o = operands
    z = _pre_text(o["streams"], o["phi"], jnp.zeros_like(o["bias"]),
                  o["alpha"])[1]
    quiet = dict(operands, du=jnp.zeros_like(o["du"]),
                 dxt=jnp.zeros_like(o["dxt"]), dz=z / jnp.std(z))
    o = quiet
    want = jax.vjp(_pre_text, f32(o["streams"]), o["phi"], o["bias"],
                   o["alpha"])[1]((f32(o["du"]), o["dz"], f32(o["dxt"])))[0]
    right = _pre_kernel(quiet)[0]
    wrong = _pre_kernel(quiet, without_norm=True)[0]
    assert share(right, want) < LIMIT
    assert share(wrong, want) > 5 * LIMIT, share(wrong, want)


def _whole(connect, streams, phi, bias, alpha, g):
    """A hyper-connection around the sublayer ``y = u / 2``."""
    u, back, _ = connect(streams, phi, bias, alpha)
    out = back((u * 0.5).astype(u.dtype))
    return jnp.sum(f32(out) * f32(g))


@pytest.fixture(scope="module")
def whole_pair(operands):
    o = operands
    args = (o["streams"], o["phi"], o["bias"], o["alpha"])
    cfg32 = types.SimpleNamespace(**{**vars(CFG), "dtype": jnp.float32})
    got = jax.grad(lambda *a: _whole(
        lambda *b: hc.connect(CFG, *b, interpret=True), *a, o["g"]),
        argnums=(0, 1, 2, 3))(*args)
    plain = jax.grad(lambda *a: _whole(
        lambda *b: hc.reference(CFG, *b), *a, o["g"]),
        argnums=(0, 1, 2, 3))(*args)
    exact = jax.grad(lambda *a: _whole(
        lambda *b: hc.reference(cfg32, *b), *a, o["g"]),
        argnums=(0, 1, 2, 3))(f32(o["streams"]), *args[1:])
    return got, plain, exact


@pytest.mark.parametrize("at,name", enumerate(
    ["dstreams", "dphi", "dbias", "dalpha"]))
def test_a_whole_hyper_connections_gradient_through_the_kernels(
        whole_pair, at, name):
    """Within the limit of the float32 form, and no further from it than
    plain autodiff of the same text in bf16 streams by more than a
    rounding."""
    got, plain, exact = whole_pair
    assert share(got[at], exact[at]) < LIMIT
    assert share(got[at], exact[at]) < share(plain[at], exact[at]) + 2e-3


def test_the_forward_is_the_references(operands):
    o = operands
    args = (o["streams"], o["phi"], o["bias"], o["alpha"])
    u, back, res = hc.connect(CFG, *args, interpret=True)
    u0, back0, res0 = hc.reference(CFG, *args)
    np.testing.assert_array_equal(f32(u), f32(u0))
    np.testing.assert_array_equal(res, res0)
    np.testing.assert_array_equal(f32(back(o["y"])), f32(back0(o["y"])))


@pytest.mark.parametrize("n,c,tokens,dtype,taken", [
    (4, 3584, 8192, jnp.bfloat16, True),     # xing4.0-29b-a4b
    (4, 256, 128, jnp.bfloat16, True),
    (2, 128, 256, jnp.bfloat16, True),
    (4, 3584, 8192, jnp.float32, False),     # the float32 twin
    (4, 192, 8192, jnp.bfloat16, False),     # c % 128
    (4, 256, 192, jnp.bfloat16, False),      # not whole token blocks
    (5, 256, 128, jnp.bfloat16, False),      # the sums are unrolled to 4
    (6, 256, 128, jnp.bfloat16, False),
    (4, 256, 0, jnp.bfloat16, False),
])
def test_takes(n, c, tokens, dtype, taken):
    assert kernels.takes(n, c, tokens, dtype) is taken


@pytest.mark.parametrize("case", ["float32", "width", "off_the_tpu"])
def test_what_the_kernels_refuse_is_the_reference(operands, case):
    """Float32 streams, a width that is no multiple of 128 and the CPU
    without ``interpret`` give :func:`reference`'s program, its gradient's
    too, operation for operation."""
    o = operands
    streams, phi, interpret = o["streams"], o["phi"], True
    cfg = CFG
    if case == "float32":
        streams = f32(streams)
        cfg = types.SimpleNamespace(**{**vars(CFG), "dtype": jnp.float32})
    elif case == "width":
        streams, phi = streams[..., :192], phi[:N * 192]
    else:
        interpret = False

    def grad_of(connect):
        return jax.make_jaxpr(jax.grad(lambda *a: _whole(
            connect, *a, jnp.ones_like(streams)), argnums=(0, 1, 2, 3)))(
            streams, phi, o["bias"], o["alpha"])

    ours = grad_of(lambda *a: hc.connect(cfg, *a, interpret=interpret))
    plain = grad_of(lambda *a: hc.reference(cfg, *a))
    assert "pallas_call" not in str(ours)
    assert str(ours) == str(plain)
    taken = grad_of(lambda *a: hc.connect(CFG, *a, interpret=True)) \
        if case == "off_the_tpu" else None
    assert taken is None or str(taken).count("pallas_call") == 2


def _calls(jaxpr, found):
    """``{a jitted call's name: its name stack}``, through every inner
    jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit"):
            found[eqn.params["name"]] = str(eqn.source_info.name_stack)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _calls(inner, found)
    return found


def test_the_kernels_stand_under_their_scopes(operands):
    """A ``custom_vjp``'s backward is traced outside the forward's scope:
    both calls re-enter theirs, or ``hyper_connection_ms_step`` under-reads
    and the roofline share passes 100%."""
    o = operands
    jaxpr = jax.make_jaxpr(jax.grad(lambda s: _whole(
        lambda *b: hc.connect(CFG, *b, interpret=True), s, o["phi"],
        o["bias"], o["alpha"], o["g"])))(o["streams"])
    calls = _calls(jaxpr.jaxpr, {})
    assert "hvd.hc.post" in calls["post_bwd"], calls
    assert "hvd.hc.pre" in calls["pre_bwd"], calls


@pytest.mark.parametrize("name,reduction,unit,better", [
    ("hyper_connection_kernel_calls_step", "trace_op_count_per_step",
     "count/step", "higher"),
    ("hyper_connection_kernels_ms_step", "trace_op_ms_per_step", "ms/step",
     "lower"),
])
def test_the_metrics_read_both_kernels_by_their_names(name, reduction, unit,
                                                      better):
    """The op line's names under the module's own pattern, in the one cell
    that builds a hyper-connection, and nothing of another kernel's."""
    with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    (reader,) = metric["readers"]
    assert reader == {"reduction": reduction,
                      "pattern": kernels.OP_LINE_NAMES}
    assert metric["name"] == name and metric["ranks"] == "rank0"
    for op in (kernels.POST_BWD_NAME, kernels.PRE_BWD_NAME,
               kernels.PRE_BWD_NAME + ".17"):
        assert re.search(reader["pattern"], op)
    for op in ("hvd_mla_operands_bwd", "hvd_rows_to_tokens", "fusion.12"):
        assert not re.search(reader["pattern"], op)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "kernel",
                     "moves": "samples_per_s_chip",
                     "workloads": ["xing4.0-29b-a4b-wfbp-1chip"]}


def test_pallas_loads_where_a_kernel_is_built():
    """``import horovod_tpu.models.hyper_connections`` brings the kernels'
    module and not pallas."""
    import subprocess
    import sys

    code = ("import sys, horovod_tpu.models.hyper_connections\n"
            "print('horovod_tpu.kernels.hyper_connection' in sys.modules, "
            "any(m.startswith('jax.experimental.pallas') "
            "for m in sys.modules))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO_ROOT, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "PYTHONPATH": REPO_ROOT})
    assert out.stdout.strip() == "True False", out.stdout + out.stderr[-2000:]
