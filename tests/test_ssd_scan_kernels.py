"""The chunked state-space scan's two pallas kernels
(``kernels/ssd_scan.py``) in interpret mode against the chunked form, which
``tests/test_ssd_scan.py`` holds to the recurrence, and what ``takes()``
refuses.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from .test_olmoe import rel_err
from .test_ssd_scan import scan_inputs


@pytest.mark.parametrize("heads,p,groups", [(16, 64, 1), (32, 64, 2),
                                            (8, 128, 1), (64, 64, 1)],
                         ids=["the_cells", "two_groups", "heads_of_128",
                              "granites_64_heads_one_group"])
def test_the_kernels_are_the_chunked_form(heads, p, groups):
    """The two pallas kernels in interpret mode against ``chunked`` on the
    same bf16 inputs: ``y`` to bf16's rounding, the cotangents of ``x``,
    ``B`` and ``C`` too, those of ``dt`` and ``a`` (fp32 sums) closer."""
    from horovod_tpu.kernels import ssd_scan

    args = scan_inputs(1, 2, 256, heads, p, groups, 128, jnp.bfloat16)
    assert ssd_scan.takes(256, heads, p, groups, 128)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(weight * fn(*a).astype(jnp.float32))

    got = ssd_scan.ssd_scan(*args, interpret=True)
    want = ssd_scan.chunked(*args)
    assert got.dtype == jnp.bfloat16 and rel_err(got, want) < 1e-2
    grads = jax.grad(loss(functools.partial(ssd_scan.ssd_scan,
                                            interpret=True)),
                     argnums=range(5))(*args)
    want_grads = jax.grad(loss(ssd_scan.chunked), argnums=range(5))(*args)
    for name, g, w, tol in zip("x dt a b c".split(), grads, want_grads,
                               (1e-2, 2e-3, 2e-3, 1e-2, 1e-2)):
        assert g.dtype == w.dtype and rel_err(g, w) < tol, name


@pytest.mark.parametrize("shape,taken", [
    ((8192, 16, 64, 1, 128), True), ((8192, 128, 64, 8, 128), True),
    ((8192, 64, 64, 1, 128), True),
    ((8192, 16, 64, 1, 64), False), ((8100, 16, 64, 1, 128), False),
    ((8192, 4, 64, 1, 128), False), ((8192, 16, 32, 1, 128), False),
    ((8192, 16, 64, 3, 128), False)],
    ids=["the_cells", "the_whole_mixer", "granites_whole_mixer", "state_64",
         "no_whole_chunks", "four_heads", "heads_of_32",
         "heads_in_no_groups"])
def test_takes_refuses_what_the_kernels_cannot_run(shape, taken):
    from horovod_tpu.kernels import ssd_scan

    assert ssd_scan.takes(*shape) is taken
    assert not ssd_scan.takes(*shape, dtype=jnp.float32)
    assert not ssd_scan.takes(*shape, chunk=64)
