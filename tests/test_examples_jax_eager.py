"""``examples/jax/jax_synthetic_benchmark.py --mode eager`` under ``hvdrun``:
``tests/test_examples.py``'s case, in a file of its own so that a test worker
takes it by itself (``--dist loadfile`` hands out files; the two modes are the
longest cases of the examples, 133 and 86 s in the driver's run of PR 65).
"""

import pytest

from .test_examples import _hvdrun


@pytest.mark.parametrize("mode", ["eager"])
def test_jax_synthetic_mode(mode):
    """The native example's two runtime flavors, two ranks on the XLA data
    plane: ``wfbp`` is the overlapped step (in-program gradient allreduce),
    ``eager`` goes through ``DistributedOptimizer`` and applies the
    updates under jit."""
    out = _hvdrun(
        2, ["examples/jax/jax_synthetic_benchmark.py", "--mode", mode,
            "--batch-size", "4", "--image-size", "32",
            "--num-warmup-batches", "1", "--num-iters", "1",
            "--num-batches-per-iter", "2"],
        extra_cli=("--data-plane", "xla"), timeout=420)
    assert "Total img/sec" in out
