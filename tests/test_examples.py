"""Smoke-run every BASELINE example config under a real 2-process hvdrun
launch with CI-sized knobs (BASELINE.md: "examples running unmodified" is
the acceptance bar; reference CI runs its examples the same way)."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launch_once(cmd, env, timeout):
    """One launcher invocation in its OWN process group: a timeout kill
    must reach the worker grandchildren too (killing only the launcher
    leaves orphans holding the output pipes — communicate() would block
    on them, and they'd keep loading the box for the retry)."""
    import signal

    proc = subprocess.Popen(cmd, cwd=REPO_ROOT, text=True, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err, False
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        out, err = proc.communicate()
        return proc.returncode, out, err, True


def _hvdrun(np_, script_args, timeout=420, extra_cli=()):
    from .helpers import (
        _log_retry,
        _timeout_scale,
        infra_retryable,
        retry_backoff,
    )

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TF_CPP_MIN_LOG_LEVEL="2")
    from .helpers import scaled_mesh_startup_timeout

    env.setdefault("HOROVOD_MESH_STARTUP_TIMEOUT",
                   scaled_mesh_startup_timeout())
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(np_), *extra_cli, sys.executable, *script_args]
    # Same load-scaled-timeout + infra-retry intent as
    # helpers.run_distributed.  The launcher interleaves rank streams, so
    # the per-rank gate is approximated: retry only when infra text is
    # present AND no product-assert marker is — one rank's peer-death
    # text must not mask a sibling's real crash.
    for attempt in (0, 1, 2):
        code, out, err, timed_out = _launch_once(
            cmd, env, timeout * _timeout_scale())
        if code == 0:
            break
        blob = out + err
        retryable = (timed_out or infra_retryable(AssertionError(blob))) \
            and "AssertionError" not in blob
        if attempt == 2 or not retryable:
            break
        _log_retry(f"_hvdrun attempt {attempt + 1}: timed_out={timed_out}")
        retry_backoff(attempt + 1)
    assert code == 0, (
        f"timed_out={timed_out} (budget {timeout * _timeout_scale():.0f}s)",
        out[-2000:], err[-2000:])
    return out


def test_keras_mnist(tmp_path):
    tf = pytest.importorskip("tensorflow")  # noqa: F841
    out = _hvdrun(2, ["examples/keras/keras_mnist.py", "--epochs", "1"])
    assert "FINAL rank0 loss=" in out


def test_tensorflow2_synthetic_benchmark():
    tf = pytest.importorskip("tensorflow")  # noqa: F841
    out = _hvdrun(2, ["examples/tensorflow2/tensorflow2_synthetic_benchmark.py",
                      "--num-iters", "1", "--num-warmup-batches", "1",
                      "--num-batches-per-iter", "1", "--batch-size", "2",
                      "--image-size", "32"])
    assert "img/sec" in out.lower() or "images/sec" in out.lower()


def test_pytorch_imagenet_resnet50(tmp_path):
    torch = pytest.importorskip("torch")  # noqa: F841
    out = _hvdrun(2, ["examples/pytorch/pytorch_imagenet_resnet50.py",
                      "--epochs", "1", "--synthetic-batches", "2",
                      "--image-size", "32", "--batch-size", "2",
                      "--checkpoint-format",
                      str(tmp_path / "ck-{epoch}.pth.tar")])
    assert "epoch 0" in out


def test_adasum_bert_pretraining():
    # Two ranks each compile the BERT pretraining step — the heaviest
    # compile in the suite; the default 420 s budget is marginal even
    # before load scaling (sole failure of full runs 3 and 4).
    out = _hvdrun(2, ["examples/adasum/adasum_bert_pretraining.py",
                      "--steps", "3", "--batch-size", "2",
                      "--seq-len", "16"], timeout=900)
    assert "ADASUM BERT DONE" in out


def test_elastic_tensorflow2_resnet50(tmp_path):
    tf = pytest.importorskip("tensorflow")  # noqa: F841
    discover = tmp_path / "discover.sh"
    discover.write_text("#!/bin/sh\necho localhost:2\n")
    discover.chmod(0o755)
    out = _hvdrun(2, ["examples/elastic/tensorflow2_resnet50_elastic.py",
                      "--batches", "6", "--commit-every", "3",
                      "--batch-size", "2", "--image-size", "32"],
                  extra_cli=["--min-np", "1",
                             "--host-discovery-script", str(discover)])
    assert "ELASTIC RESNET DONE" in out
