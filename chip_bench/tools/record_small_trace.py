"""Record the small traces kept in ``tests/data/``: a few steps of a tiny
made-up loop with the benchmark's span names, so that ``trace_reduce.py`` can
be checked on a real ``.xplane.pb`` by hand.

    hvdrun -np 1 python3 chip_bench/tools/record_small_trace.py --out chiprun_out/small
    hvdrun -np 4 python3 chip_bench/tools/record_small_trace.py --out chiprun_out/small

Each step: a jitted chain of matmuls (``grad_step``), a host sleep with, at
several ranks, an ``hvd.allreduce`` of the result (``dopt.update``), and the
read of a scalar (``loss_read``).  Rank 0 traces its chip and writes
``small_np<N>.xplane.pb``.  Needs a TPU; nothing here is a measurement.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as span

    import horovod_tpu as hvd
    from chip_bench import trace_reduce

    hvd.init()
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("record_small_trace: needs a TPU")

    @jax.jit
    def grad_step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x, jnp.sum(x.astype(jnp.float32))

    x = jax.device_put(jnp.full((2048, 2048), 0.01, jnp.bfloat16), dev)

    def step(x):
        with span("grad_step"):
            y, loss = grad_step(x)
        with span("dopt.update"):
            time.sleep(0.003)
            if hvd.size() > 1:
                y = hvd.allreduce(y, name="small.y")
        with span("loss_read"):
            float(loss)
        return y

    for _ in range(3):
        x = step(x)
    trace_dir = tempfile.mkdtemp(prefix="small-trace-")
    if hvd.rank() == 0:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(args.steps):
        x = step(x)
    if hvd.rank() == 0:
        jax.profiler.stop_trace()
        os.makedirs(args.out, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(trace_dir), os.path.join(
            args.out, f"small_np{hvd.size()}.xplane.pb"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
