#!/usr/bin/env python3
"""Records the small v5e trace kept beside the tests
(``recorded_v5e.xplane.pb``): a few runs of one jitted program that holds a
matmul and the fused cross-entropy Mosaic kernel, each launched inside a
``dispatch`` span, with a host sleep between them so that the trace has named
idle gaps. Run by hand on the chip:

    chiprun -- python3 tests/bench_harness/record_fixture.py

The trace lands in ``chiprun_out/``; copy it beside this file.
"""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks import harness, trace
    from distributed_tpu.ops import pallas_kernels

    harness.device_gate(1, rehearsal=False)

    @jax.jit
    def program(x, w, labels):
        logits = jnp.dot(x, w).astype(jnp.bfloat16)
        return jnp.mean(pallas_kernels.fused_softmax_xent(logits, labels))

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (1024, 512), jnp.bfloat16)
    w = jax.random.normal(kw, (512, 4096), jnp.bfloat16)
    labels = jnp.zeros((1024,), jnp.int32)
    jax.block_until_ready(program(x, w, labels))
    out = os.path.join(ROOT, "chiprun_out", "fixture_trace")
    shutil.rmtree(out, ignore_errors=True)
    harness.start_trace(out)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("dispatch"):
            jax.block_until_ready(program(x, w, labels))
        with jax.profiler.TraceAnnotation("input_wait"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace.find_xplane(out)
    dest = os.path.join(ROOT, "chiprun_out", "recorded_v5e.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(out, ignore_errors=True)
    t = trace.load(dest)
    dev = trace.device(t)
    print("bytes", os.path.getsize(dest), "window_s", t.window_s, "busy_s",
          trace.busy_seconds(t), "runs", len(trace.module_runs(dev)),
          "xent_s", sum(e.seconds for e in trace.matching(dev, "dtpu_xent")),
          "gaps", trace.idle_gaps(t)[:4], "ops", trace.top_ops(t)[:4])


if __name__ == "__main__":
    main()
