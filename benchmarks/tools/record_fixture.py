"""Record the small trace that ``tests/test_trace.py`` reduces: five launches
of one small program, 30 ms apart, the waits under a ``bench.input_wait`` span.

    python3 benchmarks/tools/record_fixture.py <out_dir>     (on the chip)
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace

    @jax.jit
    def fixture_step(x):
        return jnp.tanh(x @ x) / 64.0

    x = jnp.ones((512, 512), jnp.bfloat16)
    fixture_step(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="bench_fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(5):
            x = fixture_step(x)
            x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.input_wait"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(trace.find_xplane(log_dir), os.path.join(out_dir, "fixture.xplane.pb"))
    shutil.rmtree(log_dir, ignore_errors=True)
    print(os.path.getsize(os.path.join(out_dir, "fixture.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
