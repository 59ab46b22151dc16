"""Record the small trace ``test_trace_reduce.py`` reads.

    python benchmark/tests/record_fixture.py <out_dir>

Two benchmark spans around runs of two jitted programs (milliseconds
each) with host sleeps of 50 and 30 ms, so the trace has device
programs, busy time and idle gaps labelled by a span.  Recorded on a TPU v5e for the committed
fixture; on the CPU it records no device plane.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def fused_tile_step(x):
    for _ in range(64):
        x = jnp.sin(x) * 1.0001 + x
    return x


@jax.jit
def screen_step(x):
    return (x @ x.T).sum(axis=0)


def main(out_dir: str) -> None:
    x = jnp.ones((4096, 4096), jnp.float32)
    fused_tile_step(x).block_until_ready()
    screen_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dedup_reps"):
            for _ in range(3):
                x = fused_tile_step(x)
            x.block_until_ready()
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.match_chunk"):
            screen_step(x).block_until_ready()
        time.sleep(0.03)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
