"""Record ``chipbench/testdata/small.xplane.pb`` on the chip (run once,
by hand; the file is committed):

    python chipbench/tests/record_fixture.py <out_dir>

Five bursts of matrix multiplications with a 30 ms sleep after each,
under harness-style spans, so that the reduction's answers are known
from the recipe: five long idle gaps of about 30 ms, each inside a
``sleep`` span, and device time that the ``XLA Modules`` line confirms.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def burst(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    burst(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench:window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("chipbench:work"):
                burst(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench:sleep"):
                time.sleep(0.03)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
