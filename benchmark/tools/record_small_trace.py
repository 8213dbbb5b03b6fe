"""Records the small trace kept as ``tests/data/small.xplane.pb``: three
steps of a small jitted program on the chip, each under the benchmark's
``bench:trainer.step`` span.  Run on the chip; writes into chiprun_out/."""
import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp


def main(out="chiprun_out/small_trace"):
    if jax.devices()[0].platform == "cpu":
        sys.exit("record on the chip")
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:trainer.step"):
            x = step(x)
            x.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, "chiprun_out/small.xplane.pb")
    print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
