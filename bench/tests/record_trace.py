"""Records ``data/small.xplane.pb``, the trace the reduction's test reads:
three steps of a small jitted matmul under the benchmark's span names, with
host-only work (a sleep inside ``bench.select``) between the device's
operations.  Run on a TPU:

    python3 bench/tests/record_trace.py <out_dir>
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced_steps"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                with jax.profiler.TraceAnnotation("bench.learner_step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.select"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    src = next(Path(tmp).rglob("*.xplane.pb"))
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(out_dir) / "small.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])
