"""Records ``data/scoped.xplane.pb``, the trace the scope reduction's test
reads: a jitted ``step`` whose parts carry name scopes (a ``lax.scan``, a
``while`` on the device, under ``demo.loop`` with its body's product under
``demo.body``, then a last product under ``demo.tail``), called three times
in the benchmark's window span, each call in a ``nat.`` span inside a
``bench.`` span, with host-only work (a sleep in ``nat.demo.host`` inside
``bench.select``) between the device's operations.  Run on a TPU:

    python3 bench/tests/record_scoped_trace.py <out_dir>
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def step(x):
    def body(c, _):
        with jax.named_scope("demo.body"):
            return jnp.tanh(c @ x), None

    with jax.named_scope("demo.loop"):
        c, _ = jax.lax.scan(body, x, None, length=4)
    with jax.named_scope("demo.tail"):
        return c @ x


def main(out_dir: str) -> None:
    f = jax.jit(step)
    x = jnp.ones((1024, 1024), jnp.bfloat16) / 1024
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced_steps"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                with jax.profiler.TraceAnnotation("nat.demo.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.select"):
                    with jax.profiler.TraceAnnotation("nat.demo.host"):
                        time.sleep(0.02)
    jax.profiler.stop_trace()
    src = next(Path(tmp).rglob("*.xplane.pb"))
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(out_dir) / "scoped.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
