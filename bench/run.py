"""The on-chip benchmark of the NAT training step.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  One process holds the chip; there is no CPU fallback.  The last
line of standard output is the result as one JSON object; the numbers that
decide ``correct`` are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.enable()          # a fatal signal prints the Python stack

    from bench import harness

    spec = harness.load_spec(args.workload, ROOT)
    import jax

    cache = use_compile_cache()
    devs = jax.devices()
    need = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench: {args.workload} needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    harness.say(f"device: {devs[0].device_kind} x{len(devs)}, jax "
                f"{jax.__version__}, compile cache {cache}")
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, root=ROOT, spec=spec)
    for name, c in out["checks"].items():
        harness.say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
