"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits are found
by name from BENCHMARK.json (see bench/harness/registry.py).  The run
needs the chips the cell asks for: where JAX finds no TPU, or too few,
it exits 2 and prints no result.  The last line of standard output is
the result object; the numbers the check compared, each beside its
limit, are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    from harness import registry
    spec = registry.load_benchmark(ROOT)
    cell = registry.workload(spec, args.workload)
    cfg = registry.config(spec, cell["config"], ROOT)
    mix = registry.traffic(cell["traffic"])

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}",
          flush=True)
    sys.path.insert(0, str(ROOT / "src"))
    from harness.cell import run_cell
    out = run_cell(spec, cell, cfg, mix, args.seed, args.seconds,
                   bool(args.trace), T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
