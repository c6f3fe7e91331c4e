"""Readings that set the comparison's limits: the program's own numbers
over many seeds and the control's, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
        [--fault <name>]

For each seed the cell is set up and warmed up as a run would be, one
window of `--seconds` is measured, and the answers kept from it are
held to the reference twice: as the program gave them, and as the
control gives them (the reference at bfloat16 put in the program's
place, see harness/check.py).  With `--fault`, one of
`harness/faults.py` is planted under the timed path first, and the
"sound" numbers are the broken program's.  One JSON line per seed.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import faults, registry
    from harness.cell import readings
    if args.fault:
        faults.plant(args.fault)
    spec = registry.load_benchmark(ROOT)
    cell = registry.workload(spec, args.workload)
    cfg = registry.config(spec, cell["config"], ROOT)
    mix = registry.traffic(cell["traffic"])
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        sound, ctrl = readings(cfg, mix, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, "sound": sound,
                          "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
