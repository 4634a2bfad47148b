#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints progress and, last, every number the correctness check compared
beside its limit on standard error; the last line of standard output is the
result as one JSON object.  Without a TPU, with fewer chips than the cell
asks for, or on a device missing from ``bench/peaks.json``, it exits with
code 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control (the reference in float8) on the "
                         "window's served tokens instead of the check")
    args = ap.parse_args()

    from bench import harness, work
    from repro.launch.xla_setup import honor_bf16_rounding, use_persistent_cache
    honor_bf16_rounding()
    import jax
    devices = jax.devices()
    manifest, cell, cfg, mix = harness.cell_parts(args.workload)
    if devices[0].platform != "tpu":
        print(f"bench: no TPU visible (platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 2
    work.peaks(devices[0].device_kind)
    cache = use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log(f"{args.workload}: {devices[0].device_kind} x{len(devices)}, "
                f"jax {jax.__version__}, compile cache {cache}")
    out = harness.run(cell, cfg, mix, manifest, args.seed, args.seconds,
                      bool(args.trace), T_START, control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
