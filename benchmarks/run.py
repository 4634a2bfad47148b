"""Paper-figure harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * bench_precision    — Fig. 4  (DIBL error surface, effective bits)
  * bench_energy_area  — Fig. 5  (energy + area vs N, section 4.2 anchors)
  * bench_latency      — section 4.2 latency / Fig. 2d pipelining
  * bench_comparison   — section 4.2 prior-work comparison table
  * bench_perceptron   — section 3 case study (10x10x10 time-domain MLP)
  * bench_llm_mapping  — beyond-paper: assigned archs costed on TD-VMM tiles

Serving speed on the chip is measured by ``bench/run.py`` over the cells of
``BENCHMARK.json``, not here.
"""
from __future__ import annotations

import traceback


def main() -> None:
    from benchmarks import (bench_comparison, bench_energy_area,
                            bench_latency, bench_llm_mapping,
                            bench_perceptron, bench_precision)
    print("name,us_per_call,derived")
    failed = False
    for mod in (bench_precision, bench_energy_area, bench_latency,
                bench_comparison, bench_perceptron, bench_llm_mapping):
        try:
            mod.run()
        except Exception:  # noqa: BLE001 — benches are independent
            failed = True
            print(f"{mod.__name__},ERROR,see_stderr")
            traceback.print_exc()
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
