"""Benchmark orchestrator: one section per paper table/figure.

Prints ``name,...`` CSV blocks.  Roofline rows appear when dry-run reports
exist (reports/dryrun/*.json).
"""

from __future__ import annotations

import sys

sys.path.insert(0, "src")


def main() -> None:
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_table2, bench_table3, bench_fig6,
                            bench_fig7, bench_fig8, bench_kernels, roofline)

    print("# === Table II: per-layer backprop runtime ===")
    bench_table2.run()
    print("\n# === Table III: prologue latency ===")
    bench_table3.run()
    print("\n# === Fig 6: runtime reduction per network ===")
    bench_fig6.run()
    print("\n# === Fig 7: off-chip bandwidth reduction ===")
    bench_fig7.run()
    print("\n# === Fig 8: buffer bandwidth reduction (sparsity) ===")
    bench_fig8.run()
    print("\n# === Kernel microbenchmarks (CPU wall-clock) ===")
    bench_kernels.run()
    print("\n# === Roofline (from dry-run artifacts) ===")
    try:
        rows = roofline.run()
        if not rows:
            print("(no dry-run reports found; run repro.launch.dryrun)")
    except Exception as e:  # noqa: BLE001
        print(f"(roofline unavailable: {e})")


if __name__ == "__main__":
    main()
