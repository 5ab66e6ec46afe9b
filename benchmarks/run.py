"""Benchmark harness entry point (deliverable d): one section per paper
table/figure + the roofline tables.  Prints ``name,value,...`` CSV blocks.

  table2    — optimizer-state memory (paper Table 2)
  fig1      — second-moment singular-value spectra (paper Figure 1)
  fig2      — S-RSI vs Adafactor vs SVD error/time (paper Figure 2)
  fig3      — training curves, 4 optimizers (paper Figure 3)
  ablation  — clipping (App. A), first moment (App. C), guidance (Sec 3.5)
  steptime  — optimizer update wall time
  roofline  — per (arch x cell) roofline terms from the dry-run artifacts

Run a subset: ``python -m benchmarks.run fig2 table2``.  Every section
runs even after one fails; the exit code is 1 if any failed (or was
unknown).
"""
from __future__ import annotations

import sys
import time


def main() -> int:
    sections = sys.argv[1:] or ["table2", "fig2", "fig1", "steptime",
                                "roofline", "fig3", "ablation"]
    failed = []
    for name in sections:
        t0 = time.time()
        print(f"\n# === {name} " + "=" * 50, flush=True)
        try:
            if name == "table2":
                from benchmarks.bench_memory import run
            elif name == "fig1":
                from benchmarks.bench_spectrum import run
            elif name == "fig2":
                from benchmarks.bench_srsi import run
            elif name == "fig3":
                from benchmarks.bench_training import run
            elif name == "ablation":
                from benchmarks.bench_ablation import run
            elif name == "steptime":
                from benchmarks.bench_step_time import run
            elif name == "roofline":
                from benchmarks.roofline import run
            else:
                print(f"unknown section {name!r}")
                failed.append(name)
                continue
            for row in run():
                print(row)
            print(f"# ({name}: {time.time() - t0:.1f}s)", flush=True)
        except Exception as e:  # noqa: BLE001 — keep harness going
            import traceback
            traceback.print_exc()
            print(f"# SECTION FAILED {name}: {e}")
            failed.append(name)
    if failed:
        print(f"# FAILED SECTIONS: {' '.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
