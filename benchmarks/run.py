"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Set BENCH_FULL=1 for the
full-size (paper-scale) runs; the default quick mode keeps CPU wall time
manageable.

  fig3  — UPP / class-dropping effect on DBA accuracy      (paper Fig. 3)
  fig4  — KLD vs distance per assignment strategy          (paper Fig. 4)
  fig5  — accuracy vs cloud rounds + round-reduction claim (paper Fig. 5)
  fig6  — per-EU traffic at iso-accuracy                   (paper Fig. 6)
  roofline — dry-run roofline table                        (EXPERIMENTS §Roofline)
  hfl_collectives — cross-edge collective-byte claim on mesh
  distributed — MeshSyncEngine cross-mesh parity + HLO 1/T comm accounting
  kernels — Pallas kernel micro-bench (interpret mode)
  serving — prefill/decode tok/s, ragged overhead, hot-swap, serve round
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import (
        ablation_time_compression,
        distributed_bench,
        fig3_upp_dropping,
        fig4_kld_distance,
        fig5_acc_rounds,
        fig6_traffic,
        hfl_collectives,
        kernels_bench,
        roofline,
        serving_bench,
    )

    mods = [
        ("fig4", fig4_kld_distance),
        ("fig5", fig5_acc_rounds),
        ("fig3", fig3_upp_dropping),
        ("fig6", fig6_traffic),
        ("ablation", ablation_time_compression),
        ("roofline", roofline),
        ("hfl_collectives", hfl_collectives),
        ("distributed", distributed_bench),
        ("kernels", kernels_bench),
        ("serving", serving_bench),
    ]
    failures = 0
    for name, mod in mods:
        try:
            mod.main()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,FAILED")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
