# One module per paper table/figure.  Prints ``name,value,derived`` CSV rows
# and writes JSON artifacts under benchmarks/artifacts/.
from __future__ import annotations

import argparse
import time

from . import (fig7_makespan, fig8_tails, fig9_jct_cdf, fig10_poisson,
               fig11_utilization, fig12_contention, fig13_parallelism,
               fig14_scale, fig15_failures, fig16_degradation,
               table1_comm_latency, table2_jct_stats)

ALL = [
    ("table1_comm_latency", table1_comm_latency.main),
    ("fig7_makespan", fig7_makespan.main),
    ("fig8_tails", fig8_tails.main),
    ("fig9_jct_cdf", fig9_jct_cdf.main),
    ("fig10_poisson", fig10_poisson.main),
    ("table2_jct_stats", table2_jct_stats.main),
    ("fig11_utilization", fig11_utilization.main),
    ("fig12_contention", fig12_contention.main),
    ("fig13_parallelism", fig13_parallelism.main),
    ("fig14_scale", fig14_scale.main),
    ("fig15_failures", fig15_failures.main),
    ("fig16_degradation", fig16_degradation.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="reduced job counts / rack sweep for quick runs")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    for name, fn in ALL:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        print(f"### {name}", flush=True)
        fn(small=args.small)
        print(f"bench.{name}.wall_seconds,{time.time()-t0:.1f},", flush=True)


if __name__ == "__main__":
    main()
