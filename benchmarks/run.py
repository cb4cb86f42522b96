"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (harness contract).

  fig4_1/4_2  image segmentation      -> bench_images
  fig4_3      scaling vs workers      -> bench_scaling
  fig5_1      purity vs HK-Means      -> bench_purity
  kernels     HAP kernel microbench   -> bench_kernels
  roofline    dry-run roofline rows   -> roofline (reads results/dryrun)
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: images,scaling,purity,kernels,roofline")
    ap.add_argument("--fast", action="store_true",
                    help="smaller sizes / fewer worker counts")
    args = ap.parse_args(argv)
    wanted = set(args.only.split(",")) if args.only else {
        "images", "scaling", "purity", "kernels", "roofline"}

    if "images" in wanted:
        from benchmarks import bench_images
        bench_images.main()
    if "purity" in wanted:
        from benchmarks import bench_purity
        bench_purity.main()
    if "kernels" in wanted:
        from benchmarks import bench_kernels
        bench_kernels.main([])
    if "scaling" in wanted:
        from benchmarks import bench_scaling
        if args.fast:
            rows = bench_scaling.run(n=256, iterations=10,
                                     worker_counts=(1, 4))
            for r in rows:
                print(f"mrhap_scaling_{r['mode']}_w{r['workers']},"
                      f"{r['wall_s'] * 1e6 / r['iterations']:.0f},"
                      f"comm={r['comm_bytes_iter']}B")
        else:
            bench_scaling.main([])
    if "roofline" in wanted:
        from benchmarks import roofline
        roofline.main()
    return 0


if __name__ == "__main__":
    sys.exit(main())
