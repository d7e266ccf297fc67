"""Benchmark harness: one function per paper table/figure.

  fig3_lda       — paper Fig. 3 (exec time vs K, butterfly vs prefix)
  sampler_bench  — core drawing-strategy throughput grid (paper §5 micro);
                   also writes BENCH_sampler.json
  roofline       — §Roofline terms from the dry-run artifacts

Prints ``name,us_per_call,derived`` CSV.
"""

import sys


def main() -> None:
    args = set(sys.argv[1:])
    run_all = not args

    if run_all or "sampler" in args:
        from benchmarks import sampler_bench
        sampler_bench.main([])
    if run_all or "fig3" in args:
        from benchmarks import fig3_lda
        fig3_lda.main()
    if run_all or "roofline" in args:
        from benchmarks import roofline
        roofline.main()


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
