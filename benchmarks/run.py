"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
import sys
import traceback


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (fig2_accuracy, fig3_intra, fig5_mean,
                            fig6_median, fig7_kmeans, fig8_ssabe,
                            fig9_sampling, fig10_delta, kernelbench,
                            roofline)
    print("name,us_per_call,derived")
    modules = [fig2_accuracy, fig3_intra, fig5_mean, fig6_median,
               fig7_kmeans, fig8_ssabe, fig9_sampling, fig10_delta,
               kernelbench, roofline]
    failed = []
    for mod in modules:
        try:
            mod.run()
        except Exception as e:
            failed.append((mod.__name__, e))
            print(f"{mod.__name__},0.0,ERROR={e!r}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
