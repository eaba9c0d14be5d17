"""Benchmark entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract.
``--full`` raises the dataset scale (default is CPU-minutes sized).
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-store", action="store_true",
                    help="skip the store-throughput sweep (figures only)")
    ap.add_argument("--skip-hotpath", action="store_true",
                    help="skip the one-pass search hot-path comparison")
    ap.add_argument("--skip-frontier", action="store_true",
                    help="skip the adaptive-vs-fixed recall frontier")
    args = ap.parse_args()

    from . import fig4_rho, fig5_effect_n, fig8_effect_k, fig9_recall_time, table4_query_perf

    print("name,us_per_call,derived")

    rows = table4_query_perf.run(scale=args.scale)
    for r in rows:
        print(f"table4/{r['dataset']}/{r['method']},{r['query_ms_per_q']*1e3:.1f},"
              f"recall={r['recall']:.3f};ratio={r['overall_ratio']:.3f};idx_s={r['index_s']:.2f}")

    for r in fig4_rho.run():
        print(f"fig4/rho_star,0,c={r['c']:.2f};rho*={r['rho_star_4c2']:.5f};"
              f"bound={r['bound_1_c_alpha']:.5f}")

    for r in fig5_effect_n.run(fractions=(0.25, 0.5, 1.0)):
        print(f"fig5/effect_n/{r['method']},{r['query_ms_per_q']*1e3:.1f},"
              f"n={r['n']};recall={r['recall']:.3f}")

    for r in fig8_effect_k.run(ks=(1, 10, 50), scale=args.scale):
        print(f"fig8/effect_k/{r['method']},{r['query_ms_per_q']*1e3:.1f},"
              f"k={r['k']};recall={r['recall']:.3f}")

    for r in fig9_recall_time.run(scale=args.scale):
        print(f"fig9/recall_time,{r['query_ms_per_q']*1e3:.1f},"
              f"c={r['c']};steps={r['steps']};recall={r['recall']:.3f}")

    if not args.skip_store:
        from . import store_throughput

        report = store_throughput.main(
            scale=args.scale, out="store_throughput.json"
        )
        for r in report["results"]:
            print(f"store/qps/{r['engine']}/bs{r['batch_size']},"
                  f"{1e6 / r['sustained_qps']:.1f},"
                  f"qps={r['sustained_qps']:.1f};p50ms={r['latency_ms_p50']:.1f};"
                  f"p99ms={r['latency_ms_p99']:.1f}")

    if not args.skip_hotpath:
        from . import search_hotpath

        rep = search_hotpath.run(
            n=max(4096, int(100_000 * args.scale)), smoke=args.scale < 1.0
        )
        for eng, r in rep["engines"].items():
            print(f"hotpath/{eng},{1e6 / r['qps_new']:.1f},"
                  f"speedup={r['speedup']};qps_ref={r['qps_ref']};"
                  f"recall={r['recall_new']:.3f}")

    if not args.skip_frontier:
        from . import recall_frontier

        rep = recall_frontier.run(
            n=max(8192, int(100_000 * args.scale)),
            d=64 if args.scale >= 1.0 else 24,
            smoke=args.scale < 1.0,
        )
        for row in rep["fixed"]:
            print(f"frontier/fixed/steps{row['steps']},"
                  f"{1e6 / row['qps']:.1f},"
                  f"recall={row['recall']:.3f};slots={row['mean_slots']}")
        for tag in ("adaptive", "planned_adaptive"):
            r = rep[tag]
            print(f"frontier/{tag},{1e6 / r['qps']:.1f},"
                  f"recall={r['recall']:.3f};slots={r['mean_slots']};"
                  f"term_step={r['mean_term_step']}")

    if not args.skip_roofline:
        from . import roofline

        for mesh in ("pod16x16", "pod2x16x16"):
            for r in roofline.run(mesh):
                if r.get("status") == "ok":
                    print(f"roofline/{mesh}/{r['arch']}/{r['shape']},0,"
                          f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
                          f"mem={r['mem_gib_per_dev']:.1f}GiB")
                else:
                    print(f"roofline/{mesh}/{r['arch']}/{r['shape']},0,{r['status']}")


if __name__ == "__main__":
    from repro.jit_cache import enable_compile_cache

    enable_compile_cache()
    main()
