"""Benchmark harness: one module per paper table/figure + system benches.
Prints ``name,us_per_call,derived`` CSV lines.

The serving bench runs in smoke mode here (the full SLO sweep is a
dedicated run: ``python -m benchmarks.serve_slo``). The roofline table
needs dry-run records under results/ and is opt-in via ``--roofline``.
"""
import argparse
import os
import sys
import traceback

#: on a CPU host the BSP benches need a mesh of virtual devices; the flag
#: only shapes the host platform, so it is harmless on an accelerator. It
#: must be set before jax is imported — the modules are imported in main().
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                 ""):
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""),
         "--xla_force_host_platform_device_count=8"]).strip()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true",
                    help="include the roofline table (reads dry-run records "
                         "under results/; skipped by default)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    from . import (bsp_throughput, data_plane_bench, kernels_bench,
                   query_throughput, roofline, sa_throughput, segments_bench,
                   serve_slo, sparse_bench, supersteps, table1_example,
                   table2_covers, table3_rounds)
    mods = [table1_example, table2_covers, table3_rounds, supersteps,
            sa_throughput, query_throughput, segments_bench, sparse_bench,
            data_plane_bench, kernels_bench, bsp_throughput, serve_slo]
    if args.roofline:
        mods.insert(mods.index(bsp_throughput), roofline)
    # the harness runs the distributed + serving + data-plane benches in
    # smoke mode (full grids are dedicated runs of those modules)
    modargs = {bsp_throughput: ["--smoke", "--out", ""],
               segments_bench: ["--smoke", "--out", ""],
               sparse_bench: ["--smoke", "--out", ""],
               data_plane_bench: ["--smoke", "--out", ""],
               serve_slo: ["--smoke", "--out", ""]}
    failed = []
    for m in mods:
        name = m.__name__.split(".")[-1]
        print(f"## {name}")
        try:
            m.main(*([modargs[m]] if m in modargs else []))
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0.0,ERROR={e}")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
