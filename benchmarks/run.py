"""Benchmark runner: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Sizes are scaled for the
CPU container; pass --full for larger sweeps.  The roofline section reads
the dry-run artifacts if present (see repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from . import (bench_batch_size, bench_cofactor, bench_factorized_payloads,
               bench_grad_compression, bench_kernels, bench_matrix_chain,
               bench_serve, bench_stream, bench_sum_aggregates,
               bench_triangle, bench_view_counts, roofline)
from .common import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    use_compile_cache()

    sections = [
        ("stream executor (fused vs per-call; BENCH_stream.json)",
         lambda: bench_stream.run(
             batches=(16, 64, 256, 1024) if args.full else (16, 64, 256))),
        ("serve (snapshot reads; BENCH_serve.json)",
         lambda: bench_serve.run(
             batches=(64, 1024, 8192, 32768) if args.full
             else (64, 1024, 8192))),
        ("sum_aggregates (Fig 8)", lambda: bench_sum_aggregates.run(
            batch=512 if args.full else 256)),
        ("matrix_chain (Fig 9)", lambda: bench_matrix_chain.run(
            sizes=(128, 256, 512, 1024) if args.full else (128, 256))),
        ("cofactor (Fig 10)", lambda: bench_cofactor.run(
            batch=256 if args.full else 64, n_batches=8)),
        ("triangle (Fig 11)", lambda: bench_triangle.run(
            n=96 if args.full else 32)),
        ("batch_size (Fig 12)", lambda: bench_batch_size.run(
            batches=(16, 64, 256, 1024, 4096) if args.full else (16, 128, 512))),
        ("factorized_payloads (Fig 13)", lambda: bench_factorized_payloads.run(
            scales=(8, 16, 32, 64) if args.full else (8, 16))),
        ("view_counts (Sec 8.2/8.4)", bench_view_counts.run),
        ("kernels", bench_kernels.run),
        ("grad_compression", bench_grad_compression.run),
        ("roofline (from dry-run artifacts)", roofline.run),
    ]
    failures = 0
    for title, fn in sections:
        if args.only and args.only not in title:
            continue
        print(f"\n### {title}")
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
