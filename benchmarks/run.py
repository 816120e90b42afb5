"""Benchmark runner: one section per paper table/figure + framework
benchmarks.  ``python -m benchmarks.run [--fast] [--json out.json]``
prints CSV rows and optionally writes the same results machine-readable.

Sections:
  fig5     — accuracy vs output-layer executions (paper Fig. 5)
  table2   — silicon throughput/power model (paper Table II)
  kern     — Pallas kernel microbench + TPU memory-roofline derivations
  roofline — the 40-cell dry-run roofline table (§Roofline source)
  e2e      — fused-pipeline vs layer-by-layer end-to-end throughput
  conv     — end-to-end binary CNN: fused conv pipeline vs unpacked
             layer-by-layer + accuracy-vs-passes on 28x28/64x64
  noise    — silicon-noise robustness curves + fused-MC vs faithful speedup
  serve    — classification serving engine under closed/open-loop load

JSON schema (picbnn-bench/v1): {"schema", "meta": {...}, "sections":
{name: [row, ...]}} where each row is the section's CSV tuple as a list
(the e2e section emits measurement dicts instead of CSV tuples).
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def _rows_jsonable(rows):
    return [list(r) if isinstance(r, (tuple, list)) else r for r in rows]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset: "
                         "fig5,table2,kern,roofline,e2e,conv,noise,serve")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (sections -> rows)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    t0 = time.time()
    from benchmarks import (
        accuracy,
        conv_throughput,
        e2e_throughput,
        kernels_bench,
        noise_robustness,
        roofline_table,
        serve_load,
        table2,
    )

    sections: dict[str, list] = {}
    if only is None or "table2" in only:
        sections["table2"] = _rows_jsonable(table2.main())
    if only is None or "kern" in only:
        sections["kern"] = _rows_jsonable(kernels_bench.main(fast=args.fast))
    if only is None or "roofline" in only:
        sections["roofline"] = _rows_jsonable(roofline_table.main())
    if only is None or "e2e" in only:
        # rows only — the committed BENCH_e2e.json trajectory file is
        # written solely by `python -m benchmarks.e2e_throughput`
        sections["e2e"] = _rows_jsonable(
            e2e_throughput.main(fast=args.fast, write_json=False)
        )
    if only is None or "conv" in only:
        # dict rows — the committed BENCH_conv.json trajectory file is
        # written solely by `python -m benchmarks.conv_throughput`
        sections["conv"] = conv_throughput.main(fast=args.fast,
                                                write_json=False)
    if only is None or "noise" in only:
        # rows only — the committed BENCH_noise.json trajectory file is
        # written solely by `python -m benchmarks.noise_robustness`
        sections["noise"] = _rows_jsonable(
            noise_robustness.main(fast=args.fast, write_json=False)
        )
    if only is None or "serve" in only:
        # dict rows — the committed BENCH_serve.json trajectory file is
        # written solely by `python -m benchmarks.serve_load`
        sections["serve"] = serve_load.main(fast=args.fast,
                                            write_json=False)
    if only is None or "fig5" in only:
        sections["fig5"] = _rows_jsonable(accuracy.main(fast=args.fast))
    elapsed = time.time() - t0
    print(f"# benchmarks done in {elapsed:.1f}s")

    if args.json:
        import jax

        record = {
            "schema": "picbnn-bench/v1",
            "meta": {
                "fast": args.fast,
                "elapsed_s": round(elapsed, 2),
                "backend": jax.default_backend(),
                "platform": platform.platform(),
                "python": sys.version.split()[0],
                "jax_version": jax.__version__,
            },
            "sections": sections,
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}")
    return sections


if __name__ == "__main__":
    main()
