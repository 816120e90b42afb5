"""Runner for the paper's figure and table scripts.
``python -m benchmarks.run [--fast] [--json out.json]`` prints CSV rows
and optionally writes the same results machine-readable.  Speed on the
chip is measured by the on-chip benchmark (`bench/`, `BENCHMARK.json`),
not here.

Sections:
  fig5     — accuracy vs output-layer executions (paper Fig. 5)
  table2   — silicon throughput/power model (paper Table II)
  roofline — the 40-cell dry-run roofline table (§Roofline source)
  noise    — silicon-noise robustness curves + fused-MC vs faithful speedup

JSON schema (picbnn-bench/v1): {"schema", "meta": {...}, "sections":
{name: [row, ...]}} where each row is the section's CSV tuple as a list.
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
                         "fig5,table2,roofline,noise")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (sections -> rows)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    t0 = time.time()
    from benchmarks import accuracy, noise_robustness, roofline_table, table2

    sections: dict[str, list] = {}
    if only is None or "table2" in only:
        sections["table2"] = _rows_jsonable(table2.main())
    if only is None or "roofline" in only:
        sections["roofline"] = _rows_jsonable(roofline_table.main())
    if only is None or "noise" in only:
        # rows only — the committed BENCH_noise.json trajectory file is
        # written solely by `python -m benchmarks.noise_robustness`
        sections["noise"] = _rows_jsonable(
            noise_robustness.main(fast=args.fast, write_json=False)
        )
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
