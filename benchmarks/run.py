"""Benchmark runner — one harness per paper table/figure plus framework
benches.  Prints ``name,us_per_call,derived`` CSV rows (us_per_call is
model-microseconds for emulated-transfer benches; see common.py).

Every suite that runs also persists its result dict as
``BENCH_<suite>.json`` (stable name, sorted keys) — the committed
baselines the ``bench-diff`` CI lane compares fresh runs against (see
:mod:`benchmarks.diff`).  ``perfile`` keeps its richer model dump (per
route: t0, throughput, rho, and — where the batched data plane was
fitted — t0_batched and the speedup), so the per-file-overhead
trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                            [--out DIR]
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time
import traceback

#: THE suite registry: name -> (module under benchmarks/, one-line why).
#: The CLI help, unknown-suite guard, and default run order all derive
#: from this — adding a bench here is the whole registration.
SUITES: dict[str, tuple[str, str]] = {
    "perfile": ("bench_perfile", "Figs 6-11 + Table 1"),
    "startup": ("bench_startup", "Fig 12 (Eq. 6)"),
    "throughput": ("bench_throughput", "Figs 13-16"),
    "intercloud": ("bench_intercloud", "Figs 17-18"),
    "integrity": ("bench_integrity", "Figs 19-21"),
    "chaos": ("bench_chaos", "goodput vs fault rate"),
    "resilience": ("bench_resilience", "health plane: breakers + failover"),
    "manager": ("bench_manager", "fleet goodput + fairness + refit"),
    "federation": ("bench_federation", "multi-site goodput + handoff"),
    "svc": ("bench_svc", "service plane: streaming status vs polling"),
    "obs": ("bench_obs", "observability plane: tracing+metrics overhead"),
    "ckpt": ("bench_ckpt", "framework: §8 coalescing"),
    "data": ("bench_data", "framework: ingest"),
    "kernels": ("bench_kernels", "framework: pallas kernels"),
}


def _write_perfile_json(models: dict, path: str = "BENCH_perfile.json") -> None:
    """Serialize bench_perfile's fitted models, pairing each route with
    its ``+batch`` counterpart."""
    from .common import batched_route

    out = {}
    for route, m in models.items():
        if "+batch" in route:
            continue
        rec = {"t0": m.t0, "alpha": m.alpha, "throughput": m.throughput,
               "rho": m.rho, "r2": m.r2, "s0": m.s0}
        batched = models.get(batched_route(route))
        if batched is not None:
            rec["t0_batched"] = batched.t0
            rec["rho_batched"] = batched.rho
            rec["t0_speedup"] = (m.t0 / batched.t0
                                 if batched.t0 > 0 else float("inf"))
        out[route] = rec
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {path} ({len(out)} routes)", file=sys.stderr)


def _sanitize(value):
    """JSON-clean a suite result: stringify exotic keys/values, keep
    numbers (non-finite floats become strings so json stays strict)."""
    if isinstance(value, dict):
        return {str(k): _sanitize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_sanitize(v) for v in value]
    if isinstance(value, bool) or value is None or isinstance(value, str):
        return value
    if isinstance(value, int):
        return value
    if isinstance(value, float):
        return value if math.isfinite(value) else str(value)
    return str(value)


def _write_suite_json(name: str, result: dict, out_dir: str) -> None:
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(_sanitize(result), f, indent=1, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small N / fewer providers")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + ",".join(SUITES))
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_<suite>.json baselines "
                         "(default: cwd)")
    args = ap.parse_args()
    wanted = (args.only.split(",") if args.only else list(SUITES))
    unknown = [name for name in wanted if name not in SUITES]
    if unknown:
        print(f"# unknown suite(s): {','.join(unknown)} "
              f"(known: {','.join(SUITES)})", file=sys.stderr)
        sys.exit(2)
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.monotonic()
    failed: list[str] = []
    for name in wanted:
        module_name, why = SUITES[name]
        print(f"# --- {name} ({why}) ---", file=sys.stderr)
        try:
            # import AFTER the env flag so common.py picks QUICK up
            module = importlib.import_module(f".{module_name}",
                                             package=__package__)
            result = module.run()
        except Exception:
            # a broken benchmark must fail the scripted run (CI gates on
            # the exit code), not scroll past as a stack trace
            traceback.print_exc()
            failed.append(name)
            continue
        if name == "perfile" and result:
            _write_perfile_json(result,
                                path=os.path.join(args.out,
                                                  "BENCH_perfile.json"))
        elif result:
            _write_suite_json(name, result, args.out)
    print(f"# total wall: {time.monotonic() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
