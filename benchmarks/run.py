# One function per paper table / subsystem section.  Prints the
# ``name,us_per_call,derived`` CSV; section failures become an attributable
# ``<section>_error`` row *and* a nonzero exit code (CI must not mistake a
# broken section for a clean sweep).
#
# Every run also writes a machine-readable perf-trajectory snapshot
# ``BENCH_<n>.json`` at the repo root (per-section wall time + CSV rows,
# window-cache + vector-kernel stats, jobs, git rev) — the trajectory the
# roadmap's "fast as the hardware allows" goal is tracked against.
# ``--jobs`` fans the simulation sections over a process pool; ``--quick``
# selects the CI smoke shapes; the persistent window cache warms repeated
# runs (``--cache-dir`` / ``--no-persist``, see EXPERIMENTS.md).
#
# The trajectory is numbered by the PR that recorded each point, so it has
# gaps: there is no BENCH_6.json because PR 6 (the serving engine) landed
# no trajectory-grade full-space run.  Numbers are PR labels, not a dense
# sequence — ``_default_bench_path`` therefore always proposes a *fresh*
# number and never reuses an existing one.
import argparse
import json
import os
import re
import subprocess
import sys
import time

# Direct-script invocation (`python benchmarks/run.py`) puts benchmarks/ at
# sys.path[0]; the repo root (benchmarks package) and src/ (repro package)
# must both be importable.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

#: Sections whose perf dicts land under ``perf`` in the snapshot.
_PERF: dict = {}


def _tables(args):
    from benchmarks import bench_tables
    return bench_tables.run()


def _ws_ina(args):
    from benchmarks import bench_ws_ina
    return bench_ws_ina.run(jobs=args.jobs, quick=args.quick)


def _ws_vs_os(args):
    from benchmarks import bench_ws_vs_os
    return bench_ws_vs_os.run(jobs=args.jobs, quick=args.quick)


def _kernels(args):
    from benchmarks import bench_kernels
    return bench_kernels.run()


def _mapper(args):
    from benchmarks import bench_mapper
    return bench_mapper.run(jobs=args.jobs, quick=args.quick)


def _mapper_full(args):
    from benchmarks import bench_mapper
    lines, perf = bench_mapper.run_full_perf(jobs=args.jobs)
    _PERF["mapper_full"] = perf
    return lines


def _plan(args):
    from benchmarks import bench_plan
    lines, perf = bench_plan.run(quick=args.quick)
    _PERF["plan"] = perf
    return lines


def _serve(args):
    from benchmarks import bench_serve
    lines, perf = bench_serve.run(quick=args.quick)
    _PERF["serve"] = perf
    return lines


def _hierarchy(args):
    from benchmarks import bench_hierarchy
    lines, perf = bench_hierarchy.run(quick=args.quick)
    _PERF["hierarchy"] = perf
    return lines


def _faults(args):
    from benchmarks import bench_faults
    lines, perf = bench_faults.run(quick=args.quick)
    _PERF["faults"] = perf
    return lines


def _roofline(args):
    if not os.path.exists("results/dryrun_singlepod.json"):
        return ["roofline_skipped,0,run_launch/dryrun_first"]
    from benchmarks import roofline
    return roofline.run()


def _analysis(args):
    from benchmarks import bench_analysis
    lines, perf = bench_analysis.run(quick=args.quick)
    _PERF["analysis"] = perf
    return lines


SECTIONS = {
    "tables": _tables,
    "ws_ina": _ws_ina,
    "ws_vs_os": _ws_vs_os,
    "kernels": _kernels,
    "mapper": _mapper,
    "mapper_full": _mapper_full,
    "plan": _plan,
    "serve": _serve,
    "analysis": _analysis,
    "hierarchy": _hierarchy,
    "faults": _faults,
    "roofline": _roofline,
}

#: Default section list: everything except the (slow) full-space perf probe
#: under --quick.
def _default_sections(quick: bool) -> str:
    names = [s for s in SECTIONS if not (quick and s == "mapper_full")]
    return ",".join(names)


def _error_row(section: str, exc: Exception) -> str:
    # Keep the CSV parseable: no commas/newlines in the derived column.
    msg = f"{type(exc).__name__}: {exc}".replace(",", ";")
    msg = " ".join(msg.split())[:160]
    return f"{section}_error,0,{msg}"


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_ROOT,
            capture_output=True, text=True, timeout=10).stdout.strip() or "?"
    except (OSError, subprocess.SubprocessError):
        return "?"


def _default_bench_path(args, sections, root: str = None) -> str:
    """Where a snapshot goes when ``--bench-out`` is not given.

    The repo-root ``BENCH_<n>.json`` trajectory holds one
    *trajectory-grade* data point per PR (full shapes, mapper_full perf
    probe).  The default is always the **next free** number
    (``max(taken) + 1``): the trajectory is append-only, and because its
    numbers are PR labels with gaps (no BENCH_6.json — see the file
    docstring) "one past the highest" is the only default that can never
    land on an existing file and silently overwrite a recorded point.  A
    PR that wants a specific label states it with ``--bench-out
    BENCH_<n>.json``.  Quick or partial runs must not enter the record at
    all — they land in ``results/bench_snapshot.json`` instead.
    """
    root = root or _ROOT
    if args.quick or "mapper_full" not in sections:
        return os.path.join(root, "results", "bench_snapshot.json")
    taken = [int(m.group(1)) for f in os.listdir(root)
             if (m := re.fullmatch(r"BENCH_(\d+)\.json", f))]
    return os.path.join(root, f"BENCH_{max(taken) + 1 if taken else 4}.json")


def _write_snapshot(path, args, sections, section_stats, failed) -> None:
    from repro.core.noc.simcache import SIM_CACHE
    from repro.core.noc.vectorized import vector_stats
    snap = {
        "schema": 1,
        "git_rev": _git_rev(),
        "created_unix": time.time(),
        "argv": sys.argv[1:],
        "jobs": args.jobs,
        "quick": args.quick,
        "sections": section_stats,
        "failed": failed,
        "cache": SIM_CACHE.stats(),
        "vector": vector_stats(),
        "perf": _PERF,
    }
    with open(path, "w") as fh:
        json.dump(snap, fh, indent=2)
        fh.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run benchmark sections; print name,us_per_call,derived "
                    "CSV rows and write a BENCH_<n>.json perf snapshot.")
    ap.add_argument("--sections", "--section", dest="sections", default=None,
                    help=f"comma-separated subset of {tuple(SECTIONS)}")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="process-pool width for simulation sections "
                         "(0 = all cores; default 1)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke shapes (quick sweep/mapper spaces)")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="perf-snapshot path (default: next BENCH_<n>.json "
                         "at the repo root; 'none' disables)")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="persistent window-cache directory (default "
                         "$REPRO_SIMCACHE_DIR or results/.simcache)")
    ap.add_argument("--no-persist", action="store_true",
                    help="in-memory window cache only (no on-disk store)")
    args = ap.parse_args(argv)
    if args.jobs < 0:
        ap.error("--jobs must be >= 0 (0 = all cores)")
    if args.jobs == 0:
        from repro.exec import default_jobs
        args.jobs = default_jobs(None)
    sections = [s for s in
                (args.sections or _default_sections(args.quick)).split(",")
                if s]
    unknown = [s for s in sections if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown sections {unknown}; pick from {tuple(SECTIONS)}")

    if not args.no_persist:
        from repro.core.noc.simcache import SIM_CACHE
        SIM_CACHE.persist(args.cache_dir or SIM_CACHE.persist_default_dir())

    lines = ["name,us_per_call,derived"]
    failed = []
    section_stats = {}
    for section in sections:
        t0 = time.time()
        try:
            rows = SECTIONS[section](args)
            lines += rows
            section_stats[section] = {
                "status": "ok",
                "elapsed_us": (time.time() - t0) * 1e6,
                "rows": rows,
            }
        except Exception as e:                              # noqa: BLE001
            failed.append(section)
            row = _error_row(section, e)
            lines.append(row)
            section_stats[section] = {
                "status": "error",
                "elapsed_us": (time.time() - t0) * 1e6,
                "rows": [row],
            }
    print("\n".join(lines))

    bench_path = args.bench_out or _default_bench_path(args, sections)
    if bench_path.lower() != "none":
        try:
            os.makedirs(os.path.dirname(bench_path) or ".", exist_ok=True)
            _write_snapshot(bench_path, args, sections, section_stats, failed)
            print(f"perf snapshot: {bench_path}", file=sys.stderr)
        except OSError as e:
            print(f"could not write perf snapshot: {e}", file=sys.stderr)

    if failed:
        print(f"benchmark sections failed: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
