"""Benchmark driver — one function per paper table.

Prints ``name,us_per_call,derived`` CSV per benchmark row, plus the
roofline table from the latest dry-run artifacts if present.

  PYTHONPATH=src python -m benchmarks.run [--rows N] [--quick]

Perf-claim protocol (ROADMAP): this container's timings swing ±30-100%
run to run, so before/after comparisons must use ``--repeat`` (min-fold)
AND ``--interleave OLD_CHECKOUT`` — each repeat runs the baseline tree
and the current tree back to back in subprocesses, so machine-state drift
hits both sides equally instead of masquerading as a regression.
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile


_HOTPATH_METRICS = ("diff_cold_s", "diff_warm_s", "merge_s")
_WORKFLOW_METRICS = ("branch_s", "pr_diff_s", "publish_s", "revert_s")
_PROBE_METRICS = ("probe_s",)
_COLDSTORE_METRICS = ("spill_s", "evict_s", "diff_fault_s",
                      "diff_warm_s", "merge_fault_s")


def _row_metrics(row_or_op):
    op = row_or_op if isinstance(row_or_op, str) else row_or_op["op"]
    if op.startswith("Workflow"):
        return _WORKFLOW_METRICS
    if op.startswith("Probe"):
        return _PROBE_METRICS
    if op.startswith("Coldstore"):
        return _COLDSTORE_METRICS
    return _HOTPATH_METRICS


def _environment() -> dict:
    """Provenance header for every BENCH json (ISSUE 10 satellite): two
    artifacts are only comparable when this block matches."""
    import platform
    env = {"platform": platform.platform(),
           "python": platform.python_version(),
           "jax_platforms": os.environ.get("JAX_PLATFORMS", "")}
    try:
        import numpy
        env["numpy"] = numpy.__version__
    except ImportError:  # pragma: no cover
        env["numpy"] = None
    try:
        import jax
        env["jax"] = jax.__version__
    except ImportError:
        env["jax"] = None
    try:
        from repro.core.wal import CRC32C_IMPL
        env["crc32c"] = CRC32C_IMPL
    except ImportError:  # pragma: no cover
        env["crc32c"] = None
    return env


def _run_hotpath_subprocess(root: str, n_rows: int) -> list:
    """One hotpath+workflow pass of the tree at ``root`` (its own
    benchmarks/ and src/), returning the raw result rows."""
    tmp = tempfile.mkdtemp(prefix="bench_ab_")
    try:
        out = os.path.join(tmp, "rows.json")
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + ((os.pathsep + env["PYTHONPATH"])
                                     if env.get("PYTHONPATH") else "")
        subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--hotpath-only",
             "--rows", str(n_rows), "--json", out],
            cwd=root, env=env, check=True, stdout=subprocess.DEVNULL)
        with open(out) as f:
            return json.load(f)["results"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _min_fold(acc, rows):
    if acc is None:
        return rows
    by_key = {(r["op"], r["change"]): r for r in acc}
    for r2 in rows:
        r = by_key.get((r2["op"], r2["change"]))
        if r is None:
            acc.append(r2)
            continue
        for m in _row_metrics(r) + ("diff_warm_avg_s",):
            if m in r and m in r2:
                r[m] = min(r[m], r2[m])
    return acc


def _run_interleaved(baseline_root: str, n_rows: int, repeat: int):
    """Alternate baseline/current per repeat, min-folding each side."""
    old_rows = new_rows = None
    for rep in range(repeat):
        print(f"# interleave {rep + 1}/{repeat}: baseline "
              f"({baseline_root})", flush=True)
        old_rows = _min_fold(old_rows,
                             _run_hotpath_subprocess(baseline_root, n_rows))
        print(f"# interleave {rep + 1}/{repeat}: current", flush=True)
        new_rows = _min_fold(new_rows, _run_hotpath_subprocess(".", n_rows))
    old_by_key = {(r["op"], r["change"]): r for r in old_rows}
    for r in new_rows:
        old = old_by_key.get((r["op"], r["change"]))
        line = f"A/B {r['op']}/{r['change']}:"
        for m in _row_metrics(r):
            if old is None or m not in old or m not in r:
                continue
            ratio = old[m] / r[m] if r[m] > 0 else float("inf")
            line += (f" {m[:-2]} {old[m]*1e3:.1f}->{r[m]*1e3:.1f}ms"
                     f" ({ratio:.2f}x)")
        print(line, flush=True)
    return old_rows, new_rows


def _fold_hotpath_trajectory(prev_path, n_rows, rows, note):
    """Fold a fresh hotpath/workflow run into the committed before/after
    shape.

    ``before`` comes from the previous BENCH json — its ``after`` block when
    it is itself a trajectory file, its raw metrics otherwise — so each PR's
    committed file always compares against the immediately preceding engine
    (ROADMAP: keep ``BENCH_vcs.json`` monotone). Rows the previous file
    lacks (a freshly added scenario) enter as raw metrics and seed the next
    PR's ``before``."""
    with open(prev_path) as f:
        prev = json.load(f)
    prev_by_key = {}
    for r in prev.get("results", []):
        op = r.get("op") or f"HotDiffMerge{r['mode']}"
        src = r.get("after", r)
        prev_by_key[(op, r["change"])] = {
            m: src[m] for m in _row_metrics(op) if m in src}
    results = []
    for r in rows:
        metrics = _row_metrics(r)
        before = prev_by_key.get((r["op"], r["change"]))
        after = {m: r[m] for m in metrics}
        entry = {"op": r["op"], "change": r["change"], "rows": r["rows"],
                 "changed_rows": r["changed_rows"]}
        if before:
            entry["before"] = before
            entry["after"] = after
            for m in metrics:
                if m in before and after[m] > 0:
                    entry[f"speedup_{m[:-2]}"] = round(before[m] / after[m], 2)
        else:
            entry.update(after)
        if "counters" in r:
            # per-case registry snapshot (ISSUE 8) — carried verbatim;
            # _min_fold only folds the timing metrics above
            entry["counters"] = r["counters"]
        results.append(entry)
    out = {"bench": "diff_merge_hotpath", "rows": n_rows,
           "env": _environment(),
           "change_sets": {r["change"]: r["changed_rows"] for r in rows},
           "results": results}
    if note:
        out["note"] = note
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=None,
                    help="base table rows (default 2M; --quick = 200k)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-collab", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (e.g. BENCH_vcs.json)")
    ap.add_argument("--hotpath-only", action="store_true",
                    help="run only the visibility hot-path benchmark")
    ap.add_argument("--compare-to", default=None, metavar="PATH",
                    help="previous hotpath BENCH json: fold the fresh run "
                         "into the before/after trajectory structure "
                         "(before = previous file's after/raw numbers)")
    ap.add_argument("--note", default=None,
                    help="free-form note stored in the --compare-to output")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="hotpath only: run N times and keep the per-case "
                         "minimum of each timing (robust against noisy "
                         "shared-tenancy machines)")
    ap.add_argument("--interleave", default=None, metavar="BASELINE_ROOT",
                    help="hotpath only: A/B mode — each repeat runs the "
                         "baseline checkout at BASELINE_ROOT and then this "
                         "tree, back to back in subprocesses (min-fold per "
                         "side). --json folds the result as before=baseline "
                         "mins, after=current mins. This is the required "
                         "protocol for perf claims on this noisy container.")
    args = ap.parse_args()
    n_rows = args.rows or (200_000 if args.quick else 2_000_000)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    if args.interleave:
        # the children run the cells: this parent stays off the JAX
        # backend, which would otherwise hold the chip they need
        if not args.hotpath_only:
            ap.error("--interleave requires --hotpath-only")
        old_rows, rows = _run_interleaved(args.interleave, n_rows,
                                          args.repeat)
        if args.json:
            tf = tempfile.NamedTemporaryFile(
                "w", suffix=".json", delete=False)
            try:
                json.dump({"results": old_rows}, tf)
                tf.close()
                payload = _fold_hotpath_trajectory(tf.name, n_rows, rows,
                                                   args.note)
            finally:
                os.unlink(tf.name)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
        return

    from . import vcs_tables as V
    from repro.kernels import ops as _ops
    # force one-time jax backend init OUTSIDE the timed cells: without
    # JAX_PLATFORMS pinned, the first lazy jax.default_backend() pays
    # TPU-plugin probing (hundreds of ms) inside whatever cell hits it
    _ops.backend_uses_pallas()

    if args.hotpath_only:
        run_once = lambda: (V.diff_merge_hotpath(n_rows)
                            + V.workflow_scenario(n_rows)
                            + V.probe_scenario(n_rows)
                            + V.coldstore_scenario(n_rows))
        rows = run_once()
        for rep in range(args.repeat - 1):
            print(f"# repeat {rep + 2}/{args.repeat} (min-fold)")
            rows = _min_fold(rows, run_once())
        for r in rows:
            if r["op"].startswith("Probe"):
                c = r.get("counters", {})
                print(f"probe/{r['op']}/{r['change']}: "
                      f"{r['probe_s']*1e3:.1f}ms for {r['changed_rows']} "
                      f"queries (probe.queries={c.get('probe.queries', 0)} "
                      f"hits={c.get('probe.hits', 0)})")
                continue
            if r["op"].startswith("Coldstore"):
                c = r.get("counters", {})
                print(f"coldstore/{r['op']}/{r['change']}: "
                      f"spill {r['spill_s']*1e3:.1f}ms "
                      f"evict {r['evict_s']*1e3:.1f}ms "
                      f"diff fault {r['diff_fault_s']*1e3:.1f}ms "
                      f"warm {r['diff_warm_s']*1e3:.1f}ms "
                      f"merge fault {r['merge_fault_s']*1e3:.1f}ms "
                      f"(store.faults={c.get('store.faults', 0)} "
                      f"spills={c.get('store.spills', 0)})")
                continue
            if r["op"].startswith("Workflow"):
                print(f"workflow/{r['op']}/{r['change']}: "
                      f"branch {r['branch_s']*1e3:.1f}ms "
                      f"diff {r['pr_diff_s']*1e3:.1f}ms "
                      f"publish {r['publish_s']*1e3:.1f}ms "
                      f"revert {r['revert_s']*1e3:.1f}ms")
                continue
            print(f"hotpath/{r['op']}/{r['change']}: "
                  f"diff cold {r['diff_cold_s']*1e3:.1f}ms "
                  f"warm {r['diff_warm_s']*1e3:.1f}ms "
                  f"merge {r['merge_s']*1e3:.1f}ms "
                  f"builds c/w/m={r['visibility_builds_cold']}"
                  f"/{r['visibility_builds_warm']}"
                  f"/{r['visibility_builds_merge']}")
        if args.json:
            payload = {"bench": "diff_merge_hotpath", "rows": n_rows,
                       "env": _environment(), "results": rows}
            if args.compare_to:
                payload = _fold_hotpath_trajectory(
                    args.compare_to, n_rows, rows, args.note)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
        return

    json_out = {"rows": n_rows, "env": _environment(), "sections": {}}
    print("name,us_per_call,derived")

    # ---- Table 1: clone vs insert
    t1 = V.table1_clone(n_rows)
    json_out["sections"]["table1"] = t1
    for r in t1:
        print(f"table1/{r['op']},{r['time_s']*1e6:.0f},"
              f"space_bytes={r['space_bytes']}")
    sys.stdout.flush()

    # ---- Tables 2/3: diff + merge, builtin vs SQL
    t23 = V.table23_diff_merge(n_rows)
    json_out["sections"]["table23"] = t23
    for r in t23:
        kind = "table2" if r["op"].startswith("Diff") else "table3"
        print(f"{kind}/{r['op']}/{r['change']}/builtin,"
              f"{r['builtin_s']*1e6:.0f},speedup="
              f"{r['sql_s']/max(r['builtin_s'],1e-9):.1f}x")
        print(f"{kind}/{r['op']}/{r['change']}/sql,{r['sql_s']*1e6:.0f},")
    sys.stdout.flush()

    # ---- visibility hot path (ISSUE 1): cold vs warm diffs + counters
    hp = V.diff_merge_hotpath(n_rows)
    json_out["sections"]["hotpath"] = hp
    for r in hp:
        print(f"hotpath/{r['op']}/{r['change']}/diff_warm,"
              f"{r['diff_warm_s']*1e6:.0f},"
              f"cold_us={r['diff_cold_s']*1e6:.0f};"
              f"builds_warm={r['visibility_builds_warm']}")
    sys.stdout.flush()

    # ---- workflow porcelain (ISSUE 3): branch -> PR -> publish -> revert
    wf = V.workflow_scenario(n_rows)
    json_out["sections"]["workflow"] = wf
    for r in wf:
        print(f"workflow/{r['op']}/{r['change']}/publish,"
              f"{r['publish_s']*1e6:.0f},"
              f"branch_us={r['branch_s']*1e6:.0f};"
              f"diff_us={r['pr_diff_s']*1e6:.0f};"
              f"revert_us={r['revert_s']*1e6:.0f}")
    sys.stdout.flush()

    if not args.skip_collab:
        # ---- Tables 4/5: collaborative, no conflicts
        t45 = V.collaborative(n_rows, overlap=0.0)
        json_out["sections"]["table45"] = t45
        for r in t45:
            print(f"table45/{r['op']}/{r['change']}/diff,"
                  f"{r['diff_avg_s']*1e6:.0f},")
            print(f"table45/{r['op']}/{r['change']}/merge,"
                  f"{r['merge_avg_s']*1e6:.0f},"
                  f"timeline={'|'.join(str(t) for t in r['merge_times'])}")
        sys.stdout.flush()
        # ---- Tables 6/7: collaborative, 10% overlap conflicts
        t67 = V.collaborative(n_rows, overlap=0.10)
        json_out["sections"]["table67"] = t67
        for r in t67:
            print(f"table67/{r['op']}/{r['change']}/diff,"
                  f"{r['diff_avg_s']*1e6:.0f},conflicts={r['true_conflicts']}")
            print(f"table67/{r['op']}/{r['change']}/merge,"
                  f"{r['merge_avg_s']*1e6:.0f},"
                  f"timeline={'|'.join(str(t) for t in r['merge_times'])}")
        sys.stdout.flush()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_out, f, indent=1)

    # ---- Roofline table (from dry-run artifacts, if present)
    from . import roofline
    print()
    roofline.render("dryrun_results.json")


if __name__ == '__main__':
    main()
