#!/usr/bin/env python3
"""Run the data-VCS main path once on a TPU and check every result.

    python chip_smoke.py             # one chip: phases a, b and c
    python chip_smoke.py --chips 4   # four chips: sharded vs unsharded only

One chip:
  a. Golden parity: the fixed-seed workloads of ``benchmarks/digests.py``
     (PK and NoPK) must reproduce the committed ``GOLDEN`` and
     ``GOLDEN_APPLY`` digests, so the CPU path's byte identity holds here.
  b. TPC-H lineitem at scale factor 1 (6,001,215 rows, PK) through ``Repo``:
     load and tag ``base``, branch ``dev``, update 100k random rows on dev
     and a disjoint 100k on main. The diff ``base..dev`` must equal
     ``sql_diff`` and the change set computed here in numpy. A PR with one
     check must pass it; publish must leave main equal to a numpy-built
     table; reverting the PR must restore main's content digest.
  c. Evidence that each device kernel (rowhash, probe, lower bound,
     segsum) compiled for and ran on the TPU.

Four chips: phase b's setup with a change set large enough that the Δ
stream passes the key-sharding threshold, then its diff and merge under the
automatic shard policy and again with one shard. Digests must agree; the
devices each kernel ran on are printed.

Every line but the last is a log line. The last is one JSON object naming
the device. The script exits non-zero without that line when JAX finds no
TPU, when the repository is not beside it, or when any check fails.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SF1_ROWS = 6_001_215
#: kernel name -> the attribute of ``repro.kernels.ops`` that dispatches it
KERNELS = {"rowhash": "rowhash_pallas", "probe": "probe_lanes",
           "lower_bound": "lower_bound_lanes", "segsum": "boundary_pallas"}


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


class Compiles:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) through JAX's monitoring events."""

    def __init__(self):
        self.built = 0
        self.cache_hits = 0

    def on_duration(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, compiles: Compiles):
    built, hits = compiles.built, compiles.cache_hits
    t0 = time.perf_counter()
    log(f"phase {name}")
    yield
    log(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, "
        f"compilations {compiles.built - built} "
        f"(of which {compiles.cache_hits - hits} from the persistent cache)")


class KernelLog:
    """Wraps the device entry points that ``ops`` dispatches to and records,
    per kernel, the calls, the ``interpret`` flag passed, and the devices
    holding each result."""

    def __init__(self, ops):
        self.fns = {}
        self.calls = collections.Counter()
        self.devices = collections.defaultdict(collections.Counter)
        self.interpret = collections.defaultdict(set)
        for name, attr in KERNELS.items():
            self.fns[name] = getattr(ops, attr)
            setattr(ops, attr, self._wrap(name, self.fns[name]))

    def _wrap(self, name, fn):
        def run(*args, **kw):
            out = fn(*args, **kw)
            first = out[0] if isinstance(out, tuple) else out
            self.calls[name] += 1
            for d in first.devices():
                self.devices[name][f"{d.platform}:{d.id}"] += 1
            self.interpret[name].add(bool(kw.get("interpret", False)))
            return out
        return run

    def reset(self):
        self.calls.clear()
        self.devices.clear()


def content_digest(lo, hi) -> str:
    """Order-independent digest over full-row signatures."""
    import numpy as np
    order = np.lexsort((hi, lo))
    h = hashlib.sha256(lo[order].tobytes())
    h.update(hi[order].tobytes())
    return h.hexdigest()


def table_digest(repo, table: str) -> str:
    _, _, lo, hi = repo.table(table).scan(with_sigs=True)
    return content_digest(lo, hi)


def numpy_sigs(schema, batch, names):
    """Row or key signatures computed on the host by the numpy rowhash —
    the reference the device kernel must match."""
    from repro.core import CType, sigs
    from repro.kernels import ops
    lob = {c.name: sigs.lob_sig64(batch[c.name]) for c in schema.columns
           if c.ctype is CType.LOB and c.name in names}
    w = ops._rowhash_np(sigs.column_lanes(schema, batch, names, lob))
    return ops.pack64(w[:, 1], w[:, 0]), ops.pack64(w[:, 3], w[:, 2])


def edited(base, idx, tag: int):
    """The rows ``base[idx]`` with an updated quantity and comment."""
    import numpy as np
    rows = {k: v[idx].copy() for k, v in base.items()}
    rows["l_quantity"] = rows["l_quantity"] + float(tag)
    rows["l_comment"] = np.array([b"edit-%d-%d" % (tag, i)
                                  for i in range(idx.shape[0])], dtype=object)
    return rows


def setup(n_rows: int, changed: int, seed: int, compiles: Compiles):
    """Load lineitem, tag ``base``, branch ``dev``, and update ``changed``
    disjoint random rows on each of dev and main."""
    import numpy as np
    from repro.configs.paper_vcs import LINEITEM_SCHEMA, gen_lineitem
    from repro.core import Repo
    with phase("b.load", compiles):
        base = gen_lineitem(n_rows, seed=seed)
        repo = Repo()
        repo.create_table("lineitem", LINEITEM_SCHEMA)
        repo.insert("lineitem", base)
        repo.tag("base", "lineitem")
        log(f"  loaded {n_rows} rows in "
            f"{len(repo.table('lineitem').directory.data_oids)} objects")
    with phase("b.branch_update", compiles):
        repo.branch("dev", ["lineitem"])
        rng = np.random.default_rng([seed, changed])
        pick = rng.choice(n_rows, size=2 * changed, replace=False)
        dev_idx, main_idx = np.sort(pick[:changed]), np.sort(pick[changed:])
        repo.update_by_keys("dev/lineitem", edited(base, dev_idx, 1))
        repo.update_by_keys("lineitem", edited(base, main_idx, 2))
    return repo, base, dev_idx, main_idx


def expected_diff(base, dev_idx):
    """The diff base..dev as sorted (key_lo, key_hi, row_lo, row_hi, cnt)
    columns: each updated key loses its old row and gains its new one."""
    import numpy as np
    from repro.configs.paper_vcs import LINEITEM_SCHEMA as S
    old = {k: v[dev_idx] for k, v in base.items()}
    new = edited(base, dev_idx, 1)
    k_lo, k_hi = numpy_sigs(S, old, S.primary_key)
    o_lo, o_hi = numpy_sigs(S, old, S.names)
    n_lo, n_hi = numpy_sigs(S, new, S.names)
    m = dev_idx.shape[0]
    cols = (np.concatenate([k_lo, k_lo]), np.concatenate([k_hi, k_hi]),
            np.concatenate([o_lo, n_lo]), np.concatenate([o_hi, n_hi]),
            np.concatenate([np.full(m, -1), np.full(m, 1)]))
    return sorted_cols(*cols)


def sorted_cols(*cols):
    import numpy as np
    order = np.lexsort(cols[::-1])
    return tuple(np.asarray(c)[order].astype(np.int64 if i == 4 else
                                             np.uint64)
                 for i, c in enumerate(cols))


def workflow(n_rows: int, changed: int, seed: int, compiles: Compiles):
    """Phase b. Returns the digests it checked, for comparison across
    backends."""
    import numpy as np
    from benchmarks.digests import diff_digest
    from repro.configs.paper_vcs import LINEITEM_SCHEMA as S
    from repro.core import sql_diff
    repo, base, dev_idx, main_idx = setup(n_rows, changed, seed, compiles)
    out = {}
    with phase("b.diff", compiles):
        d = repo.diff("base", "dev", table="lineitem")
        ds = sql_diff(repo.engine.store, repo.resolve("base").snapshot,
                      repo.resolve("dev", table="lineitem").snapshot)
        out["diff"] = diff_digest(d)
        log(f"  {d.n_groups} diff groups")
        require(out["diff"] == diff_digest(ds), "snapshot_diff == sql_diff")
        got = sorted_cols(d.key_lo, d.key_hi, d.row_lo, d.row_hi, d.diff_cnt)
        want = expected_diff(base, dev_idx)
        require(all(np.array_equal(g, w) for g, w in zip(got, want)),
                f"diff == the {changed} changed keys computed in numpy")
    with phase("b.expected_tables", compiles):
        lo, hi = numpy_sigs(S, base, S.names)
        for idx, tag in ((main_idx, 2), (dev_idx, 1)):
            lo[idx], hi[idx] = numpy_sigs(S, edited(base, idx, tag), S.names)
        after_publish = content_digest(lo, hi)
        lo[dev_idx], hi[dev_idx] = numpy_sigs(
            S, {k: v[dev_idx] for k, v in base.items()}, S.names)
        before_publish = content_digest(lo, hi)
    with phase("b.pr_check", compiles):
        pre = table_digest(repo, "lineitem")
        require(pre == before_publish,
                "main before publish == numpy-built table")
        pr = repo.open_pr("dev")
        pr.add_check(lambda ctx: ctx.count("lineitem") == n_rows,
                     "row-count")
        results = repo.check(pr.id)
        log(f"  check results: {results}")
        require(len(results) == 1 and results[0].ok and not results[0].error,
                "the PR's one check passed")
    with phase("b.publish", compiles):
        rep = repo.publish(pr.id)["lineitem"]
        log(f"  publish: inserted {rep.inserted}, deleted {rep.deleted}, "
            f"true conflicts {rep.true_conflicts}")
        require((rep.inserted, rep.deleted, rep.true_conflicts)
                == (changed, changed, 0), "publish applied dev's change set")
        out["publish"] = table_digest(repo, "lineitem")
        require(out["publish"] == after_publish,
                "main after publish == numpy-built table")
    with phase("b.revert", compiles):
        repo.revert_pr(pr.id)
        out["revert"] = table_digest(repo, "lineitem")
        require(out["revert"] == pre, "main after revert == before publish")
    return out


def golden(compiles: Compiles) -> None:
    """Phase a."""
    from benchmarks.digests import (GOLDEN, GOLDEN_APPLY, run_apply_workload,
                                    run_workload)
    for pk in (True, False):
        kind = "PK" if pk else "NoPK"
        with phase(f"a.golden_{kind}", compiles):
            got = run_workload(pk)
            require(got == GOLDEN[pk], f"GOLDEN[{kind}] {got}")
        with phase(f"a.golden_apply_{kind}", compiles):
            got = run_apply_workload(pk)
            require(got == GOLDEN_APPLY[pk], f"GOLDEN_APPLY[{kind}] {got}")


def kernel_evidence(kernels: KernelLog, platform: str) -> None:
    """Phase c."""
    missing = []
    for name, attr in KERNELS.items():
        fn = kernels.fns[name]
        log(f"  {name} ({attr}): {kernels.calls[name]} calls since phase b, "
            f"results on {dict(kernels.devices[name])}, "
            f"interpret={sorted(kernels.interpret[name])}, "
            f"{fn._cache_size()} compiled programs")
        if not kernels.calls[name]:
            missing.append(name)
        elif any(not d.startswith(platform + ":")
                 for d in kernels.devices[name]):
            raise SmokeFailure(f"{name} ran off the {platform}")
        if True in kernels.interpret[name]:
            raise SmokeFailure(f"{name} ran in interpret mode")
    require(not missing, f"every kernel ran on the {platform}"
            + (f" (not reached: {missing})" if missing else ""))


def sharded_vs_unsharded(n_rows: int, changed: int, seed: int,
                         compiles: Compiles, kernels: KernelLog) -> None:
    """The four-chip path: phase b's diff and merge under the automatic
    shard policy, then again with one shard."""
    import jax
    from benchmarks.digests import diff_digest
    from repro.distributed import sharding
    repo, _, _, _ = setup(n_rows, changed, seed, compiles)
    repo.branch("main2", ["lineitem"])      # main before either merge
    store = repo.engine.store
    log(f"  key_shard_count({2 * changed}) = "
        f"{sharding.key_shard_count(2 * changed)} on "
        f"{jax.local_device_count()} local devices")
    runs = {}
    for label, forced, into in (("auto", None, "main"), ("one", 1, "main2")):
        prev = sharding.set_key_shards(forced)
        parts0 = store.metrics.counters.get("probe.shard_parts", 0)
        kernels.reset()
        try:
            for cache in (store.delta_cache, store.vis_cache):
                if cache is not None:
                    cache.clear()
            with phase(f"shards_{label}", compiles):
                d = repo.diff("base", "dev", table="lineitem")
                rep = repo.merge("dev", into)["lineitem"]
                table = "lineitem" if into == "main" else "main2/lineitem"
                runs[label] = (diff_digest(d), rep.inserted, rep.deleted,
                               rep.true_conflicts, table_digest(repo, table))
        finally:
            sharding.set_key_shards(prev)
        parts = store.metrics.counters.get("probe.shard_parts", 0) - parts0
        log(f"  {label}: digests {runs[label]}, probe.shard_parts +{parts}")
        for name in KERNELS:
            log(f"  {label}: {name} ran {kernels.calls[name]} times on "
                f"{dict(kernels.devices[name])}")
        if label == "auto":
            require(parts > 0, "the automatic policy sharded the Δ merge")
    require(runs["auto"] == runs["one"],
            "sharded and unsharded diff and merge digests are equal")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (os.path.isdir(os.path.join(ROOT, "src", "repro"))
            and os.path.isdir(os.path.join(ROOT, "benchmarks"))):
        print("chip_smoke: the repository (src/repro, benchmarks) is not "
              f"next to this script in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend {backend!r})",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind}, {len(devices)} visible, "
        f"jax {jax.__version__}")

    from repro.kernels import ops
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)
    kernels = KernelLog(ops)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            # dev's Δ of 2 x 600k rows crosses KEY_SHARD_MIN_ROWS (1Mi),
            # the size below which the policy shards nothing on any backend
            sharded_vs_unsharded(SF1_ROWS, 600_000, args.seed, compiles,
                                 kernels)
        else:
            golden(compiles)
            kernels.reset()
            workflow(SF1_ROWS, 100_000, args.seed, compiles)
            with phase("c.kernels", compiles):
                kernel_evidence(kernels, dev.platform)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    log(f"total wall {time.perf_counter() - t0:.3f} s, "
        f"compilations {compiles.built}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
