import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512").strip()
"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and extract memory / cost / collective
figures for EXPERIMENTS.md §Dry-run and §Roofline.

MUST be imported/run before any other jax initialization — the XLA_FLAGS
assignment above is the very first statement for that reason.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np

from ..configs import LM_SHAPES, all_arch_names, cells_for, get_config
from . import steps as S
from .hlo_analysis import analyze_hlo
from .mesh import HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16, make_production_mesh

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes_from_hlo(hlo: str) -> Dict[str, float]:
    """Sum operand bytes of every collective op in the optimized HLO."""
    out: Dict[str, float] = {}
    for line in hlo.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # operand types appear inside the op's argument list
        args = line.split(m.group(0) + "(", 1)
        if len(args) < 2:
            continue
        shapes = SHAPE_RE.findall(args[1])
        total = 0
        for dt, dims in shapes:
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool,
                 sc=None, n_micro: Optional[int] = None,
                 attn_block: int = 1024, mesh=None, cfg=None,
                 opt_cfg=None) -> Dict:
    cfg = cfg or get_config(arch)
    shape = LM_SHAPES[shape_name]
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.perf_counter()  # monotonic: a wall-clock step breaks timings
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, st_specs, in_sh = S.make_train_step(
                cfg, shape, mesh, sc=sc, n_micro=n_micro,
                attn_block=attn_block, opt_cfg=opt_cfg)
            st_shape = S.abstract_state(cfg, opt_cfg or S.AdamWCfg())
            abs_in, _ = S.input_specs(cfg, shape, mesh, sc)
            lowered = step.lower(st_shape, abs_in["batch"])
        elif shape.kind == "prefill":
            step, pspecs, in_sh = S.make_prefill_step(
                cfg, shape, mesh, sc=sc, attn_block=attn_block)
            import functools
            from ..models import lm
            params_shape = jax.eval_shape(
                functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
            abs_in, _ = S.input_specs(cfg, shape, mesh, sc)
            args = [params_shape, abs_in["tokens"]]
            if "ctx" in abs_in:
                args.append(abs_in["ctx"])
            lowered = step.lower(*args)
        else:
            step, pspecs, in_sh, abs_in = S.make_decode_step(
                cfg, shape, mesh, sc=sc)
            import functools
            from ..models import lm
            params_shape = jax.eval_shape(
                functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0))
            lowered = step.lower(params_shape, abs_in["token"],
                                 abs_in["cache"])
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # jax<=0.4 returns [dict]
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    # static analysis with loop trip counts (cost_analysis counts scan
    # bodies once — see hlo_analysis.py)
    hc = analyze_hlo(hlo)
    coll = hc.coll

    flops = float(hc.flops)
    bytes_accessed = float(hc.dot_bytes)
    coll_total = float(hc.collective_bytes)

    # roofline terms (seconds); cost_analysis is per-device for SPMD modules
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_accessed / HBM_BW
    t_collective = coll_total / ICI_LINK_BW

    # MODEL_FLOPS: 6·N_active·D for train, 2·N_active·D for inference.
    # enc-dec: encoder params see the frame sequence, decoder params the
    # token sequence — count both streams.
    n_active = cfg.n_params_active()
    n_enc = cfg.n_params_encoder()
    B, sl = shape.global_batch, shape.seq_len
    mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[shape.kind]
    if shape.kind == "decode":
        model_flops = mult * (n_active - n_enc) * B
    elif cfg.is_encdec:
        model_flops = mult * ((n_active - n_enc) * B * min(448, sl)
                              + n_enc * B * sl)
    else:
        model_flops = mult * n_active * B * sl
    model_flops_per_chip = model_flops / n_chips

    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device_bytes": int(getattr(mem, "argument_size_in_bytes", 0)
                                + getattr(mem, "output_size_in_bytes", 0)
                                + getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "hlo_flops": flops,
        "hlo_flops_raw_costanalysis": float(cost.get("flops", 0.0)),
        "hlo_bytes": bytes_accessed,
        "collective_bytes": coll_total,
        "collectives": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_collective)], key=lambda kv: kv[1])[0],
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops
                               if flops else 0.0),
        "roofline_fraction": (model_flops_per_chip / PEAK_FLOPS_BF16)
        / max(t_compute, t_memory, t_collective)
        if max(t_compute, t_memory, t_collective) > 0 else 0.0,
    }
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for name in all_arch_names():
            cfg = get_config(name)
            for sh in cells_for(cfg):
                cells.append((name, sh.name))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape))
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for arch, shp in cells:
        for mp in meshes:
            meshname = "2x16x16" if mp else "16x16"
            if (arch, shp, meshname) in done:
                continue
            print(f"=== {arch} × {shp} × {meshname}", flush=True)
            try:
                r = analyze_cell(arch, shp, multi_pod=mp)
                print(json.dumps(
                    {k: r[k] for k in ("per_device_bytes", "hlo_flops",
                                       "collective_bytes", "bottleneck",
                                       "compile_s")}), flush=True)
                results.append(r)
            except Exception as e:  # a failure here is a bug in the system
                traceback.print_exc()
                results.append({"arch": arch, "shape": shp,
                                "mesh": meshname, "error": str(e)[:500]})
            json.dump(results, open(args.out, "w"), indent=1)
    n_err = sum(1 for r in results if "error" in r)
    print(f"done: {len(results)} cells, {n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
