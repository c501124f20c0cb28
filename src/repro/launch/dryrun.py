import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, with NO allocation (ShapeDtypeStruct inputs).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Per pair it records: memory_analysis (bytes/device), cost_analysis
(FLOPs, bytes), the collective schedule (bytes per collective kind,
parsed from the optimized HLO), and the three roofline terms
(EXPERIMENTS.md §Roofline).  For train shapes the Parle inner step and
the Parle sync step are lowered as SEPARATE programs — the sync's
collective bytes amortize over L=25 inner steps, which is the paper's
communication claim in compiled-HLO terms.

The XLA_FLAGS line above MUST execute before any jax import: jax locks
the device count at first init.  512 host devices back the 2x16x16 mesh.
"""
import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ParleConfig, get_config
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.launch import steps as steps_lib
from repro.sharding import partition

# ------------------------------------------------------------------
# TPU v5e hardware model (per chip)
# ------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

# HLO collective accounting lives in launch/hlo_stats.py (import-safe:
# no device-state side effects); re-exported here for back-compat.
from repro.launch.hlo_stats import (COLLECTIVE_OPS, _type_bytes,  # noqa: F401
                                    collective_bytes)


# ------------------------------------------------------------------
# Program builders per input-shape kind
# ------------------------------------------------------------------

# perf-iteration knobs (EXPERIMENTS.md §Perf); set via CLI
OPTIONS = {"policy": "fsdp_tp", "remat": True, "moe_groups": 0}


def build_train_programs(cfg, mesh, shape_info, param_dtype=jnp.bfloat16):
    """Returns [(tag, jitted, example_args)] for the Parle training path."""
    replica_axis = mesh_lib.replica_axis_of(mesh)
    n = mesh.shape[replica_axis] if replica_axis else 1
    gb = shape_info["global_batch"]
    per_replica = gb // n
    pcfg = ParleConfig(n_replicas=n, lr=0.1, lr_inner=0.1)

    inner, sync = steps_lib.make_parle_steps(
        cfg, pcfg, weight_decay=5e-4, remat=OPTIONS["remat"])

    state_sds = specs_lib.parle_state_shapes(cfg, pcfg, param_dtype)
    p_sds = specs_lib.param_shapes(cfg, param_dtype)
    state_ps = specs_lib.parle_state_pspecs(cfg, p_sds, replica_axis,
                                            policy=OPTIONS["policy"])
    state_ps = partition.sanitize_pspecs(state_ps, state_sds, mesh)
    state_sh = specs_lib.to_shardings(mesh, state_ps)

    batch_sds = specs_lib.train_batch_specs(
        cfg, shape_info["seq_len"], per_replica, n, param_dtype)
    baxes = ("data", "model") if OPTIONS["policy"] == "dp_only" else ("data",)
    batch_ps = specs_lib.batch_pspec_tree(batch_sds, mesh, replica_axis, True,
                                          batch_axes=baxes)
    batch_sh = specs_lib.to_shardings(mesh, batch_ps)

    inner_jit = jax.jit(inner, in_shardings=(state_sh, batch_sh),
                        out_shardings=(state_sh, None))
    sync_jit = jax.jit(sync, in_shardings=(state_sh,), out_shardings=state_sh)
    return [("train_inner", inner_jit, (state_sds, batch_sds)),
            ("parle_sync", sync_jit, (state_sds,))]


def build_prefill_program(cfg, mesh, shape_info, param_dtype=jnp.bfloat16):
    gb, T = shape_info["global_batch"], shape_info["seq_len"]
    prefill = steps_lib.make_prefill_step(cfg)
    p_sds = specs_lib.param_shapes(cfg, param_dtype)
    p_ps = partition.sanitize_pspecs(
        partition.param_pspecs(p_sds, policy=OPTIONS["policy"]), p_sds, mesh)
    p_sh = specs_lib.to_shardings(mesh, p_ps)

    batch_sds = specs_lib.prefill_batch_specs(cfg, T, gb, param_dtype)
    batch_ps = specs_lib.batch_pspec_tree(batch_sds, mesh, None, False)
    batch_sh = specs_lib.to_shardings(mesh, batch_ps)

    cache_sds = specs_lib.cache_shapes(cfg, gb, T, param_dtype)
    cache_ps = specs_lib.cache_pspecs(cfg, cache_sds, mesh)
    cache_ps = partition.sanitize_pspecs(cache_ps, cache_sds, mesh)
    cache_sh = specs_lib.to_shardings(mesh, cache_ps)

    jitted = jax.jit(prefill, in_shardings=(p_sh, batch_sh, cache_sh),
                     out_shardings=(None, cache_sh))
    return [("prefill", jitted, (p_sds, batch_sds, cache_sds))]


def build_decode_program(cfg, mesh, shape_info, param_dtype=jnp.bfloat16):
    gb, T = shape_info["global_batch"], shape_info["seq_len"]
    decode = steps_lib.make_decode_step(cfg)
    p_sds = specs_lib.param_shapes(cfg, param_dtype)
    p_ps = partition.sanitize_pspecs(
        partition.param_pspecs(p_sds, policy=OPTIONS["policy"]), p_sds, mesh)
    p_sh = specs_lib.to_shardings(mesh, p_ps)

    batch_sds = specs_lib.decode_batch_specs(cfg, gb)
    batch_ps = specs_lib.batch_pspec_tree(batch_sds, mesh, None, False)
    batch_sh = specs_lib.to_shardings(mesh, batch_ps)

    cache_sds = specs_lib.cache_shapes(cfg, gb, T, param_dtype)
    cache_ps = specs_lib.cache_pspecs(cfg, cache_sds, mesh)
    cache_ps = partition.sanitize_pspecs(cache_ps, cache_sds, mesh)
    cache_sh = specs_lib.to_shardings(mesh, cache_ps)

    # decode returns (next_token_array, cache) — not the batch dict
    jitted = jax.jit(decode, in_shardings=(p_sh, batch_sh, cache_sh),
                     out_shardings=(batch_sh["tokens"], cache_sh))
    return [("decode", jitted, (p_sds, batch_sds, cache_sds))]


def build_programs(cfg, mesh, shape_name: str):
    info = specs_lib.INPUT_SHAPES[shape_name]
    cfg = specs_lib.adapt_for_shape(cfg, shape_name)
    if info["kind"] == "train":
        return build_train_programs(cfg, mesh, info)
    if info["kind"] == "prefill":
        return build_prefill_program(cfg, mesh, info)
    return build_decode_program(cfg, mesh, info)


# ------------------------------------------------------------------
# Roofline terms
# ------------------------------------------------------------------

def roofline_terms(cost, coll_total_bytes, num_chips):
    """cost_analysis (and the partitioned HLO the collectives are parsed
    from) is PER-DEVICE after SPMD partitioning (calibrated against a
    known matmul — see EXPERIMENTS.md §Dry-run), so each term divides by
    one chip's capability; equivalently total/(chips * peak)."""
    flops = cost.get("flops", 0.0)
    byac = cost.get("bytes accessed", 0.0)
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": byac / HBM_BW,
        "collective_s": coll_total_bytes / ICI_BW,
    }


def model_flops(cfg, shape_info, kind: str, n_replicas: int = 1) -> float:
    """Analytic MODEL_FLOPS = 6*N*D (train) / 2*N*D (fwd-only), using
    active params for MoE.  Total across devices."""
    n_active = cfg.active_params()
    gb, T = shape_info["global_batch"], shape_info["seq_len"]
    if kind == "train":
        tokens = gb * T          # global batch is split across replicas
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * gb * T
    return 2.0 * n_active * gb   # decode: one token per sequence


def analyze_one(tag, jitted, args, num_chips, mflops=0.0):
    t0 = time.time()
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    cost = dict(cost) if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    terms = roofline_terms(cost, coll["total_bytes"], num_chips)
    dom = max(terms, key=terms.get)
    flops_dev = cost.get("flops", 0.0)
    rec = {
        "program": tag,
        "compile_s": round(compile_s, 1),
        "flops_per_device": flops_dev,
        "flops_total": flops_dev * num_chips,
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        "model_flops": mflops,
        "model_flops_ratio": (mflops / (flops_dev * num_chips))
                             if flops_dev else None,
        "collectives": coll,
        "roofline": terms,
        "dominant": dom,
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    return rec


# archs whose fully-unrolled HLO exceeds this container's compile budget
# (126 layers x d_model 16384): their single-pod roofline is DEPTH-
# EXTRAPOLATED — lower at L0 and 2*L0 fully unrolled, take the per-layer
# delta (depth-independent parts cancel exactly), scale to the real L.
# arch -> L0.  Hybrid uses L0 = attn_every so each extrapolation unit
# carries exactly one shared-attention site.
EXTRAPOLATED_ARCHS = {
    "llama3-405b": 2,            # 126L x d16384
    "llama4-scout-17b-a16e": 2,  # 48L MoE: unrolled HLO too big
    "qwen1.5-32b": 2,            # 64L x d5120 MHA
    "musicgen-large": 2,         # 48L: >15 min unrolled compile
    "qwen2-moe-a2.7b": 2,        # 24L x 60 experts
    "qwen2.5-3b": 2,             # 36L
    "zamba2-1.2b": 6,            # hybrid: one attn site per 6 SSM layers
}


def _combine_extrapolated(rec_small, rec_big, L0, L_target, num_chips):
    """corrected = f(L0) + (L - L0)/L0 * (f(2*L0) - f(L0)), per metric."""
    scale = (L_target - L0) / float(L0)
    out = []
    small = {p["program"]: p for p in rec_small}
    big = {p["program"]: p for p in rec_big}
    for tag, ps in small.items():
        pb = big[tag]
        rec = dict(ps)
        for key in ("flops_per_device", "flops_total",
                    "bytes_accessed_per_device"):
            rec[key] = ps[key] + scale * (pb[key] - ps[key])
        coll = {}
        for kind in ps["collectives"]["bytes"]:
            coll[kind] = ps["collectives"]["bytes"][kind] + scale * (
                pb["collectives"]["bytes"][kind] - ps["collectives"]["bytes"][kind])
        rec["collectives"] = {
            "bytes": coll, "total_bytes": sum(coll.values()),
            "counts": {k: ps["collectives"]["counts"][k] + int(scale * (
                pb["collectives"]["counts"][k] - ps["collectives"]["counts"][k]))
                for k in ps["collectives"]["counts"]},
        }
        rec["roofline"] = {
            "compute_s": rec["flops_per_device"] / PEAK_FLOPS,
            "memory_s": rec["bytes_accessed_per_device"] / HBM_BW,
            "collective_s": rec["collectives"]["total_bytes"] / ICI_BW,
        }
        rec["dominant"] = max(rec["roofline"], key=rec["roofline"].get)
        if rec.get("model_flops"):
            rec["model_flops_ratio"] = rec["model_flops"] / rec["flops_total"]
        rec["accounting"] = f"depth_extrapolated(L0={L0})"
        out.append(rec)
    return out


def run_pair(arch: str, shape_name: str, multi_pod: bool, verbose=True):
    # honest accounting: fully unroll layer/chunk scans at trace time so
    # HloCostAnalysis counts every iteration (see utils/scan.py).  The
    # multi-pod pass only proves lowering/compilation, so it keeps the
    # rolled (fast-compile) form; the roofline table is single-pod.
    os.environ["REPRO_SCAN_UNROLL"] = "1" if multi_pod else "full"
    os.environ.setdefault("REPRO_CHUNK_Q", "4096")   # bound unrolled-HLO size
    cfg = get_config(arch)
    if OPTIONS["moe_groups"] and cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_groups=OPTIONS["moe_groups"])
    if OPTIONS.get("moe_impl") and cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_impl=OPTIONS["moe_impl"])
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    if OPTIONS.get("moe_impl"):
        from repro.models import moe as _moe
        _moe.AMBIENT_MESH = mesh
    num_chips = mesh.size
    info = specs_lib.INPUT_SHAPES[shape_name]
    out = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "num_chips": num_chips, "programs": []}

    extrapolate = (not multi_pod) and arch in EXTRAPOLATED_ARCHS
    L0 = EXTRAPOLATED_ARCHS.get(arch, 2)
    with mesh:
        if extrapolate:
            recs = {}
            for L in (L0, 2 * L0):
                c = dataclasses.replace(cfg, num_layers=L)
                mf = model_flops(c, info, info["kind"])
                recs[L] = [
                    analyze_one(tag, jitted, args, num_chips,
                                mflops=(mf if tag != "parle_sync" else 0.0))
                    for tag, jitted, args in build_programs(c, mesh, shape_name)]
            combined = _combine_extrapolated(recs[L0], recs[2 * L0],
                                             L0, cfg.num_layers, num_chips)
            # model_flops must reflect the REAL depth
            for rec in combined:
                if rec.get("model_flops"):
                    rec["model_flops"] = model_flops(cfg, info, info["kind"])
                    rec["model_flops_ratio"] = (rec["model_flops"] /
                                                rec["flops_total"])
            out["programs"] = combined
        else:
            for tag, jitted, args in build_programs(cfg, mesh, shape_name):
                mf = model_flops(cfg, info, info["kind"]) if tag != "parle_sync" else 0.0
                rec = analyze_one(tag, jitted, args, num_chips, mflops=mf)
                out["programs"].append(rec)
        if verbose:
            for rec in out["programs"]:
                r = rec["roofline"]
                print(f"  [{out['mesh']}] {arch} x {shape_name} :: {rec['program']}: "
                      f"compute {r['compute_s']:.3e}s  mem {r['memory_s']:.3e}s  "
                      f"coll {r['collective_s']:.3e}s  -> {rec['dominant']} "
                      f"(compile {rec['compile_s']}s"
                      f"{', ' + rec['accounting'] if rec.get('accounting') else ''})",
                      flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(specs_lib.INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--policy", default="fsdp_tp",
                    choices=["fsdp_tp", "tp_only", "dp_only"],
                    help="weight sharding policy (§Perf knob)")
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"],
                    help="activation checkpoint policy (§Perf knob)")
    ap.add_argument("--tag", default="", help="suffix for result files")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip pairs whose result JSON already exists")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="GShard grouped MoE dispatch (§Perf knob)")
    ap.add_argument("--moe-impl", default="",
                    choices=["", "pjit", "shard_map"],
                    help="MoE dispatch implementation (§Perf knob)")
    args = ap.parse_args()
    OPTIONS["moe_groups"] = args.moe_groups
    OPTIONS["moe_impl"] = args.moe_impl
    OPTIONS["policy"] = args.policy
    OPTIONS["remat"] = {"full": True, "dots": "dots", "none": False}[args.remat]

    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(specs_lib.INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                if args.tag:
                    tag += f"__{args.tag}"
                if args.skip_existing and os.path.exists(
                        os.path.join(args.out, tag + ".json")):
                    print(f"  skip {tag} (exists)", flush=True)
                    continue
                try:
                    rec = run_pair(arch, shape, mp)
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    print(f"  FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                    failures.append((tag, str(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        sys.exit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
