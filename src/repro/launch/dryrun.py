import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede every other import: jax locks the platform and the device
# count on first init. A CPU-only tool: neither this process nor the cells it
# starts as children may take a TPU.
"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on the
production mesh and extract the roofline inputs.

For each cell this produces a JSON artifact with:
  * compile/lower wall time,
  * ``compiled.memory_analysis()``  (bytes per device — proves the cell fits),
  * ``compiled.cost_analysis()``    (HLO FLOPs + bytes accessed),
  * per-collective wire bytes parsed from the partitioned HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute),
  * MODEL_FLOPS = 6*N*D (6*N_active*D for MoE) for the useful-compute ratio.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k            # one cell
  python -m repro.launch.dryrun --all --jobs 4                             # everything
  python -m repro.launch.dryrun --arch kimi... --shape train_4k --multi-pod
Variants (--rules / --grad-accum / --remat / --opt-dtype) drive the §Perf hillclimb.
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ArchConfig, ShapeSpec
from repro.dist.sharding import (
    Rules, abstract_state, make_rules, param_shardings, use_rules,
)
from repro.launch.costmodel import analytic_flops, probe_costs
from repro.launch.mesh import make_production_mesh, mesh_tag
from repro.models import build_model, input_specs
from repro.optim import AdamW, AdamWConfig
from repro.train.step import make_train_step

# ---------------------------------------------------------------------- defaults

BIG_MODEL_BYTES = 8 * 2 ** 30 * 16       # serve_tp replicates over data: cap 8GB/chip


def default_rules_preset(cfg: ArchConfig, shape: ShapeSpec) -> str:
    if shape.kind == "train":
        return "train"
    if shape.name == "long_500k":
        return "serve_seqkv"
    total_bytes = cfg.param_counts()["total"] * 2   # bf16
    return "serve_tp" if total_bytes <= BIG_MODEL_BYTES else "serve_2d"


def default_opt_dtype(cfg: ArchConfig) -> str:
    # >=398B models need quantized moments to fit 512 x 16GB (see optim/adamw.py)
    return "int8" if cfg.param_counts()["total"] > 100e9 else "float32"


def default_grad_accum(cfg: ArchConfig, shape: ShapeSpec, n_data: int) -> int:
    """Pick microbatch ~2 sequences per data shard at 4k tokens."""
    if shape.kind != "train":
        return 1
    per_shard = max(shape.global_batch // n_data, 1)
    target_micro = 2
    return max(per_shard // target_micro, 1)


# ----------------------------------------------------------- collective parsing

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device wire bytes by collective kind (ring-algorithm approximations)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([a-z0-9-]+)", line)
        if not m:
            continue
        op = m.group(2)
        base = op.removesuffix("-start")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        result_part = line.split("=", 1)[1]
        result_part = result_part.split(op, 1)[0]       # result shape(s) only
        nbytes = _shape_bytes(result_part)
        if base == "all-reduce":
            nbytes *= 2                                  # reduce-scatter + all-gather
        out[base] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ------------------------------------------------------------------- cell build

def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, rules: Rules, *,
               grad_accum: int, opt_dtype: str):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""
    model = build_model(cfg, max_seq=shape.seq_len + 1)
    specs = model.param_specs()
    p_sds = abstract_state(specs)
    p_sh = param_shardings(specs, rules, mesh)
    inputs = input_specs(cfg, shape)

    if shape.kind == "train":
        opt = AdamW(AdamWConfig(state_dtype=opt_dtype))
        o_specs = opt.state_specs(specs)
        o_sds = abstract_state(o_specs)
        o_sh = param_shardings(o_specs, rules, mesh)
        raw = make_train_step(model, opt, grad_accum=grad_accum)

        def fn(params, opt_state, batch):
            with use_rules(rules, mesh):
                return raw(params, opt_state, batch)

        args = (p_sds, o_sds, inputs)
        in_sh = (p_sh, o_sh, None)
        out_sh = (p_sh, o_sh, None)
        return fn, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        def fn(params, batch):
            with use_rules(rules, mesh):
                return model.prefill(params, batch, capacity=shape.seq_len)

        return fn, (p_sds, inputs), (p_sh, None), None, ()

    # decode: cache of depth seq_len, one new token
    c_specs = model.cache_specs(shape.global_batch, shape.seq_len)
    c_sds = abstract_state(c_specs)
    c_sh = param_shardings(c_specs, rules, mesh)

    def fn(params, cache, token):
        with use_rules(rules, mesh):
            return model.decode(params, cache, token)

    args = (p_sds, c_sds, inputs["token"])
    return fn, args, (p_sh, c_sh, None), (None, c_sh), (1,)


# -------------------------------------------------------------------- one cell

def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules_preset: Optional[str] = None, grad_accum: Optional[int] = None,
             opt_dtype: Optional[str] = None, remat: Optional[str] = None,
             variant: str = "baseline", out_dir: str = "artifacts/dryrun",
             save_hlo: bool = False, probes: bool = True,
             rule_overrides: Optional[Dict] = None) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name in cfg.skipped_shapes():
        raise SystemExit(f"cell ({arch}, {shape_name}) is assignment-skipped: "
                         f"{cfg.skipped_shapes()[shape_name]}")
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_data = mesh.devices.shape[-2]
    preset = rules_preset or default_rules_preset(cfg, shape)
    rules = make_rules(preset, mesh, **(rule_overrides or {}))
    ga = grad_accum if grad_accum is not None else default_grad_accum(cfg, shape, n_data)
    od = opt_dtype or default_opt_dtype(cfg)

    fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh, rules,
                                                 grad_accum=ga, opt_dtype=od)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    record: Dict = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": mesh_tag(mesh), "n_devices": int(mesh.devices.size),
        "rules": preset, "grad_accum": ga, "opt_dtype": od,
        "remat": cfg.remat,
        "params_total": cfg.param_counts()["total"],
        "params_active": cfg.param_counts()["active"],
    }
    with mesh:
        t0 = time.time()
        lowered = jitted.lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        record["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")
        }
        record["bytes_per_device"] = (
            record["memory"]["argument_size_in_bytes"]
            + record["memory"]["temp_size_in_bytes"]
            - record["memory"]["alias_size_in_bytes"])
        ca = compiled.cost_analysis() or {}
        record["flops_per_device"] = float(ca.get("flops", 0.0))
        record["bytes_accessed_per_device"] = float(ca.get("bytes accessed", 0.0))
        hlo = compiled.as_text()
        record["collectives"] = parse_collective_bytes(hlo)
        record["hlo_lines"] = hlo.count("\n")

    # useful-model-FLOPs: 6*N*D per token (training does fwd+bwd; serve_step fwd only)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = record["params_active"]
    factor = 6.0 if shape.kind == "train" else 2.0
    record["model_flops_global"] = factor * n_active * tokens
    record["tokens"] = tokens
    record["analytic_flops_global"] = analytic_flops(cfg, shape, grad_accum=ga)

    # ---- cost probes: unrolled reduced-depth variants -> true per-device costs
    if probes:
        def build_and_lower(pcfg, pga, micro):
            pshape = dataclasses.replace(shape, global_batch=micro * pga)
            pfn, pargs, pin_sh, pout_sh, pdonate = build_cell(
                pcfg, pshape, mesh, rules, grad_accum=pga, opt_dtype=od)
            pj = jax.jit(pfn, in_shardings=pin_sh, out_shardings=pout_sh,
                         donate_argnums=pdonate)
            with mesh:
                pc = pj.lower(*pargs).compile()
            pca = pc.cost_analysis() or {}
            return (float(pca.get("flops", 0.0)),
                    float(pca.get("bytes accessed", 0.0)),
                    parse_collective_bytes(pc.as_text()))

        t2 = time.time()
        pcost = probe_costs(build_and_lower, cfg, shape, ga)
        record["probe_s"] = round(time.time() - t2, 2)
        ext = pcost["extrapolated"]
        corr = pcost["slstm_correction"]
        ndev = record["n_devices"]
        record["costs_per_device"] = {
            "flops": ext["flops"] + corr["flops"] / ndev,
            "bytes": ext["bytes"] + corr["bytes"] / ndev,
            "collectives": {k: ext[k] for k in
                            ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute", "coll_total")},
        }
        record["probe_detail"] = pcost["probes"]

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}__{record['mesh']}__{variant}"
    (out / f"{tag}.json").write_text(json.dumps(record, indent=2))
    if save_hlo:
        (out / f"{tag}.hlo.txt").write_text(hlo)
    return record


# ----------------------------------------------------------------- orchestrator

def all_cells_cli(jobs: int, out_dir: str, multi_pod_also: bool, timeout: int) -> int:
    """Run every runnable cell in subprocesses (isolation + parallelism)."""
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape_name in cfg.shape_names():
            cells.append((arch, shape_name, False))
            if multi_pod_also:
                cells.append((arch, shape_name, True))
    procs: Dict[Tuple, subprocess.Popen] = {}
    failures = []
    done = 0
    pending = list(reversed(cells))
    t_start = time.time()
    while pending or procs:
        while pending and len(procs) < jobs:
            arch, shape_name, mp = pending.pop()
            tag = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}"
            outp = Path(out_dir)
            outp.mkdir(parents=True, exist_ok=True)
            existing = list(outp.glob(
                f"{arch}__{shape_name}__{'pod2x' if mp else 'data16x'}*__baseline.json"))
            if existing:
                done += 1
                print(f"[dryrun] skip (cached): {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape_name, "--out", out_dir]
            if mp:
                # multi-pod pass proves the 'pod' axis shards; roofline (probes)
                # is derived from the single-pod artifacts only
                cmd.extend(["--multi-pod", "--no-probes"])
            log = open(outp / f"{tag}.log", "w")
            procs[(arch, shape_name, mp)] = (subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT), time.time(), log)
            print(f"[dryrun] launch: {tag} ({len(procs)} running, "
                  f"{len(pending)} queued, {done} done, {time.time()-t_start:.0f}s)")
        time.sleep(2.0)
        for key, (p, t0, log) in list(procs.items()):
            rc = p.poll()
            if rc is None and time.time() - t0 > timeout:
                p.kill()
                rc = -9
            if rc is not None:
                log.close()
                del procs[key]
                done += 1
                if rc != 0:
                    failures.append((key, rc))
                    print(f"[dryrun] FAIL rc={rc}: {key}")
                else:
                    print(f"[dryrun] ok: {key} ({time.time()-t0:.0f}s)")
    print(f"[dryrun] finished {done} cells, {len(failures)} failures "
          f"in {time.time()-t_start:.0f}s")
    for f in failures:
        print("  FAILED:", f)
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--opt-dtype", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--moe-local", action="store_true",
                    help="per-data-shard MoE dispatch (hillclimb variant)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--multi-pod-also", action="store_true", default=True)
    args = ap.parse_args()

    if args.all:
        raise SystemExit(all_cells_cli(args.jobs, args.out, args.multi_pod_also,
                                       args.timeout))
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   rules_preset=args.rules, grad_accum=args.grad_accum,
                   opt_dtype=args.opt_dtype, remat=args.remat,
                   variant=args.variant, out_dir=args.out, save_hlo=args.save_hlo,
                   probes=not args.no_probes,
                   rule_overrides={"moe_dispatch": "local"} if args.moe_local else None)
    skip = ("memory", "probe_detail")
    print(json.dumps({k: v for k, v in rec.items() if k not in skip}, indent=2))
    print("memory:", json.dumps(rec["memory"], indent=2))


if __name__ == "__main__":
    main()
