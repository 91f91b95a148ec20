import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production mesh with 512 placeholder host devices.

THE THREE LINES ABOVE MUST STAY FIRST — jax locks the device count on
first init, so the flag must be set before any other import (including
repro.*).  The platform is pinned to the CPU: the 512 host devices
describe no real hardware, and the dry-run (and the one child per cell it
spawns) must never take an accelerator another process is using.

Single-cell mode (used by the orchestrator, one subprocess per cell so a
crash or RAM spike in one compile cannot take down the sweep):

    PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
        --shape train_4k [--multi-pod] --out reports/dryrun/<cell>.json

Sweep mode:

    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        [--jobs N] [--timeout S]
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax

REPORT_DIR = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               lower_only: bool = False, kv_bits: int = -1) -> dict:
    from repro import configs
    from repro.launch import shapes as shp
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_production_mesh
    from repro.models import lm
    from repro.parallel import sharding
    from repro.roofline import analysis
    from repro.serve import prepare

    t0 = time.time()
    live, reason = shp.cell_is_live(arch, shape_name)
    if not live:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "SKIP", "reason": reason}

    cfg = configs.get_config(arch)
    if kv_bits >= 0:
        import dataclasses as _dc
        cfg = cfg.replace(quant=_dc.replace(cfg.quant, kv_bits=kv_bits))
    shape = shp.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    from repro.parallel.sharding import activation_mesh
    with mesh, activation_mesh(mesh):
        if shape.kind == "train":
            state_struct = jax.eval_shape(
                lambda: steps_lib.make_train_state(
                    lm.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg))
            batch_struct = shp.input_specs(cfg, shape_name)
            p_sh = sharding.param_shardings(state_struct["params"], cfg,
                                            mesh)
            o_sh = sharding.opt_state_shardings(state_struct["opt_state"],
                                                p_sh, cfg, mesh)
            st_sh = {"params": p_sh, "opt_state": o_sh,
                     "step": jax.sharding.NamedSharding(
                         mesh, jax.sharding.PartitionSpec())}
            b_sh = sharding.batch_shardings(batch_struct, cfg, mesh,
                                            shape.global_batch)
            step = steps_lib.make_train_step(cfg)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_struct, batch_struct)
        elif shape.kind == "prefill":
            params_struct = jax.eval_shape(
                lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
            batch_struct = shp.input_specs(cfg, shape_name)
            p_sh = sharding.param_shardings(params_struct, cfg, mesh)
            b_sh = sharding.batch_shardings(batch_struct, cfg, mesh,
                                            shape.global_batch)
            step = steps_lib.make_prefill_step(cfg, shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_struct, batch_struct)
        else:  # decode
            params_struct = jax.eval_shape(
                lambda: prepare.prepare_serving_params(
                    lm.init_params(jax.random.PRNGKey(0), cfg), cfg))
            specs = shp.input_specs(cfg, shape_name)
            caches_struct, batch_struct = specs["caches"], specs["batch"]
            p_sh = sharding.param_shardings(params_struct, cfg, mesh)
            c_sh = sharding.cache_shardings(
                caches_struct, cfg, mesh, shape.global_batch,
                sequence_parallel=(shape_name == "long_500k"))
            b_sh = sharding.batch_shardings(batch_struct, cfg, mesh,
                                            shape.global_batch)
            i_sh = jax.sharding.NamedSharding(mesh,
                                              jax.sharding.PartitionSpec())
            step = steps_lib.make_decode_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh, i_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_struct, caches_struct,
                                   batch_struct, specs["index"])

        t_lower = time.time() - t0
        if lower_only:
            return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "status": "LOWER_OK", "lower_s": round(t_lower, 1)}
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = analysis.collective_bytes(hlo)
        mflops = analysis.model_flops(cfg, shape)

    report = analysis.summarize_cell(arch, shape_name, mesh_name, chips,
                                     cost or {}, coll, mflops)
    report.update({
        "status": "OK",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": _mem_dict(mem),
        "param_count_total": cfg.param_counts()["total"],
        "param_count_active": cfg.param_counts()["active"],
    })
    return report


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_single(args):
    try:
        report = lower_cell(args.arch, args.shape, args.multi_pod,
                            lower_only=args.lower_only,
                            kv_bits=args.kv_bits)
    except Exception as e:  # structured failure for the sweep report
        report = {"arch": args.arch, "shape": args.shape,
                  "mesh": "2x16x16" if args.multi_pod else "16x16",
                  "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    out = json.dumps(report, indent=1, default=str)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(out)
    print(out)
    if report["status"] in ("OK", "LOWER_OK"):
        print(f"\n[dry-run OK] {args.arch} x {args.shape} "
              f"mesh={report['mesh']} dominant={report.get('dominant')}")
    return 0 if report["status"] in ("OK", "SKIP", "LOWER_OK") else 1


def run_all(args):
    from repro import configs
    from repro.launch import shapes as shp

    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    cells = [(a, s) for a in configs.ARCH_NAMES for s in shp.SHAPES]
    meshes = [True, False] if args.multi_pod_also else [args.multi_pod]
    jobs = []
    for mp in meshes:
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
            out = REPORT_DIR / f"{tag}.json"
            if out.exists() and not args.force:
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", str(out)]
            if mp:
                cmd.append("--multi-pod")
            jobs.append((tag, cmd))

    running, failed, done = [], [], 0
    while jobs or running:
        while jobs and len(running) < args.jobs:
            tag, cmd = jobs.pop(0)
            p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                 stderr=subprocess.PIPE)
            running.append((tag, p, time.time()))
            print(f"[start] {tag} ({len(jobs)} queued)")
        still = []
        for tag, p, t0 in running:
            rc = p.poll()
            if rc is None:
                if time.time() - t0 > args.timeout:
                    p.kill()
                    failed.append((tag, "timeout"))
                    print(f"[TIMEOUT] {tag}")
                else:
                    still.append((tag, p, t0))
            else:
                done += 1
                if rc != 0:
                    err = p.stderr.read().decode()[-500:]
                    failed.append((tag, err))
                    print(f"[FAIL rc={rc}] {tag}")
                else:
                    print(f"[done {time.time()-t0:.0f}s] {tag}")
        running = still
        time.sleep(2)
    print(f"\ncompleted={done} failed={len(failed)}")
    for tag, err in failed:
        print(f"  FAILED {tag}: {err[:200]}")
    return 1 if failed else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-also", action="store_true",
                    help="sweep both meshes (with --all)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--timeout", type=float, default=3600)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--kv-bits", type=int, default=-1,
                    help="override cfg.quant.kv_bits (hillclimb knob)")
    ap.add_argument("--out")
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    sys.exit(run_single(args))


if __name__ == "__main__":
    main()
