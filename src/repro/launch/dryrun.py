import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.  The
run is pinned to the CPU platform in-process (``jax_platforms`` below), so
on a TPU host it never takes the chip from another process.

For each cell we build abstract params/optimizer/batch/cache trees
(``jax.eval_shape`` — nothing is allocated), attach the production
shardings, ``jit(...).lower(...).compile()`` the step, and record:

* ``memory_analysis()``  — per-device bytes (proves the config fits);
* ``cost_analysis()``    — HLO FLOPs / bytes for §Roofline;
* collective op census + bytes parsed from the partitioned HLO;
* MODEL_FLOPS (6·N_active·D or 2·N_active·D) for the usefulness ratio.

Artifacts go to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` and are
skipped when already present (incremental; delete to re-run).

Usage:
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh multi
"""

import argparse
import gc
import json
import time
import traceback

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from repro import models
from repro.configs.registry import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_stats
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import sharding as shd_env

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")


def _memory_dict(mem) -> dict:
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        if hasattr(mem, attr):
            try:
                out[attr] = int(getattr(mem, attr))
            except Exception:  # noqa: BLE001
                pass
    if not out:
        out["repr"] = str(mem)
    return out


def _cost_dict(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    keep = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
    return {
        k: float(v)
        for k, v in dict(cost).items()
        if isinstance(v, (int, float)) and k in keep
    }


def run_gpc_cell(multi_pod: bool, outdir: str, force: bool = False,
                 replicate_x: bool = False) -> dict:
    """The paper's own workload (GPC def-CG iteration at n=2^20) as a cell."""
    from repro.configs.gpc_mnist import CONFIG as GPC
    from repro.launch import gpc_dryrun

    mesh_name = "multi" if multi_pod else "single"
    variant = "newton_1m_optx" if replicate_x else "newton_1m"
    tag = f"gpc-mnist__{variant}__{mesh_name}"
    path = os.path.join(outdir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    record = {
        "arch": "gpc-mnist", "shape": variant, "mesh": mesh_name,
        "chips": 512 if multi_pod else 256, "status": "pending",
        "note": "one def-CG(8) iteration; scale by measured iteration counts",
    }
    try:
        t0 = time.time()
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
        lowered = gpc_dryrun.lower_cell(GPC, mesh, replicate_x=replicate_x)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        record["lower_s"] = round(t_lower, 2)
        record["compile_s"] = round(time.time() - t0, 2)
        record["memory"] = _memory_dict(compiled.memory_analysis())
        record["cost"] = _cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        analysis = hlo_stats.analyze(hlo)
        record["hlo_flops_per_device"] = analysis["flops"]
        record["hlo_traffic_bytes_per_device"] = analysis["traffic_bytes"]
        record["collectives"] = analysis["collectives"]
        record["top_collectives"] = analysis["top_collectives"]
        record["while_trips"] = analysis["while_trips"]
        record["op_census"] = hlo_stats.op_census(hlo)
        record["model_flops"] = gpc_dryrun.model_flops(GPC)
        record["status"] = "ok"
        del hlo, compiled, lowered
    except Exception as exc:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(exc).__name__}: {exc}"
        record["traceback"] = traceback.format_exc()[-4000:]
    finally:
        gc.collect()
    _write(path, record)
    return record


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             force: bool = False) -> dict:
    if arch == "gpc-mnist":
        return run_gpc_cell(multi_pod, outdir, force)
    if arch == "gpc-mnist-optx":
        return run_gpc_cell(multi_pod, outdir, force, replicate_x=True)
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}".replace("/", "_")
    path = os.path.join(outdir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": 512 if multi_pod else 256, "status": "pending",
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=why)
        _write(path, record)
        return record

    try:
        t0 = time.time()
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
        dp = 1
        for a, s in zip(mesh.axis_names, mesh.devices.shape):
            if a in ("pod", "data"):
                dp *= s
        tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        batch_ok = shape.global_batch % dp == 0
        env = mesh_lib.axis_env_for(mesh, batch_shardable=batch_ok)
        shd_env.set_axis_env(env)

        key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_s = jax.eval_shape(
            lambda k: models.init(k, cfg, tp=tp), key_s
        )
        p_shard = mesh_lib.param_shardings(mesh, params_s, env)
        batch_s = steps_lib.input_specs(cfg, shape)
        b_shard = mesh_lib.batch_shardings(mesh, batch_s, env)

        moment_dtype = jnp.bfloat16 if cfg.total_params() > 1e11 else jnp.float32
        record["moment_dtype"] = str(jnp.dtype(moment_dtype))

        with jax.set_mesh(mesh):
            if shape.kind == "train":
                opt_s = jax.eval_shape(
                    lambda p: steps_lib.init_opt_state(p, moment_dtype),
                    params_s,
                )
                opt_shard = type(opt_s)(
                    mu=p_shard, nu=p_shard,
                    count=mesh_lib.replicated(mesh),
                )
                step = steps_lib.make_train_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_shard, opt_shard, b_shard),
                    out_shardings=(p_shard, opt_shard, None),
                    donate_argnums=(0, 1),
                )
                lowered = jitted.lower(params_s, opt_s, batch_s)
            elif shape.kind == "prefill":
                state_s = jax.eval_shape(
                    lambda: models.init_decode_state(
                        cfg, shape.global_batch, max_len=shape.seq_len
                    )
                )
                st_shard = mesh_lib.decode_state_shardings(mesh, state_s, env)
                step = steps_lib.make_prefill_step(cfg, shape.seq_len)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_shard, b_shard, st_shard),
                    out_shardings=None,
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(params_s, batch_s, state_s)
            else:  # decode
                if cfg.is_encdec:
                    _, build_state = steps_lib.decode_state_specs(cfg, shape)
                    state_s = jax.eval_shape(build_state, params_s)
                else:
                    state_s, _ = steps_lib.decode_state_specs(cfg, shape)
                st_shard = mesh_lib.decode_state_shardings(mesh, state_s, env)
                step = steps_lib.make_serve_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_shard, b_shard["tokens"], st_shard),
                    out_shardings=None,
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(
                    params_s, batch_s["tokens"], state_s
                )
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        record["lower_s"] = round(t_lower, 2)
        record["compile_s"] = round(t_compile, 2)
        record["memory"] = _memory_dict(compiled.memory_analysis())
        record["cost"] = _cost_dict(compiled.cost_analysis())

        hlo = compiled.as_text()
        analysis = hlo_stats.analyze(hlo)
        # per-device, while-trip-corrected (see hlo_stats docstring)
        record["hlo_flops_per_device"] = analysis["flops"]
        record["hlo_traffic_bytes_per_device"] = analysis["traffic_bytes"]
        record["collectives"] = analysis["collectives"]
        record["top_collectives"] = analysis["top_collectives"]
        record["while_trips"] = analysis["while_trips"]
        record["op_census"] = hlo_stats.op_census(hlo)
        record["hlo_bytes"] = len(hlo)
        del hlo, analysis, compiled, lowered, jitted

        record["model_flops"] = steps_lib.model_flops(cfg, shape)
        record["active_params"] = cfg.active_params()
        record["total_params"] = cfg.total_params()
        record["status"] = "ok"
    except Exception as exc:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(exc).__name__}: {exc}"
        record["traceback"] = traceback.format_exc()[-4000:]
    finally:
        shd_env.set_axis_env(None)
        gc.collect()

    _write(path, record)
    return record


def _write(path: str, record: dict):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--outdir", default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args()

    archs = (
        list(ARCH_IDS) + ["gpc-mnist"]
        if (args.all or args.arch is None)
        else [args.arch]
    )
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = (
        [False, True] if args.mesh == "both"
        else [args.mesh == "multi"]
    )

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, args.outdir, args.force)
                line = (
                    f"{rec['arch']:24s} {rec['shape']:12s} "
                    f"{rec['mesh']:6s} {rec['status']:7s}"
                )
                if rec["status"] == "ok":
                    line += (
                        f" flops={rec['cost'].get('flops', 0):.3e}"
                        f" compile={rec.get('compile_s', 0):.0f}s"
                    )
                elif rec["status"] == "error":
                    n_fail += 1
                    line += " " + rec.get("error", "")[:120]
                print(line, flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
