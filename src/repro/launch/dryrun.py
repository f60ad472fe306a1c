import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module: jax locks the
# device count at first backend init.  Everything else follows.
if os.environ.get("REPRO_DRYRUN_DEVICES"):  # test hook (still pre-jax-init)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + os.environ["REPRO_DRYRUN_DEVICES"]
    )

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ASSIGNED, get_config
from ..core.fusion import GlassConfig
from ..models.api import build_model
from ..sharding.ctx import use_rules
from ..sharding.partition import Planner, _path_str
from ..train.optim import OptConfig, init_opt_state
from .hlo_cost import analyze_hlo
from .mesh import make_production_mesh
from .specs import (
    SHAPES,
    applicable_shapes,
    batch_specs,
    compact_config,
    decode_specs,
    param_specs,
    prior_spec,
)
from .steps import make_decode_step, make_glass_prefill, make_train_step

# Hardware model: TPU v5e
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
LINK_BW = 50e9  # bytes/s / link (ICI)

# per-arch training knobs (memory levers; see EXPERIMENTS.md SS Perf)
TRAIN_OVERRIDES = {
    "grok-1-314b": dict(grad_accum=16, fsdp=True),
    "dbrx-132b": dict(grad_accum=8, fsdp=True),
    "qwen2-vl-72b": dict(grad_accum=8, fsdp=True),
    "gemma2-27b": dict(grad_accum=4, fsdp=True),
    "gemma2-9b": dict(grad_accum=2, fsdp=False),
    "whisper-large-v3": dict(grad_accum=1, fsdp=False),
}
DEFAULT_TRAIN = dict(grad_accum=2, fsdp=False)

def model_flops_global(cfg, shape, kind: str, density: float | None) -> float:
    """6*N*D (train) / 2*N_active*D (inference), D = tokens processed."""
    n = cfg.n_active_params()
    if kind == "train":
        return 6.0 * n * shape.batch * shape.seq
    if kind == "prefill":
        return 2.0 * n * shape.batch * shape.seq
    dcfg = compact_config(cfg, density) if density else cfg
    return 2.0 * dcfg.n_active_params() * shape.batch


def analyze(compiled, meta: dict, n_devices: int) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    # trip-count-aware HLO walk (raw cost_analysis counts scan bodies once —
    # see hlo_cost.py; raw numbers kept for reference under "xla_raw")
    hlo = analyze_hlo(txt)
    flops_dev = float(hlo.dot_flops)
    # HBM traffic model: allocator-true buffers — every argument byte read,
    # every output written, temps written+read once each.  The instruction-
    # level sum (hlo.traffic_bytes) massively overcounts on the CPU backend
    # (its fusion boundaries differ from TPU) and is kept as a diagnostic.
    bytes_dev = float(
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + 2 * ma.temp_size_in_bytes
    )
    coll_dev = float(hlo.collective_traffic)
    colls = {
        k: {"count": hlo.collective_counts.get(k, 0), "bytes": v}
        for k, v in hlo.collective_bytes.items()
    }
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        ),
    }
    mf_global = model_flops_global(
        meta["cfg_obj"], SHAPES[meta["shape"]], meta["kind"], meta.get("density")
    )
    mf_dev = mf_global / n_devices
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / LINK_BW,
    }
    bottleneck = max(terms, key=terms.get)
    rec = {
        **{k: v for k, v in meta.items() if k != "cfg_obj"},
        "n_devices": n_devices,
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": colls,
        "memory": mem,
        "model_flops_per_device": mf_dev,
        "useful_flops_ratio": (mf_dev / flops_dev) if flops_dev else None,
        "xla_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "instr_traffic_upper_bound": float(hlo.traffic_bytes),
        },
        "roofline_terms_s": terms,
        "bottleneck": bottleneck,
        "roofline_step_s": max(terms.values()),
        "fits_hbm_16g": mem["peak_bytes"] <= 16 * 1024**3,
        # CPU-backend caveat: bf16 dot operands are converted to f32 on the
        # host backend, inflating temp buffers ~2x vs TPU (native bf16 MXU).
        # argument_bytes (resident params/cache/opt state) is conversion-free.
        "memory_caveat": "temp_bytes includes CPU-only bf16->f32 dot-operand conversions",
    }
    return rec


def _opt_shardings(planner: Planner, pshapes, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(path, leaf):
        return NamedSharding(mesh, planner.opt_spec(_path_str(path), leaf.shape))

    mu = jax.tree_util.tree_map_with_path(one, pshapes)
    import copy

    from ..train.optim import OptState

    return OptState(step=NamedSharding(mesh, P()), mu=mu, nu=jax.tree.map(lambda s: s, mu))


def lower_cell(
    arch: str,
    shape_name: str,
    mesh,
    *,
    density: float | None = 0.5,
    mode_override: dict | None = None,
):
    """Lower + compile one (arch x shape) cell on the given mesh.

    Returns (lowered, compiled, meta)."""
    cfg = get_config(arch) if isinstance(arch, str) else arch
    shape = SHAPES[shape_name]
    kind = shape.kind
    ov = dict(TRAIN_OVERRIDES.get(cfg.name, DEFAULT_TRAIN))
    if mode_override:
        ov.update(mode_override)
    meta = {
        "arch": cfg.name,
        "shape": shape_name,
        "kind": kind,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "density": density if kind != "train" else None,
        "overrides": {k: v for k, v in ov.items() if k in ("grad_accum", "fsdp")},
        "cfg_obj": cfg,
    }

    if ov.get("expert_replication"):
        cfg = cfg.replace(expert_replication=ov["expert_replication"])
    if ov.get("remat"):
        cfg = cfg.replace(remat=ov["remat"])
    meta["cfg_obj"] = cfg

    if kind == "train":
        model = build_model(cfg)
        planner = Planner(
            cfg, mesh, mode="train", fsdp=ov.get("fsdp", False), pure_dp=ov.get("pure_dp", False)
        )
        pshapes = param_specs(cfg)
        pshard = planner.params(pshapes)
        oshapes = jax.eval_shape(init_opt_state, pshapes)
        oshard = _opt_shardings(planner, pshapes, mesh)
        bshapes = batch_specs(cfg, shape)
        bshard = planner.data_shardings(bshapes)
        # grads accumulate in the optimizer-moment (ZeRO) sharding: additionally
        # data-sharded, so per-microbatch grad sync is a reduce-scatter instead
        # of a full all-reduce, and the f32 carry is 1/data_n the size.
        step = make_train_step(
            model, OptConfig(), grad_accum=ov.get("grad_accum", 1), grad_shardings=oshard.mu
        )
        rules = planner.activation_rules(shape.batch, seq_parallel=ov.get("seq_parallel", False))
        with mesh, use_rules(mesh, rules):
            lowered = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            ).lower(pshapes, oshapes, bshapes)
    elif kind == "prefill":
        model = build_model(cfg)
        planner = Planner(cfg, mesh, mode="prefill")
        model_n = mesh.shape.get("model", 1)
        pshapes = param_specs(cfg)
        pshard = planner.params(pshapes)
        bshapes = batch_specs(cfg, shape)
        bshard = planner.data_shardings(bshapes)
        gcfg = GlassConfig(density=density or 0.5, selection="shard_balanced", n_shards=model_n)
        prefill = make_glass_prefill(model, gcfg, max_len=shape.seq, mesh=mesh, model_shards=model_n)
        prshape = prior_spec(cfg)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rules = planner.activation_rules(shape.batch)
        with mesh, use_rules(mesh, rules):
            lowered = jax.jit(
                prefill,
                in_shardings=(pshard, bshard, NamedSharding(mesh, P())),
            ).lower(pshapes, bshapes, prshape)
    else:  # decode
        dcfg = compact_config(cfg, density) if density else cfg
        model = build_model(dcfg)
        planner = Planner(dcfg, mesh, mode="decode")
        specs = decode_specs(cfg, shape, density)
        pshard = planner.params(specs["params"])
        cshard = planner.cache_shardings(specs["cache"])
        from jax.sharding import NamedSharding, PartitionSpec as P

        tok_shard = NamedSharding(
            mesh, P(planner.dp if shape.batch % planner.dp_n == 0 else None, None)
        )
        step = make_decode_step(model)
        rules = planner.activation_rules(shape.batch)
        with mesh, use_rules(mesh, rules):
            lowered = jax.jit(
                step,
                in_shardings=(pshard, cshard, tok_shard, NamedSharding(mesh, P())),
                out_shardings=(tok_shard, cshard),
                donate_argnums=(1,),
            ).lower(specs["params"], specs["cache"], specs["token"], specs["cache_len"])

    compiled = lowered.compile()
    return lowered, compiled, meta


def run_cell(arch, shape_name, mesh, out_dir: Path, **kw) -> dict:
    t0 = time.time()
    lowered, compiled, meta = lower_cell(arch, shape_name, mesh, **kw)
    n_dev = int(np.prod(mesh.devices.shape))
    rec = analyze(compiled, meta, n_dev)
    rec["compile_s"] = round(time.time() - t0, 1)
    mesh_tag = "x".join(str(s) for s in mesh.devices.shape)
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = out_dir / f"{rec['arch']}__{shape_name}__{mesh_tag}.json"
    fname.write_text(json.dumps(rec, indent=1, default=str))
    mem_gb = rec["memory"]["peak_bytes"] / 1024**3
    print(
        f"[dryrun] {rec['arch']:18s} {shape_name:12s} mesh={mesh_tag:10s} "
        f"mem/dev={mem_gb:6.2f}GiB flops/dev={rec['hlo_flops_per_device']:.3e} "
        f"bottleneck={rec['bottleneck']:12s} useful={rec['useful_flops_ratio'] or 0:.2f} "
        f"compile={rec['compile_s']}s",
        flush=True,
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all assigned)")
    ap.add_argument("--shape", default=None, help="shape name (default: all applicable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--density", type=float, default=0.5)
    ap.add_argument("--dense-baseline", action="store_true", help="decode without GLASS compaction")
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = [args.arch] if args.arch else ASSIGNED
    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    density = None if args.dense_baseline else args.density
    failures = []
    for mesh in meshes:
        for arch in archs:
            cfg = get_config(arch)
            shapes = [args.shape] if args.shape else applicable_shapes(cfg)
            for shp in shapes:
                try:
                    run_cell(arch, shp, mesh, out_dir, density=density)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((arch, shp, str(e)))
                    print(f"[dryrun] FAIL {arch} {shp}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures")
        raise SystemExit(1)
    print("[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
