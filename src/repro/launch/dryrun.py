"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be executed as a fresh process (``python -m repro.launch.dryrun``): the
first two lines force 512 host platform devices before jax initializes.
Smoke tests and benchmarks run in normal processes and see 1 device.

A CPU tool, never on the chip path: it fakes 512 host devices and
``--all`` starts one child process per cell after the parent has touched
JAX, and a chip belongs to one process at a time.  Run it with
``JAX_PLATFORMS=cpu``; ``chip_smoke.py`` is what runs on the chip.

Per cell this:
  1. builds the production mesh (16x16 or 2x16x16),
  2. lowers the train/prefill/serve step with abstract ShapeDtypeStruct
     inputs (zero allocation),
  3. compiles, prints memory_analysis() and cost_analysis(),
  4. parses collective bytes out of the post-SPMD HLO text,
  5. writes a JSON record for the roofline analyzer (core.analyzer).

``--all`` runs every runnable cell in subprocesses (isolation against
compiler memory growth; already-written records are skipped, so the sweep
is resumable).
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402  (env var must precede any jax import)
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, all_cells, get_config
from repro.core import hlo_analysis as H
from repro.core import hlo_flops as HF
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models import moe as MOE
from repro.train import train_step as TS

DEFAULT_OUT = "experiments/dryrun"

# Baseline per-arch training config required to fit the 16 GiB/chip v5e
# budget on the 256-chip pod (documented in EXPERIMENTS.md Section Dry-run).
# grad_accum trades step latency for activation memory; the qwen3 MoE cell
# additionally keeps AdamW moments in bf16 (235B params x fp32 triple would
# need 11 GiB/chip for optimizer state alone).
GRAD_ACCUM_DEFAULTS = {
    ("qwen2-72b", "train_4k"): 8,
    ("qwen3-moe-235b-a22b", "train_4k"): 8,
    ("gemma3-12b", "train_4k"): 4,
    ("falcon-mamba-7b", "train_4k"): 2,
    ("recurrentgemma-9b", "train_4k"): 8,
}
OPT_DTYPE_DEFAULTS = {
    "qwen3-moe-235b-a22b": "bfloat16",
}


def input_specs(cfg, shape):
    """Abstract (ShapeDtypeStruct) stand-ins for every model input."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            n_mm = min(s // 4, 1024)
            specs["mm_embeds"] = jax.ShapeDtypeStruct(
                (b, n_mm, cfg.d_model), jnp.float32)
            specs["positions_3d"] = jax.ShapeDtypeStruct((3, b, s), i32)
        return specs
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b,), i32),
            "pos": jax.ShapeDtypeStruct((), i32)}


def abstract_state(cfg, shape, kind):
    """Abstract (shape-only) params + decode cache via ``jax.eval_shape``."""
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    if kind != "decode":
        return params, None
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
    return params, cache


def sparse_components(cfg, shape):
    """Paper-model metadata attached to the record (DESIGN.md Section 6)."""
    out = []
    tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1)
    if cfg.num_experts:
        out.append(MOE.sparse_component_spec(cfg, shape, tokens))
    if "local" in cfg.layer_pattern:
        w = min(cfg.window_size, shape.seq_len)
        out.append({
            "name": f"local_attention/{cfg.name}",
            "regime": "diagonal",
            "n": shape.seq_len,
            "nnz": shape.seq_len * w,
            "d": cfg.num_heads * cfg.head_dim,
            "sizeof_val": 2,
        })
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             grad_accum: int = 0, verbose: bool = True,
             causal_impl: str = "masked",
             chunked_loss: bool = False) -> dict:
    """Trace one (arch, shape, mesh) cell and return its dry-run record.

    Compiles nothing and allocates no real arrays: the step function is
    traced over abstract state on a production mesh, and the record
    carries the HLO cost analysis plus the sparse-component metadata the
    roofline analyzer (``repro.core.analyzer``) consumes.
    """
    from repro.models import attention as ATT
    ATT.set_causal_impl(causal_impl)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if grad_accum <= 0:
        grad_accum = GRAD_ACCUM_DEFAULTS.get((arch, shape_name), 1)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)

    t0 = time.time()
    with mesh:
        params_abs, cache_abs = abstract_state(cfg, shape, shape.kind)
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            from repro.optim import adamw
            opt_cfg = adamw.AdamWConfig(
                state_dtype=OPT_DTYPE_DEFAULTS.get(arch, "float32"))
            step, _ = TS.make_train_step(cfg, shape, mesh,
                                         opt_cfg=opt_cfg,
                                         grad_accum=grad_accum,
                                         chunked_loss=chunked_loss)
            opt_abs = jax.eval_shape(
                lambda p: adamw.init_state(p, opt_cfg), params_abs)
            step_abs = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = step.lower(params_abs, opt_abs, specs, step_abs)
        elif shape.kind == "prefill":
            step, _ = TS.make_prefill_step(cfg, shape, mesh)
            lowered = step.lower(params_abs, specs)
        else:
            step, _ = TS.make_serve_step(cfg, shape, mesh)
            lowered = step.lower(params_abs, cache_abs, specs["tokens"],
                                 specs["pos"])
        compiled = lowered.compile()
        mem = H.memory_summary(compiled)
        cost_raw = H.cost_summary(compiled)
        hlo_text = compiled.as_text()
        # Loop-aware re-count: XLA's cost_analysis counts while bodies once;
        # scan-heavy programs need trip-count multipliers (core.hlo_flops).
        loop_aware = HF.analyze_hlo(hlo_text)
        cost = {"flops_per_device": loop_aware["flops"],
                "bytes_per_device": loop_aware["bytes_accessed"]}
        coll = loop_aware["collective_bytes"]
        counts = loop_aware["collective_counts"]
        if verbose:
            print(f"--- {arch} / {shape_name} / {mesh_name} ---")
            print("memory_analysis:", compiled.memory_analysis())
            print("cost_analysis (raw, loops-once) flops=%.4g bytes=%.4g"
                  % (cost_raw["flops_per_device"],
                     cost_raw["bytes_per_device"]))
            print("loop-aware flops=%.4g bytes=%.4g"
                  % (cost["flops_per_device"], cost["bytes_per_device"]))
            print("collective bytes/device:", {k: f"{v:.3g}"
                                               for k, v in coll.items()})

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "step_kind": shape.kind,
        "grad_accum": grad_accum,
        "causal_impl": causal_impl,
        "chunked_loss": chunked_loss,
        "cost": cost,
        "cost_raw": cost_raw,
        "memory": mem,
        "collectives": coll,
        "collective_counts": counts,
        "model_flops": cfg.model_flops(shape),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.param_count(active=True),
        "sparse_components": sparse_components(cfg, shape),
        "compile_seconds": time.time() - t0,
    }
    return record


def record_path(out_dir, arch, shape_name, multi_pod):
    """Path the dry-run record for one cell is written to / read from."""
    tag = "pod2" if multi_pod else "pod1"
    return os.path.join(out_dir, f"{arch}__{shape_name}__{tag}.json")


def main():
    """Run one dry-run cell (or --all) and write the JSON records."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=0)
    ap.add_argument("--causal-impl", default="masked",
                    choices=("masked", "triangle"))
    ap.add_argument("--chunked-loss", action="store_true")
    ap.add_argument("--out-dir", default=DEFAULT_OUT)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    if args.all:
        failures = []
        for arch, shape_name in all_cells():
            for multi_pod in (False, True):
                path = record_path(args.out_dir, arch, shape_name,
                                   multi_pod)
                if os.path.exists(path) and not args.force:
                    print("skip (exists):", path)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out-dir", args.out_dir]
                if multi_pod:
                    cmd.append("--multi-pod")
                print(">>>", " ".join(cmd), flush=True)
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    failures.append((arch, shape_name, multi_pod))
        if failures:
            print("FAILED cells:", failures)
            sys.exit(1)
        print("all cells OK")
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       grad_accum=args.grad_accum,
                       causal_impl=args.causal_impl,
                       chunked_loss=args.chunked_loss)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    path = record_path(args.out_dir, args.arch, args.shape, args.multi_pod)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
