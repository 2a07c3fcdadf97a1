import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST run before any other import (jax locks the
platform and device count at first backend init): the dry-run — and
only the dry-run — sees 512 placeholder CPU devices so
`make_production_mesh` can build the 16×16 single-pod and 2×16×16
multi-pod meshes. It is pinned to the CPU so that on a TPU host it
neither holds the chip (its ``--all`` mode spawns one child per cell)
nor meets a mesh larger than the host's chips.

Per cell this produces an artifact JSON under ``artifacts/dryrun/`` with
``memory_analysis`` / ``cost_analysis`` outputs plus the loop-aware
parsed HLO costs (FLOPs, HBM traffic, per-type collective bytes) that
§Roofline consumes.

Usage:
    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all            # every cell, subprocess-isolated
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import compute_costs, model_flops  # noqa: E402
from repro.configs import ShapeConfig, shapes_for_arch  # noqa: E402
from repro.configs.registry import ARCH_NAMES, get_config  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import LMModel  # noqa: E402
from repro.optim import adamw  # noqa: E402

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun"
)
TRAIN_MICROBATCHES = 8
# Per-arch overrides: activation-heavy configs trade collective volume
# (more ZeRO weight gathers) for peak HBM.
ARCH_MICROBATCHES = {
    "qwen3-moe-235b-a22b": 16,
    "llava-next-34b": 16,
}


def microbatches_for(arch: str, mesh=None, global_batch: int = 256) -> int:
    mb = ARCH_MICROBATCHES.get(arch, TRAIN_MICROBATCHES)
    if mesh is not None:
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        # per-µbatch batch must stay divisible by the DP shard count
        mb = min(mb, max(1, global_batch // dp))
    return mb


def input_specs(cfg, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, n = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    act_dtype = jnp.dtype(cfg.dtype)
    if shape.kind in ("train", "prefill"):
        if cfg.uses_embeddings_input:
            return {
                "embeddings": jax.ShapeDtypeStruct((b, n, cfg.d_model),
                                                   act_dtype),
                "targets": jax.ShapeDtypeStruct((b, n), i32),
            }
        return {
            "inputs": jax.ShapeDtypeStruct((b, n), i32),
            "targets": jax.ShapeDtypeStruct((b, n), i32),
        }
    # decode: one new token against a seq_len-deep cache
    if cfg.uses_embeddings_input:
        return {
            "embeddings": jax.ShapeDtypeStruct((b, 1, cfg.d_model), act_dtype)
        }
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}


def logits_sharding(mesh, batch: int):
    dp = shd.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_axis = dp if (batch % dp_size == 0 and batch > 1) else None
    return NamedSharding(mesh, P(batch_axis, None, "model"))


def lower_cell(arch: str, shape: ShapeConfig, mesh, mesh_name: str):
    cfg = get_config(arch)
    model = LMModel(cfg)
    shd.set_active_mesh(mesh)  # enables in-model activation constraints
    # MoE inference cells use the serving weight layout: fully-resident
    # 2D-sharded experts (no per-step ZeRO gathers). Dense archs keep the
    # train layout: their uneven head counts (40/36/56 over 16) cannot be
    # TP-input-sharded, and replicating those weights costs more HBM than
    # the amortized ZeRO gathers (see EXPERIMENTS §Perf iteration log).
    shd.set_rules_profile(
        "serve" if (shape.kind in ("prefill", "decode")
                    and cfg.family == "moe") else "train"
    )
    rng = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init, rng)
    p_shard = shd.param_shardings(params_shapes, mesh)
    batch = input_specs(cfg, shape)
    b_shard = shd.batch_shardings(batch, mesh)

    if shape.kind == "train":
        # ≥20B configs use the production memory diet: factored second
        # moment (Adafactor-style), bf16 momentum, bf16 grad accumulation
        # — the dense AdamW f32 state of a 235B model does not fit
        # 256×16 GB alongside activations.
        from repro.analysis import param_counts

        big = param_counts(cfg)["total"] > 2e10
        opt_cfg = adamw.AdamWConfig(
            factored_second_moment=big,
            momentum_dtype="bfloat16" if big else "float32",
            accum_dtype="bfloat16" if big else "float32",
            # chunked_update refuted: lax.map breaks param/opt donation
            # aliasing (+3.7 GB copies) — see EXPERIMENTS §Perf.
        )
        opt_shapes = jax.eval_shape(
            lambda p: adamw.init(p, opt_cfg), params_shapes
        )
        nu_shard = shd.param_shardings(opt_shapes.nu, mesh)
        o_shard = adamw.AdamWState(
            step=NamedSharding(mesh, P()), mu=p_shard, nu=nu_shard,
            compression_error=None,
        )

        num_mb = microbatches_for(arch, mesh, shape.global_batch)

        def train_step(params, opt_state, batch):
            loss, grads, metrics = adamw.accumulate_gradients(
                model.loss, params, batch, num_mb,
                accum_dtype=opt_cfg.accum_dtype,
            )
            params, opt_state, opt_metrics = adamw.update(
                grads, opt_state, params, opt_cfg
            )
            return params, opt_state, loss

        lowered = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        ).lower(params_shapes, opt_shapes, batch)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = model.apply(params, batch)
            # serving prefill emits the last position's logits only; the
            # stack compute for earlier positions stays live through the
            # causal attention dependencies.
            return logits[:, -1:, :]

        lowered = jax.jit(
            prefill_step,
            in_shardings=(p_shard, b_shard),
            out_shardings=logits_sharding(mesh, shape.global_batch),
        ).lower(params_shapes, batch)
    else:  # decode
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        c_shard = shd.cache_shardings(cache_shapes, mesh)
        ci_shapes = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

        def serve_step(params, cache, inputs, cache_index):
            return model.decode_step(params, cache, inputs, cache_index)

        lowered = jax.jit(
            serve_step,
            in_shardings=(p_shard, c_shard, b_shard, None),
            out_shardings=(
                logits_sharding(mesh, shape.global_batch), c_shard
            ),
            donate_argnums=(1,),
        ).lower(params_shapes, cache_shapes, batch, ci_shapes)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = {}
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception:  # noqa: BLE001
        pass
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                if hasattr(ma, attr):
                    mem[attr] = int(getattr(ma, attr))
    except Exception:  # noqa: BLE001
        pass

    parsed = compute_costs(compiled.as_text())
    chips = mesh.devices.size
    artifact = {
        "arch": arch,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": mesh_name,
        "chips": chips,
        "compile_seconds": compile_s,
        "cost_analysis": {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float))
        },
        "memory_analysis": mem,
        "parsed": {
            "flops_per_chip": parsed.flops,
            "traffic_bytes_per_chip": parsed.traffic_bytes,
            "collective_bytes_per_chip": parsed.collective_bytes,
            "num_collectives": len(parsed.collective_ops),
        },
        "model_flops": model_flops(get_config(arch), shape),
    }
    return artifact


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str):
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    shape = next(
        s for s in shapes_for_arch(arch) if s.name == shape_name
    )
    artifact = lower_cell(arch, shape, mesh, mesh_name)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    # memory_analysis numbers are per-device (the compiled module is the
    # per-device program)
    mem_gb = artifact["memory_analysis"].get("temp_size_in_bytes", 0) / 2**30
    arg_gb = artifact["memory_analysis"].get("argument_size_in_bytes", 0) / 2**30
    print(
        f"[dryrun] {mesh_name}/{arch}/{shape_name}: compile "
        f"{artifact['compile_seconds']:.1f}s, "
        f"flops/chip {artifact['parsed']['flops_per_chip']:.3e}, "
        f"coll GB/chip "
        f"{sum(artifact['parsed']['collective_bytes_per_chip'].values())/2**30:.1f}, "
        f"args {arg_gb:.2f} GB/chip, temp {mem_gb:.2f} GB/chip"
    )
    print(f"[dryrun] memory_analysis: {artifact['memory_analysis']}")
    return path


def all_cells():
    for arch in ARCH_NAMES:
        for shape in shapes_for_arch(arch):
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.all:
        failures = []
        for mesh_name in ("single", "multi"):
            for arch, shape_name in all_cells():
                out = args.out or os.path.normpath(
                    os.path.join(ARTIFACT_DIR, mesh_name)
                )
                done = os.path.join(out, f"{arch}__{shape_name}.json")
                if os.path.exists(done):
                    print(f"[dryrun] skip (exists): {done}")
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape_name,
                    "--mesh", mesh_name,
                ]
                r = subprocess.run(cmd, capture_output=False)
                if r.returncode != 0:
                    failures.append((mesh_name, arch, shape_name))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("[dryrun] ALL CELLS PASSED")
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    out = args.out or os.path.normpath(
        os.path.join(ARTIFACT_DIR, args.mesh)
    )
    try:
        run_cell(args.arch, args.shape, args.mesh, out)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
