"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

On a real TPU pod this runs under `jax.distributed.initialize()` with
the production mesh; on this host it runs reduced configs end-to-end
(the full configs are exercised by the dry-run). XLA flags below enable
the latency-hiding scheduler that overlaps collectives with compute on
TPU — the "overlap compute/comm" knob of the task spec.
"""

from __future__ import annotations

import argparse
import os


TPU_PERF_FLAGS = (
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true "
    "--xla_tpu_data_parallel_opt_different_sized_ops=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "") not in ("cpu",):
        os.environ.setdefault("LIBTPU_INIT_ARGS", TPU_PERF_FLAGS)

    import jax

    from repro.configs.registry import get_config, get_smoke_config
    from repro.data import TokenDataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import LMModel
    from repro.optim import AdamWConfig, warmup_cosine
    from repro.runtime import TrainConfig, TrainLoop

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = LMModel(cfg)
    mesh = make_host_mesh(args.model_axis) if len(jax.devices()) > 1 else None

    ds = TokenDataset(
        cfg.vocab_size, seq_len=args.seq_len, global_batch=args.global_batch,
        source="zipf", corpus_tokens=min(2_000_000, 200 * args.seq_len *
                                         max(args.global_batch, 8)),
    )
    tc = TrainConfig(
        total_steps=args.steps,
        checkpoint_dir=args.checkpoint_dir,
        num_microbatches=args.microbatches,
        optimizer=AdamWConfig(
            learning_rate=warmup_cosine(args.lr, args.steps // 10,
                                        args.steps),
            grad_compression=args.grad_compression,
        ),
    )
    loop = TrainLoop(model, tc, ds, mesh=mesh)
    result = loop.run()
    hist = result["history"]
    print(f"[train] {cfg.name}: {result['final_step']} steps, "
          f"median {result['median_step_time']*1e3:.1f} ms/step, "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}, "
          f"stragglers flagged: {len(result['stragglers'])}")


if __name__ == "__main__":
    main()
