"""End-to-end training driver: ~100M-parameter qwen2-family model,
synthetic tokens, full production loop (AdamW + schedule, remat,
checkpoint/restart, NaN guard, straggler watchdog).

    PYTHONPATH=src python examples/train_100m.py --steps 300

Re-running the same command resumes from the latest checkpoint —
kill it mid-run to see restart work.  ``--arch`` selects any of the 10
assigned architectures (reduced to ~100M scale automatically).
"""
import argparse
import dataclasses
import os


def build_100m(arch: str):
    from repro.configs import get_config
    from repro.configs.base import reduced_config

    base = get_config(arch)
    # ~100M-scale instantiation of the same family
    cfg = reduced_config(
        base,
        d_model=512,
        n_heads=8,
        n_kv_heads=max(2, min(base.n_kv_heads, 4)),
        head_dim=64,
        d_ff=1536 if base.d_ff > 0 else 0,
        vocab_size=32_000,
        n_layers=len(base.block_pattern) * max(1, 8 // len(base.block_pattern)),
    )
    return cfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_100m")
    args = ap.parse_args()

    import jax

    from repro import compile_cache
    from repro.data.pipeline import DataConfig
    from repro.train import optimizer as opt_mod
    from repro.train.train_step import (
        TrainOptions,
        init_train_state,
        make_train_step,
    )
    from repro.train.trainer import Trainer, TrainerConfig

    compile_cache.enable()

    cfg = build_100m(args.arch)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} ({args.arch} family) params≈{n_params/1e6:.0f}M")

    step_fn = jax.jit(
        make_train_step(
            cfg,
            opt_mod.OptimizerConfig(peak_lr=3e-4, warmup_steps=20,
                                    decay_steps=args.steps),
            TrainOptions(q_chunk=min(256, args.seq)),
        ),
        donate_argnums=(0,),
    )
    trainer = Trainer(
        train_step=step_fn,
        init_state=lambda: init_train_state(jax.random.PRNGKey(0), cfg),
        data_cfg=DataConfig(
            seq_len=args.seq,
            global_batch=args.batch,
            vocab_size=cfg.vocab_size,
            modality_tokens=cfg.num_modality_tokens,
            modality_dim=cfg.modality_dim,
            modality_is_frames=cfg.modality == "audio",
        ),
        cfg=TrainerConfig(
            total_steps=args.steps,
            checkpoint_every=100,
            checkpoint_dir=args.ckpt_dir,
            log_every=10,
        ),
    )
    trainer.install_signal_handler()
    if trainer.start_step:
        print(f"resumed from checkpoint at step {trainer.start_step}")
    result = trainer.run()

    losses = [m["loss"] for m in result["metrics"] if "loss" in m]
    print(f"finished at step {result['final_step']}")
    print("loss trajectory:", " ".join(f"{l:.3f}" for l in losses))
    if len(losses) >= 2:
        assert losses[-1] < losses[0], "loss did not decrease"
        print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}  ✓")


if __name__ == "__main__":
    main()
