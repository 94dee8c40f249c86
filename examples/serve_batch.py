"""Serving example: continuous-batching engine over a reduced model.

A stream of requests with different prompt lengths and arrival times
shares a fixed slot pool; finished slots are recycled immediately.

    PYTHONPATH=src python examples/serve_batch.py --arch qwen2-7b
    PYTHONPATH=src python examples/serve_batch.py --arch jamba-v0.1-52b
"""
import argparse
import dataclasses
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro import compile_cache
    from repro.configs import get_config
    from repro.configs.base import reduced_config
    from repro.models import lm
    from repro.serve import Engine, EngineConfig

    compile_cache.enable()

    cfg = dataclasses.replace(
        reduced_config(get_config(args.arch)), dtype="float32"
    )
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(
        params, cfg,
        EngineConfig(
            max_slots=args.slots,
            max_len=128,
            max_new_tokens=args.new_tokens,
            prefill_buckets=(8, 16, 32),
        ),
    )

    rng = np.random.default_rng(0)
    lengths = rng.integers(3, 16, size=args.requests)
    t0 = time.perf_counter()
    for n in lengths:
        eng.add_request(list(rng.integers(0, cfg.vocab_size, size=int(n))))

    rounds = 0
    while eng.queue or eng.active:
        eng.step()
        rounds += 1
        if rounds % 5 == 0:
            print(f"round {rounds:3d}: active={len(eng.active)} "
                  f"queued={len(eng.queue)} done={len(eng.finished)} "
                  f"util={eng.utilization:.0%}")
    dt = time.perf_counter() - t0

    total_new = sum(len(r.out) for r in eng.finished)
    print(f"\n{len(eng.finished)} requests, {total_new} tokens "
          f"in {dt:.1f}s ({total_new/dt:.1f} tok/s incl. compile)")
    for r in sorted(eng.finished, key=lambda r: r.rid)[:4]:
        print(f"  req{r.rid}: prompt[{len(r.prompt)}] -> {r.out}")


if __name__ == "__main__":
    main()
