"""LM distribution correctness worker (run in a subprocess with 8
virtual devices).  Checks that sharded execution through the production
specs equals single-device execution.

    python tests/lm_dist_worker.py decode_seq_sharded
"""
import os
import sys

# a CPU-only tool: virtual CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import reduced_config  # noqa: E402
from repro.dist.sharding import default_rules, kv_cache_layout, use_mesh  # noqa: E402
from repro.models import lm  # noqa: E402


def _mesh(data, model):
    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def check(name, got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.allclose(got, want, rtol=tol, atol=tol):
        print(f"MISMATCH {name}: max abs {np.abs(got-want).max():.3e}")
        sys.exit(1)
    print(f"ok: {name}")


def decode_seq_sharded():
    """KH=2 on model=4 forces the seq-sharded cache layout; the
    distributed flash-decode (shard_map + LSE psum) must equal the
    single-device dense path."""
    cfg = dataclasses.replace(
        reduced_config(get_config("yi-9b")), dtype="float32", n_kv_heads=2,
        n_layers=2,
    )
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, T = 4, 32
    cache = lm.init_cache(cfg, B, T)
    rng = np.random.default_rng(0)
    # warm the cache with random (valid) content
    cache = jax.tree.map(
        lambda c: jnp.asarray(rng.standard_normal(c.shape), c.dtype), cache
    )
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    pos = jnp.int32(20)

    # single-device reference
    ref_logits, ref_cache = lm.decode_step(params, cfg, tok, pos, cache)

    mesh = _mesh(2, 4)
    rules = default_rules()
    assert kv_cache_layout(B, T, cfg.n_kv_heads, mesh, rules) == "seq"

    def step(params, tok, pos, cache):
        with use_mesh(mesh, rules):
            return lm.decode_step(params, cfg, tok, pos, cache)

    cache_sh = jax.tree.map(
        lambda c: jax.device_put(
            c,
            NamedSharding(mesh, P(None, "data", "model", None, None))
            if c.ndim == 5 else NamedSharding(mesh, P()),
        ),
        cache,
    )
    got_logits, got_cache = jax.jit(step)(params, tok, pos, cache_sh)
    check("decode-seq-sharded logits", got_logits, ref_logits, 2e-5)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(ref_cache)):
        check("cache leaf", a, b, 2e-5)


def decode_seq_all_sharded():
    """B=1 long-context: cache spread over (data, model)."""
    cfg = dataclasses.replace(
        reduced_config(get_config("yi-9b")), dtype="float32", n_kv_heads=2,
        n_layers=2,
    )
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    B, T = 1, 64
    cache = lm.init_cache(cfg, B, T)
    rng = np.random.default_rng(1)
    cache = jax.tree.map(
        lambda c: jnp.asarray(rng.standard_normal(c.shape), c.dtype), cache
    )
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    pos = jnp.int32(50)
    ref_logits, _ = lm.decode_step(params, cfg, tok, pos, cache)

    mesh = _mesh(2, 4)
    rules = default_rules()
    assert kv_cache_layout(B, T, cfg.n_kv_heads, mesh, rules) == "seq_all"

    def step(params, tok, pos, cache):
        with use_mesh(mesh, rules):
            return lm.decode_step(params, cfg, tok, pos, cache)

    cache_sh = jax.tree.map(
        lambda c: jax.device_put(
            c,
            NamedSharding(mesh, P(None, None, ("data", "model"), None, None))
            if c.ndim == 5 else NamedSharding(mesh, P()),
        ),
        cache,
    )
    got_logits, _ = jax.jit(step)(params, tok, pos, cache_sh)
    check("decode-seq-all logits", got_logits, ref_logits, 2e-5)


SCENARIOS = {
    "decode_seq_sharded": decode_seq_sharded,
    "decode_seq_all_sharded": decode_seq_all_sharded,
}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    for n in list(SCENARIOS) if which == "all" else [which]:
        SCENARIOS[n]()
    print("ALL OK")
