import os
import sys

# tests must see ONE device (the dry-run sets 512 in its own subprocess)
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the repo root: benchmarks/ and chip_smoke.py
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    import jax
    return jax.random.PRNGKey(0)


ALL_ARCHS = [
    "whisper-tiny", "tinyllama-1.1b", "internvl2-2b", "grok-1-314b",
    "granite-34b", "llama3.2-1b", "hymba-1.5b", "qwen3-moe-235b-a22b",
    "rwkv6-7b", "qwen2.5-32b",
]


def make_batch(cfg, batch=2, seq=16, seed=0):
    import jax
    import jax.numpy as jnp
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (batch, seq), 0, cfg.vocab_size)
    b = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        b["patches"] = jax.random.normal(
            k, (batch, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(
            k, (batch, cfg.enc_seq, cfg.d_model), jnp.float32)
    return b
