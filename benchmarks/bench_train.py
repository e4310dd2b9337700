"""Training/serving substrate benchmarks on CPU smoke configs:
tokens/s for one train step per arch family + serving tokens/tick.

CSV:  train/<arch>,us_per_step,derived(tokens/s)
      serve/<arch>,us_per_tick,derived(tokens/tick)
"""

from __future__ import annotations

import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import build_model
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.step import make_train_step

FAMILIES = ["yi-6b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
            "recurrentgemma-9b", "whisper-small"]


def train_row(name: str, b: int = 4, s: int = 128, iters: int = 5):
    cfg = replace(ARCHS[name].smoke(), compute_dtype="float32",
                  param_dtype="float32")
    model = build_model(cfg, remat="none")
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-2)))
    data = SyntheticLM(DataConfig(cfg.vocab_size, s, b))
    kw = {}
    if cfg.is_encdec:
        kw["enc_embeds"] = jnp.zeros((b, cfg.encoder_seq, cfg.d_model),
                                     jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    batch.update(kw)
    params, opt, m = step(params, opt, batch)      # compile
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for i in range(iters):
        params, opt, m = step(params, opt, batch)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / iters
    return (f"train/{name}", dt * 1e6, b * s / dt)


def rows():
    return [train_row(n) for n in FAMILIES]


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.0f},{derived:.0f}")


if __name__ == "__main__":
    main()
