"""Substrate tests: data pipeline determinism, checkpoint atomicity +
resharding restore, serving engine correctness."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.checkpoint import ckpt
from repro.configs import ARCHS
from repro.data.pipeline import DataConfig, SyntheticLM, prefetch
from repro.models import build_model
from repro.serving.engine import Request, ServeConfig, ServingEngine


# ------------------------------------------------------------------ #
# data
# ------------------------------------------------------------------ #
def test_data_deterministic_resume():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8)
    a, b = SyntheticLM(cfg), SyntheticLM(cfg)
    for step in (0, 5, 1000):
        ba, bb = a.batch(step), b.batch(step)
        np.testing.assert_array_equal(ba["tokens"], bb["tokens"])
    assert not np.array_equal(a.batch(1)["tokens"], a.batch(2)["tokens"])


def test_data_sharding_partitions_batch():
    whole = SyntheticLM(DataConfig(128, 16, 8))
    sh0 = SyntheticLM(DataConfig(128, 16, 8, shard=0, num_shards=2))
    sh1 = SyntheticLM(DataConfig(128, 16, 8, shard=1, num_shards=2))
    assert sh0.local_batch == sh1.local_batch == 4
    # shards draw from distinct streams
    assert not np.array_equal(sh0.batch(3)["tokens"], sh1.batch(3)["tokens"])


def test_data_labels_shifted_and_learnable():
    d = SyntheticLM(DataConfig(64, 32, 4))
    b = d.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    # structure: each row follows one latent mode => its token deltas are
    # dominated by a single value (modulo 5% noise)
    diffs = (b["labels"].astype(int) - b["tokens"].astype(int)) % 64
    for row in diffs:
        _, counts = np.unique(row, return_counts=True)
        assert counts.max() > 0.6 * row.size


def test_prefetch_preserves_order():
    d = SyntheticLM(DataConfig(64, 8, 2))
    direct = [d.batch(i)["tokens"] for i in range(5)]
    fetched = []
    for i, b in enumerate(prefetch(d.iterate(0))):
        fetched.append(b["tokens"])
        if i == 4:
            break
    for x, y in zip(direct, fetched):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------------------------ #
# checkpoint
# ------------------------------------------------------------------ #
def tree_example(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32),
                       "scale": jnp.float32(2.5)}}


def test_checkpoint_roundtrip(tmp_path):
    t = tree_example()
    ckpt.save(str(tmp_path), 10, t, meta={"data_step": 40}, shards=2)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), t)
    got, meta = ckpt.restore(str(tmp_path), 10, like=like)
    assert meta == {"data_step": 40}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), t, got)


def test_checkpoint_latest_and_retention(tmp_path):
    t = tree_example()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, t, keep=3)
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert ckpt.available_steps(str(tmp_path)) == [3, 4, 5]


def test_checkpoint_atomic_no_partial_commit(tmp_path, monkeypatch):
    t = tree_example()
    ckpt.save(str(tmp_path), 1, t)

    # make the second save fail mid-write; step_1 must stay intact and no
    # committed step_2 may appear
    import numpy as _np
    orig = _np.savez

    def boom(*a, **kw):
        raise RuntimeError("disk full")

    monkeypatch.setattr(_np, "savez", boom)
    with pytest.raises(RuntimeError):
        ckpt.save(str(tmp_path), 2, t)
    monkeypatch.setattr(_np, "savez", orig)
    assert ckpt.available_steps(str(tmp_path)) == [1]
    got, _ = ckpt.restore(str(tmp_path), 1, like=t)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), t, got)


def test_checkpoint_restore_with_shardings(tmp_path):
    t = tree_example()
    ckpt.save(str(tmp_path), 7, t)
    sh = jax.tree.map(lambda x: jax.devices()[0], t)
    got, _ = ckpt.restore(str(tmp_path), 7, like=t, shardings=sh)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), t, got)


def test_checkpoint_model_state_roundtrip(tmp_path):
    cfg = replace(ARCHS["yi-6b"].smoke(), compute_dtype="float32",
                  param_dtype="float32")
    model = build_model(cfg, remat="none")
    params, _ = model.init(jax.random.PRNGKey(0))
    from repro.training.optimizer import init_opt_state
    state = {"params": params, "opt": init_opt_state(params)._asdict()}
    ckpt.save(str(tmp_path), 3, state, meta={"arch": cfg.name})
    got, meta = ckpt.restore(str(tmp_path), 3, like=state)
    assert meta["arch"] == cfg.name
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 state, got)


# ------------------------------------------------------------------ #
# serving
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def served_model():
    cfg = replace(ARCHS["yi-6b"].smoke(), compute_dtype="float32",
                  param_dtype="float32")
    model = build_model(cfg, remat="none")
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ref_greedy(model, params, prompt, n, max_len=64):
    last, caches = model.prefill(
        params, np.asarray(prompt)[None].astype(np.int32), pad_to=max_len)
    out = [int(jnp.argmax(last, -1)[0])]
    pos = len(prompt)
    for _ in range(n - 1):
        logits, caches = model.decode_step(
            params, jnp.asarray([out[-1]], dtype=jnp.int32), caches, pos)
        out.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return out


def test_serving_continuous_batching_matches_reference(served_model):
    cfg, model, params = served_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=l).astype(np.int32)
               for l in (5, 9, 7, 3, 11, 6)]
    refs = [_ref_greedy(model, params, p, 8) for p in prompts]
    eng = ServingEngine(model, params, ServeConfig(batch=3, max_len=64))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, ref in zip(reqs, refs):
        assert r.done and r.out_tokens == ref, (r.rid, r.out_tokens, ref)


def test_serving_batches_share_decode_ticks(served_model):
    """3 slots x 6 requests of 8 tokens should take far fewer ticks than
    serial decoding (continuous batching actually batches)."""
    cfg, model, params = served_model
    rng = np.random.default_rng(1)
    eng = ServingEngine(model, params, ServeConfig(batch=3, max_len=64))
    for i in range(6):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, size=6
                                               ).astype(np.int32),
                           max_new_tokens=8))
    eng.run()
    assert eng.ticks <= 6 * 7 / 2, eng.ticks  # well under serial 42


def test_checkpoint_cross_mesh_reshard_subprocess(tmp_path):
    """FT at fleet scale: params saved under one mesh topology restore
    under a different one (the manifest is topology-free; shardings are
    re-applied at load)."""
    import subprocess, sys, textwrap
    snippet = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import ckpt
        d = {str(tmp_path)!r}
        auto = (jax.sharding.AxisType.Auto,) * 2
        m1 = jax.make_mesh((2, 4), ("data", "model"), axis_types=auto)
        tree = {{"w": jnp.arange(64 * 32, dtype=jnp.float32
                                 ).reshape(64, 32)}}
        tree = jax.device_put(tree, NamedSharding(m1, P("data", "model")))
        ckpt.save(d, 1, tree)
        m2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
        sh2 = {{"w": NamedSharding(m2, P("model", "data"))}}
        like = {{"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}}
        got, _ = ckpt.restore(d, 1, like=like, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(64 * 32).reshape(64, 32))
        assert got["w"].sharding.spec == P("model", "data")
        print("RESHARD_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "RESHARD_OK" in out.stdout, out.stdout + out.stderr
