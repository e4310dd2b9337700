"""Distribution layer: sharding policy rules, collective parsing, the
scan-undercount fact the roofline methodology rests on, and a reduced
production-mesh lower+compile in a forced-8-device subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import HW, dominant, model_flops, \
    parse_collectives, terms_from
from repro.configs import ARCHS, SHAPES


# ------------------------------------------------------------------ #
# collective parser
# ------------------------------------------------------------------ #
HLO_SNIPPET = """
  %all-reduce.1 = f32[128,64]{1,0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  %all-gather.2 = bf16[16,512]{1,0} all-gather(%y), channel_id=2, replica_groups=[4,2]<=[8], dimensions={0}
  %reduce-scatter.3 = f32[32]{0} reduce-scatter(%z), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}
  %all-reduce-done = f32[128,64]{1,0} all-reduce-done(%all-reduce.1)
  %collective-permute.4 = s32[8]{0} collective-permute(%w), channel_id=4, source_target_pairs={{0,1}}
"""


def test_parse_collectives_ring_costs():
    out = parse_collectives(HLO_SNIPPET)
    ar = 2 * (128 * 64 * 4) * 3 / 4          # g=4
    ag = (16 * 512 * 2) * 1 / 2              # g=2
    rs = (32 * 4) * 7                        # g=8, result is the shard
    cp = 8 * 4
    assert out["all-reduce"] == pytest.approx(ar)
    assert out["all-gather"] == pytest.approx(ag)
    assert out["reduce-scatter"] == pytest.approx(rs)
    assert out["collective-permute"] == pytest.approx(cp)
    assert out["total"] == pytest.approx(ar + ag + rs + cp)


def test_terms_and_dominance():
    t = terms_from(flops=197e12 * 256, bytes_hbm=819e9 * 256,
                   wire_per_device=50e9 / 2, chips=256)
    assert t["compute"] == pytest.approx(1.0)
    assert t["memory"] == pytest.approx(1.0)
    assert t["collective"] == pytest.approx(0.5)
    assert dominant({"compute": 3, "memory": 2, "collective": 1}) == \
        "compute"


def test_model_flops_sanity():
    cfg = ARCHS["qwen3-8b"]
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    de = model_flops(cfg, SHAPES["decode_32k"])
    # train ~ 6ND: within 2x of the attention-free floor
    assert tr > 6 * cfg.param_count() * 4096 * 256
    assert de < pf < tr
    # MoE uses active params
    moe = ARCHS["qwen3-moe-235b-a22b"]
    assert model_flops(moe, SHAPES["train_4k"]) < \
        0.25 * 6 * moe.param_count() * 4096 * 256


# ------------------------------------------------------------------ #
# scan undercount (the fact the compositional costing corrects)
# ------------------------------------------------------------------ #
def test_cost_analysis_counts_scan_body_once():
    def body(c, w):
        return jnp.tanh(c @ w), None

    def f_scan(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def f_unroll(x, ws):
        for i in range(ws.shape[0]):
            x = jnp.tanh(x @ ws[i])
        return x

    def flops(fn, *avals):
        ca = jax.jit(fn).lower(*avals).compile().cost_analysis()
        # jax < 0.6 returns a one-element list of dicts (one per device),
        # newer releases return the dict directly
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return ca["flops"]

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    fs = flops(f_scan, x, ws)
    fu = flops(f_unroll, x, ws)
    assert fu == pytest.approx(8 * fs, rel=0.01)


# ------------------------------------------------------------------ #
# sharding policy
# ------------------------------------------------------------------ #
def test_policy_specs_respect_divisibility_subprocess():
    """grok's 8 experts don't divide model=16 -> d_ff TP fallback; qwen3-
    moe's 128 experts shard on model.  Needs a mesh => subprocess."""
    snippet = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.launch.specs import shapes_and_axes, param_specs
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

        for arch in ("grok-1-314b", "qwen3-moe-235b-a22b"):
            cfg = get_arch(arch)
            model = build_model(cfg)
            shapes, axes = shapes_and_axes(model)
            specs = param_specs(cfg, shapes, axes, mesh, policy="fsdp")
            sp = specs["stack0"]["b0"]["moe"]["w_gate"]   # (L, E, d, ff)
            # experts divide model=4 for both archs -> expert parallel,
            # embed dim picks up the data (fsdp) axis, layers unsharded
            assert sp[0] is None and sp[1] == "model", sp
            assert sp[2] in ("data", ("data",)), sp
            emb = specs["embed"]                           # (V, d)
            assert emb[0] == "model", emb
            assert emb[1] in ("data", ("data",)), emb
            # attention q_proj dim is TP'd under plain tp policy too
            tp = param_specs(cfg, shapes, axes, mesh, policy="tp")
            wq = tp["stack0"]["b0"]["attn"]["wq"]          # (L, d, qd)
            assert wq[2] == "model" and wq[1] is None, wq
        print("POLICY_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "POLICY_OK" in out.stdout, out.stdout + out.stderr


def test_reduced_production_cell_compiles_subprocess():
    """A smoke-sized train cell lowers+compiles with full shardings on a
    forced 8-device (2x4) mesh — the dry-run pipeline end to end."""
    snippet = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from dataclasses import replace
        from repro.configs import get_arch
        from repro.configs.base import ShapeSpec
        from repro.launch.dryrun import lower_compile
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = replace(get_arch("yi-6b").smoke(), num_layers=2)
        shape = ShapeSpec("tiny_train", 64, 8, "train")
        lowered, compiled = lower_compile(cfg, shape, mesh, remat="full")
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        assert ca["flops"] > 0
        assert ma.argument_size_in_bytes > 0
        txt = compiled.as_text()
        assert ("all-reduce" in txt) or ("reduce-scatter" in txt)
        print("CELL_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "CELL_OK" in out.stdout, out.stdout + out.stderr
