import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every runnable (arch x shape) cell and both production meshes this
lowers + compiles the real step function against ShapeDtypeStruct inputs
(no allocation), prints memory_analysis()/cost_analysis(), and — for the
roofline — compiles small *unrolled* layer-count variants whose finite
differences give exact per-layer flops/bytes/collective-wire costs
(DESIGN.md §6; XLA cost analysis counts scan bodies once, so the scanned
full-model compile proves shardability+memory while the unrolled variants
price the layers).

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] --out dryrun.json
"""

import argparse
import json
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, get_arch, runnable_shapes
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (dominant, model_flops, parse_collectives,
                                   terms_from)
from repro.launch.specs import (abstract_opt_state, make_batch,
                                make_serving_inputs, opt_specs, param_specs,
                                shapes_and_axes)
from repro.models import build_model
from repro.models.transformer import stack_layout
from repro.sharding.policy import param_policy
from repro.training.optimizer import AdamWConfig
from repro.training.step import make_prefill_step, make_train_step


def build_cell(cfg, shape, mesh, *, unroll=False, remat="dots",
               microbatch_seqs: int = 4, seq_shard: bool = False):
    """(jitted-lowerable fn, abstract args, in_specs, out_specs).

    Train cells use gradient accumulation sized so each microbatch holds
    ~``microbatch_seqs`` sequences per device (activation memory control).
    """
    model = build_model(cfg, remat=remat, unroll=unroll,
                        seq_shard=seq_shard)
    shapes, axes = shapes_and_axes(model)
    # NOTE: a "serve2d" resident layout (weights over data x model, no
    # per-layer AG on the decode path) was tried for FSDP-class serving
    # cells and REFUTED as a blanket policy: dims that don't divide 256
    # (qwen2-vl d_ff=29568) fall back to replication and explode memory;
    # per-dim factorized 2D sharding is future work (§Perf, grok decode).
    pspec = param_specs(cfg, shapes, axes, mesh)

    if shape.kind == "train":
        master = cfg.param_dtype == "bfloat16"
        ospec = opt_specs(cfg, shapes, axes, mesh, master_weights=master)
        batch, bspec = make_batch(cfg, shape, mesh)
        dp = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                          if a in ("pod", "data")]))
        per_dev = max(1, shape.global_batch // dp)
        mb = max(1, per_dev // microbatch_seqs)
        # NOTE: hoisting a TP reshard of FSDP params (make_train_step's
        # param_axes/compute_policy) was tried and REFUTED: the partitioner
        # re-gathers per microbatch regardless while the TP param copies
        # triple temp memory (EXPERIMENTS.md §Perf, grok iteration 3).
        step = make_train_step(model, AdamWConfig(master_weights=master),
                               microbatches=mb, unroll=unroll)
        args = (shapes, abstract_opt_state(shapes, master), batch)
        return step, args, (pspec, ospec, bspec), (pspec, ospec, None)

    if shape.kind == "prefill":
        batch, bspec = make_batch(cfg, shape, mesh, with_labels=False)
        prefill = make_prefill_step(model)
        fn = lambda params, b: prefill(params, b)
        return fn, (shapes, batch), (pspec, bspec), None

    # decode
    (token, caches, cur), (tspec, cspec, curspec) = make_serving_inputs(
        cfg, shape, mesh)
    fn = model.decode_step
    return (fn, (shapes, token, caches, cur),
            (pspec, tspec, cspec, curspec), (None, cspec))


def lower_compile(cfg, shape, mesh, *, unroll=False, remat="dots",
                  seq_shard=False):
    fn, args, in_specs, out_specs = build_cell(cfg, shape, mesh,
                                               unroll=unroll, remat=remat,
                                               seq_shard=seq_shard)
    from jax.sharding import NamedSharding

    def to_sharding(tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec))

    with jax.set_mesh(mesh):
        jitted = jax.jit(fn,
                         in_shardings=to_sharding(in_specs),
                         out_shardings=(to_sharding(out_specs)
                                        if out_specs is not None else None))
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def cost_of(compiled):
    ca = compiled.cost_analysis()
    wire = parse_collectives(compiled.as_text())
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                wire=float(wire["total"]),
                wire_by_op={k: v for k, v in wire.items() if k != "total"})


def _variant_layers(cfg):
    """Layer-count variants for the finite-difference costing.

    2 vs 3 pattern-repeats (not 1 vs 2): with aggressive sharding hints
    the partitioner can pick a different global strategy for a 1-repeat
    module, breaking cost linearity; 2->3 stays within one regime."""
    plen = len(cfg.block_pattern) or 1
    variants = {"a": 2 * plen, "b": 3 * plen}
    tail = cfg.num_layers % plen
    if tail:
        variants["tail"] = tail
    return plen, variants


def roofline_cell(cfg, shape, mesh, chips, *, remat="dots",
                  seq_shard=False):
    """Per-cell roofline via unrolled variants (exact per-layer costs)."""
    plen, variants = _variant_layers(cfg)
    costs = {}
    for name, nl in variants.items():
        vcfg = replace(cfg, num_layers=nl,
                       encoder_layers=min(cfg.encoder_layers, 1))
        _, comp = lower_compile(vcfg, shape, mesh, unroll=True, remat=remat,
                                seq_shard=seq_shard)
        costs[name] = cost_of(comp)
    if cfg.is_encdec and shape.kind != "decode":
        vcfg = replace(cfg, num_layers=2 * plen, encoder_layers=2)
        _, comp = lower_compile(vcfg, shape, mesh, unroll=True, remat=remat,
                                seq_shard=seq_shard)
        costs["enc2"] = cost_of(comp)

    n_full = cfg.num_layers // plen
    tail = cfg.num_layers % plen

    def combine(key):
        body = costs["b"][key] - costs["a"][key]
        base = costs["a"][key] - 2 * body
        total = base + n_full * body
        if tail:
            total += costs["tail"][key] - base
        if "enc2" in costs:
            enc_body = costs["enc2"][key] - costs["a"][key]
            total += (cfg.encoder_layers - 1) * enc_body
        return total, body, base

    flops, flops_body, flops_base = combine("flops")
    bytes_, _, _ = combine("bytes")
    wire, wire_body, wire_base = combine("wire")
    # per-device HLO costs -> global flops/bytes for the terms
    terms = terms_from(flops * chips, bytes_ * chips, wire, chips)
    mf = model_flops(cfg, shape)
    return dict(
        hlo_flops_per_device=flops, hlo_bytes_per_device=bytes_,
        wire_bytes_per_device=wire,
        wire_body_per_layer=wire_body,
        terms=terms, bottleneck=dominant(terms),
        model_flops=mf,
        useful_ratio=mf / (flops * chips) if flops else float("nan"),
        wire_by_op_one=costs["a"]["wire_by_op"],
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             do_roofline: bool = True, remat: str = "dots",
             bf16_params: bool = False, seq_shard: bool = False,
             verbose: bool = True):
    cfg = get_arch(arch)
    if bf16_params:
        cfg = replace(cfg, param_dtype="bfloat16")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    lowered, compiled = lower_compile(cfg, shape, mesh, remat=remat,
                                      seq_shard=seq_shard)
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rec = dict(
        arch=arch, shape=shape_name, mesh="x".join(
            str(s) for s in mesh.devices.shape),
        policy=param_policy(cfg),
        compile_s=round(time.time() - t0, 1),
        argument_gb=mem.argument_size_in_bytes / 1e9,
        output_gb=mem.output_size_in_bytes / 1e9,
        temp_gb=mem.temp_size_in_bytes / 1e9,
        peak_gb=(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes) / 1e9,
        scanned_flops=float(ca.get("flops", 0.0)),
    )
    if verbose:
        print(f"[{arch} x {shape_name} x {rec['mesh']}] compiled in "
              f"{rec['compile_s']}s  args={rec['argument_gb']:.2f}GB "
              f"temp={rec['temp_gb']:.2f}GB", flush=True)
        print("  memory_analysis:", mem, flush=True)
        print("  cost_analysis(flops, scanned):", rec["scanned_flops"],
              flush=True)
    if do_roofline:
        t1 = time.time()
        rl = roofline_cell(cfg, shape, mesh, chips, remat=remat,
                           seq_shard=seq_shard)
        rec["roofline"] = rl
        rec["roofline_s"] = round(time.time() - t1, 1)
        if verbose:
            t = rl["terms"]
            print(f"  roofline: compute={t['compute']*1e3:.2f}ms "
                  f"memory={t['memory']*1e3:.2f}ms "
                  f"collective={t['collective']*1e3:.2f}ms "
                  f"-> {rl['bottleneck']} | useful={rl['useful_ratio']:.2f}",
                  flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--bf16-params", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in runnable_shapes(ARCHS[arch]):
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                results.append(run_cell(arch, shape, mp,
                                        do_roofline=not args.no_roofline,
                                        remat=args.remat,
                                        bf16_params=args.bf16_params,
                                        seq_shard=args.seq_shard))
            except Exception as e:  # noqa: BLE001 — report all failures
                failures.append((arch, shape, mp, repr(e)))
                print(f"FAILED [{arch} x {shape} x multi_pod={mp}]: {e!r}",
                      flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {len(results)} records -> {args.out}")
    if failures:
        print(f"{len(failures)} FAILURES"); sys.exit(1)
    print(f"dry-run OK: {len(results)} cells compiled")


if __name__ == "__main__":
    main()
