import os
import sys

# The flag must land before jax initializes, hence before any jax import —
# callers (benchmarks.roofline auto-populate, the tier-1 smoke test) run
# this module in a SUBPROCESS for the same reason.  --smoke lowers one
# reduced combo on an 8-device mesh; forcing 512 host devices for that
# would slow the compile for nothing.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8"
    if "--smoke" in sys.argv
    else "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production meshes, with ShapeDtypeStruct inputs
(no allocation), and record memory / cost / collective analysis for the
roofline tables (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                      # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
      --shape train_4k [--multi-pod] [--fdsvrg]
Results land in results/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.launch import roofline as roofline_lib
from repro.launch.inputs import (
    decode_token_specs,
    prefill_batch_specs,
    train_batch_specs,
)
from repro.launch.mesh import chips, make_production_mesh
from repro.models import transformer
from repro.optim.optimizers import adamw
from repro.sharding.specs import ShardingCtx
from repro.train.loop import TrainSettings, init_state, make_train_step, state_specs
from repro.train.serve import make_serve_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

# per-arch gradient-accumulation (microbatching) for train_4k: keeps the
# per-device activation footprint inside v5e HBM at global batch 256
GRAD_ACCUM = {
    "qwen3-14b": 8, "jamba-v0.1-52b": 8, "gemma2-9b": 8,
    "minitron-4b": 4, "paligemma-3b": 4, "musicgen-large": 4,
    "mamba2-2.7b": 4, "olmoe-1b-7b": 4,
    "smollm-360m": 2, "granite-moe-1b-a400m": 2,
}

# pure full-attention archs skip long_500k (DESIGN.md §5 "Shape skips")
LONG_CONTEXT_ARCHS = {a for a, c in ARCHS.items() if c.supports_long_context}


def _sh(mesh, ctx: ShardingCtx, *names):
    return NamedSharding(mesh, ctx.spec(*names))


def _batch_shardings(cfg, mesh, ctx, batch_specs: dict, grad_accum: int):
    lead = (None,) if grad_accum > 1 else ()

    def names_for(key: str, rank: int):
        body = {
            "tokens": ("batch", None, None),
            "labels": ("batch", None, None),
            "patch_embeds": ("batch", None, None),
        }[key]
        return lead + body[: rank - len(lead)]

    return {
        k: NamedSharding(mesh, ctx.spec(*names_for(k, v.ndim)))
        for k, v in batch_specs.items()
    }


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend may not support it
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for attr in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        if hasattr(ma, attr):
            out[attr] = int(getattr(ma, attr))
    if not out:
        out["repr"] = str(ma)
    return out


def _rules_overrides(shape: InputShape) -> dict:
    if shape.name == "long_500k":
        # batch=1: retire the batch axes, spread the KV cache over data+model
        return {"batch": None, "seq_kv": ("data", "model")}
    return {}


def _lower_combo(cfg: ModelConfig, shape: InputShape, mesh, ctx, grad_accum: int):
    """Build + lower the right step function for one combo (no compile)."""
    tp = mesh.shape["model"]
    if shape.kind == "train":
        ga = grad_accum
        opt = adamw(3e-4)
        settings = TrainSettings(grad_accum=ga)
        state_sds = jax.eval_shape(
            lambda: init_state(cfg, jax.random.key(0), opt, tp)
        )
        sspecs = state_specs(state_sds, cfg, ctx)
        state_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), sspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        batch_sds = train_batch_specs(cfg, shape, ga)
        batch_sh = _batch_shardings(cfg, mesh, ctx, batch_sds, ga)
        step = make_train_step(cfg, ctx, opt, settings)
        jitted = jax.jit(
            step, in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None)
        )
        lowered = jitted.lower(state_sds, batch_sds)
    elif shape.kind == "prefill":
        params_sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.key(0), tp)
        )
        pspecs = transformer.param_specs(params_sds, cfg, ctx, zero1=False)
        params_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        batch_sds = prefill_batch_specs(cfg, shape)
        batch_sh = _batch_shardings(cfg, mesh, ctx, batch_sds, 1)

        def prefill_fn(params, batch):
            return transformer.prefill(params, cfg, batch, shape.seq_len, ctx)

        jitted = jax.jit(prefill_fn, in_shardings=(params_sh, batch_sh))
        lowered = jitted.lower(params_sds, batch_sds)
    else:  # decode
        params_sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.key(0), tp)
        )
        pspecs = transformer.param_specs(params_sds, cfg, ctx, zero1=False)
        params_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        cache_sds = jax.eval_shape(
            lambda: transformer.init_cache(
                cfg, shape.global_batch, shape.seq_len, ctx, tp
            )
        )
        cspecs = transformer.cache_specs(cfg, ctx)
        cache_sh = tuple(
            {k: NamedSharding(mesh, v) for k, v in c.items()} for c in cspecs
        )
        tok_sds = decode_token_specs(cfg, shape)
        tok_sh = NamedSharding(
            mesh, ctx.spec(*(("batch",) + (None,) * (tok_sds.ndim - 1)))
        )
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        serve_step = make_serve_step(cfg, ctx)
        jitted = jax.jit(
            serve_step,
            in_shardings=(params_sh, cache_sh, tok_sh, NamedSharding(mesh, P())),
            out_shardings=(None, None, cache_sh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_sds, cache_sds, tok_sds, pos_sds)

    return lowered


def _cost_tuple(compiled) -> tuple[float, float, float]:
    """(flops_per_dev, bytes_per_dev, collective_bytes_per_dev)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = roofline_lib.collective_bytes(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(sum(coll.values())),
    )


# depth pair used for the unrolled roofline extrapolation (costs are exactly
# linear in depth under full unroll, so the smallest pair suffices)
_ROOFLINE_DEPTHS = (1, 2)


def dryrun_one(arch: str, shape_name: str, multi_pod: bool) -> dict:
    """One (arch x shape x mesh) combination.

    Two kinds of compile:
      1. PRODUCTION compile — full depth, scans as scans, real grad-accum:
         proves lowering/SPMD coherence and yields memory_analysis().
      2. ROOFLINE compiles — depth R=2 and R=4 variants with every scan
         fully unrolled (cost_analysis counts while bodies once; unrolled
         trip-1 loops are exact), ga=1; FLOPs/bytes/collective-bytes are
         exactly linear in depth, so extrapolate to the full depth.
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = transformer.make_ctx(mesh, cfg, overrides=_rules_overrides(shape))
    ga = GRAD_ACCUM[arch] if shape.kind == "train" else 1

    # --- production compile ---
    t0 = time.time()
    lowered = _lower_combo(cfg, shape, mesh, ctx, ga)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = _memory_analysis_dict(compiled)
    coll_prod = roofline_lib.collective_bytes(compiled.as_text())

    if multi_pod:
        # multi-pod pass proves the "pod" axis shards (lower+compile);
        # the roofline table is single-pod only (see brief) — skip the
        # unrolled roofline compiles here.
        return {
            "arch": arch, "shape": shape_name, "mesh": "2x16x16",
            "chips": chips(mesh),
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory_analysis": mem,
            "collectives_production_hlo": coll_prod,
            "roofline": None,
            "grad_accum": ga if shape.kind == "train" else None,
            "ok": True,
        }

    # --- roofline compiles (reduced depth, fully unrolled, ga=1) ---
    import dataclasses as _dc

    from repro.models.unroll import unrolled

    plen = len(cfg.pattern)
    costs = {}
    with unrolled():
        for rr in _ROOFLINE_DEPTHS:
            cfg_r = _dc.replace(cfg, name=f"{cfg.name}@r{rr}", num_layers=rr * plen)
            # ga=1 keeps the unrolled roofline compile tractable; the one
            # thing it misses vs production is (ga-1) extra parameter
            # re-reads per step, corrected analytically below.
            lr = _lower_combo(cfg_r, shape, mesh, ctx, 1)
            costs[rr] = _cost_tuple(lr.compile())
    r_full = cfg.num_repeats
    r1, r2 = _ROOFLINE_DEPTHS
    per_layer = tuple((b - a) / (r2 - r1) for a, b in zip(costs[r1], costs[r2]))
    full = tuple(a + (r_full - r1) * d for a, d in zip(costs[r1], per_layer))
    flops_dev, bytes_dev, coll_dev = full
    if shape.kind == "train" and ga > 1:
        tp = mesh.shape["model"]
        bytes_dev += (ga - 1) * cfg.param_count() * 2 / tp  # bf16 re-reads

    nchips = chips(mesh)
    rf = roofline_lib.Roofline(
        flops_total=flops_dev * nchips,
        hbm_bytes_total=bytes_dev * nchips,
        collective_bytes_per_chip=coll_dev,
        chips=nchips,
    )
    mf = roofline_lib.model_flops(cfg, shape)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": nchips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "collectives_production_hlo": coll_prod,
        "roofline": rf.as_dict(),
        "roofline_depths": {str(r): costs[r] for r in costs},
        "model_flops": mf,
        "useful_flops_ratio": mf / rf.flops_total if rf.flops_total else None,
        "grad_accum": ga if shape.kind == "train" else None,
        "ok": True,
    }
    return result


def dryrun_fdsvrg(multi_pod: bool) -> dict:
    """The paper's own workload at kdd2010 scale: the mesh's full-gradient
    and inner-epoch programs with w feature-sharded over all chips.  The
    full gradient reads the AOT slab as its one row group.  Each program
    has its own entry under every per-compile key."""
    from repro.core.fdsvrg_shardmap import (
        FDSVRGShardedConfig, make_fullgrad, make_inner_epoch,
    )
    from repro.data.block_csr import RowGroups, aot_nnz_budget

    mesh = make_production_mesh(multi_pod=multi_pod)
    q = chips(mesh)
    d = 29_890_095  # kdd2010 dimensionality
    d_pad = ((d + q - 1) // q) * q
    n, nnz, m, u = 65_536, 32, 256, 64  # instance window per outer iteration
    cfg = FDSVRGShardedConfig(
        dim=d_pad, num_instances=n, nnz_max=nnz, eta=0.1,
        inner_steps=m, batch_size=u,
    )
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    bnnz = aot_nnz_budget(nnz, q)  # block-local stacked rows, nnz/q + skew slack
    w = jax.ShapeDtypeStruct((d_pad,), jnp.float32)
    idx = jax.ShapeDtypeStruct((q * n, bnnz), jnp.int32)
    val = jax.ShapeDtypeStruct((q * n, bnnz), jnp.float32)
    lab = jax.ShapeDtypeStruct((n,), jnp.float32)
    s0 = jax.ShapeDtypeStruct((n,), jnp.float32)
    rows = jax.ShapeDtypeStruct((n,), jnp.int32)
    samples = jax.ShapeDtypeStruct((m, u), jnp.int32)
    programs = {
        "mesh_full_grad": (
            make_fullgrad(mesh, cfg, feature_axes=axes),
            (w, RowGroups((idx,), (val,), (rows,), rows), lab),
        ),
        "mesh_inner_epoch": (
            make_inner_epoch(mesh, cfg, feature_axes=axes),
            (w, w, s0, idx, val, lab, samples),
        ),
    }
    result = {
        "arch": "fdsvrg-kdd2010",
        "shape": f"outer(N={n},M={m},u={u})",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": q,
        "compile_s": {},
        "memory_analysis": {},
        "collectives": {},
        "roofline": {},
        "ok": True,
    }
    for name, (step, args) in programs.items():
        t0 = time.time()
        compiled = step.lower(*args).compile()
        result["compile_s"][name] = round(time.time() - t0, 2)
        result["memory_analysis"][name] = _memory_analysis_dict(compiled)
        result["collectives"][name] = roofline_lib.collective_bytes(
            compiled.as_text()
        )
        result["roofline"][name] = roofline_lib.from_compiled(compiled, q).as_dict()
    return result


def dryrun_smoke() -> dict:
    """ONE reduced arch x mesh combo, fast enough for CI: smollm-360m at
    CPU-smoke scale on a 2x4 host mesh (the tests/test_dryrun_small.py
    shape).  Gives benchmarks.roofline at least one real compiled row to
    render when results/dryrun/ is empty."""
    import dataclasses

    from repro.configs import reduced_config
    from repro.dist.compat import make_mesh

    arch = "smollm-360m"
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(reduced_config(get_config(arch)), ssm_chunk=16)
    shape = InputShape("train_64", 64, 8, "train")
    ctx = transformer.make_ctx(mesh, cfg, overrides=_rules_overrides(shape))
    t0 = time.time()
    lowered = _lower_combo(cfg, shape, mesh, ctx, 1)
    compiled = lowered.compile()
    rf = roofline_lib.from_compiled(compiled, chips=8)
    return {
        "arch": f"{arch}-reduced",
        "shape": "train(seq=64,batch=8)",
        "mesh": "2x4",
        "chips": 8,
        "compile_s": round(time.time() - t0, 2),
        "memory_analysis": _memory_analysis_dict(compiled),
        "collectives": roofline_lib.collective_bytes(compiled.as_text()),
        "roofline": rf.as_dict(),
        "ok": True,
    }


def combos():
    for arch in sorted(ARCHS):
        for shape_name in INPUT_SHAPES:
            if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                continue
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fdsvrg", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="one reduced arch x mesh combo on 8 host devices")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out_dir = args.out_dir or os.path.abspath(RESULTS_DIR)
    os.makedirs(out_dir, exist_ok=True)

    if args.smoke:
        path = os.path.join(out_dir, "smoke__train_64__2x4.json")
        try:
            res = dryrun_smoke()
            rl = res["roofline"]
            print(f"[OK] smoke: compile={res['compile_s']}s "
                  f"dominant={rl['dominant']}", flush=True)
            failures = 0
        except Exception as e:
            res = {
                "arch": "smollm-360m-reduced", "shape": "train(seq=64,batch=8)",
                "mesh": "2x4", "ok": False, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"[FAIL] smoke: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            failures = 1
        with open(path, "w") as f:
            json.dump(res, f, indent=2, default=str)
        print(f"done; {failures} failures", flush=True)
        return failures

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    jobs = []
    if args.fdsvrg:
        jobs = [("fdsvrg", None)]
    elif args.arch and args.shape:
        jobs = [(args.arch, args.shape)]
    elif args.arch:
        jobs = [(a, s) for a, s in combos() if a == args.arch]
    else:
        jobs = list(combos())

    failures = 0
    for arch, shape_name in jobs:
        for mp in meshes:
            mesh_tag = "2x16x16" if mp else "16x16"
            tag = f"{arch}__{shape_name or 'paper'}__{mesh_tag}"
            path = os.path.join(out_dir, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                try:
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("ok"):
                        print(f"[SKIP] {tag}: already done", flush=True)
                        continue
                except Exception:
                    pass
            try:
                if arch == "fdsvrg":
                    res = dryrun_fdsvrg(mp)
                else:
                    res = dryrun_one(arch, shape_name, mp)
                parts = roofline_lib.per_program(res)
                for part in parts:
                    label = tag if len(parts) == 1 else f"{tag} {part['shape']}"
                    rl = part.get("roofline")
                    if rl:
                        print(
                            f"[OK] {label}: compile={part['compile_s']}s "
                            f"compute={rl['compute_s']:.4f}s memory={rl['memory_s']:.4f}s "
                            f"collective={rl['collective_s']:.4f}s dominant={rl['dominant']}",
                            flush=True,
                        )
                    else:
                        print(f"[OK] {label}: compile={part['compile_s']}s "
                              f"(multi-pod proof; roofline is single-pod)", flush=True)
            except Exception as e:
                failures += 1
                res = {
                    "arch": arch, "shape": shape_name, "mesh": mesh_tag,
                    "ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:300]}", flush=True)
            with open(path, "w") as f:
                json.dump(res, f, indent=2, default=str)
    print(f"done; {failures} failures", flush=True)
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
