"""Dry-run machinery at test scale: an 8-device (2 data x 4 model) mesh in a
subprocess, lowering + compiling train/prefill/decode for reduced variants
of three families, plus the roofline HLO parser on real compiled text.

(The full 512-device x 10-arch matrix runs via `python -m
repro.launch.dryrun --both-meshes`; its results are in results/dryrun/.)
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.roofline import (
    Roofline, collective_bytes, per_program, _shape_bytes,
)


def test_shape_bytes_parser():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[2,4,8]") == 64 * 2
    assert _shape_bytes("(f32[16], s32[4])") == 16 * 4 + 4 * 4
    assert _shape_bytes("pred[]") == 1
    assert _shape_bytes("u8[10]") == 10


def test_collective_parser_on_synthetic_hlo():
    hlo = textwrap.dedent("""
      %ag = f32[64,128] all-gather(f32[4,128] %x), replica_groups={}
      %ar = bf16[256] all-reduce(bf16[256] %y), to_apply=%sum
      %rs = f32[8] reduce-scatter(f32[128] %z), dimensions={0}
      %cp = f32[32] collective-permute(f32[32] %w), source_target_pairs={{0,1}}
      %a2a = f32[16,16] all-to-all(f32[16,16] %v), dimensions={0}
      %notacoll = f32[99] add(f32[99] %a, f32[99] %b)
    """)
    got = collective_bytes(hlo)
    assert got["all-gather"] == 64 * 128 * 4
    assert got["all-reduce"] == 256 * 2
    assert got["reduce-scatter"] == 8 * 4
    assert got["collective-permute"] == 32 * 4
    assert got["all-to-all"] == 16 * 16 * 4
    assert "add" not in got


def test_roofline_dominant_term():
    r = Roofline(flops_total=1e18, hbm_bytes_total=1e12, collective_bytes_per_chip=1e9, chips=256)
    assert r.dominant == "compute"
    r2 = Roofline(flops_total=1e12, hbm_bytes_total=1e15, collective_bytes_per_chip=1e9, chips=256)
    assert r2.dominant == "memory"
    r3 = Roofline(flops_total=1e12, hbm_bytes_total=1e9, collective_bytes_per_chip=1e13, chips=256)
    assert r3.dominant == "collective"


def test_per_program_splits_the_fdsvrg_result():
    """The FD-SVRG dry run holds one entry per program under each
    per-compile key; a one-program result passes through as it is."""
    rl = Roofline(1e12, 1e9, 1e6, 8).as_dict()
    one = {"arch": "a", "shape": "s", "compile_s": 1.0, "roofline": rl}
    assert per_program(one) == [one]
    split = {"arch": "fdsvrg", "shape": "outer", "ok": True,
             "compile_s": {"p": 1.0, "q": 2.0},
             "memory_analysis": {"p": {"temp_size_in_bytes": 1}, "q": {}},
             "collectives": {"p": {"all-reduce": 4}, "q": {}},
             "roofline": {"p": rl, "q": rl}}
    parts = per_program(split)
    assert [r["shape"] for r in parts] == ["outer/p", "outer/q"]
    assert [r["compile_s"] for r in parts] == [1.0, 2.0]
    assert parts[0]["memory_analysis"] == {"temp_size_in_bytes": 1}
    assert parts[0]["collectives"] == {"all-reduce": 4}
    assert all(r["roofline"] == rl and r["ok"] for r in parts)


_SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, reduced_config, INPUT_SHAPES
    from repro.configs.base import InputShape
    from repro.launch.roofline import collective_bytes
    from repro.launch.dryrun import _lower_combo, _rules_overrides
    from repro.models import transformer

    from repro.dist.compat import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))

    shapes = {
        "train": InputShape("t", 64, 8, "train"),
        "prefill": InputShape("p", 64, 4, "prefill"),
        "decode": InputShape("d", 64, 8, "decode"),
    }
    for arch in ("smollm-360m", "granite-moe-1b-a400m", "jamba-v0.1-52b"):
        cfg = reduced_config(get_config(arch))
        cfg = dataclasses.replace(cfg, ssm_chunk=16)
        for kind, shape in shapes.items():
            ctx = transformer.make_ctx(mesh, cfg, overrides=_rules_overrides(shape))
            lowered = _lower_combo(cfg, shape, mesh, ctx, 2 if kind == "train" else 1)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            coll = collective_bytes(compiled.as_text())
            assert float(cost.get("flops", 0)) > 0, (arch, kind)
            assert compiled.memory_analysis() is not None
            # sharded models must actually communicate
            assert sum(coll.values()) > 0, (arch, kind, coll)
            print(f"OK {arch} {kind} coll={sorted(coll)}")
    print("SMALL-DRYRUN-OK")
""")


def test_small_mesh_dryrun_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SUB], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SMALL-DRYRUN-OK" in proc.stdout


@pytest.mark.slow
def test_roofline_auto_populates_and_measures_at_least_one_combo(tmp_path, monkeypatch):
    """Regression guard for the bench that measured nothing: on a fresh
    checkout (empty results/dryrun/) `benchmarks.roofline.run` must
    auto-invoke the dryrun --smoke combo (in a subprocess, so XLA_FLAGS
    land before jax initializes) and come back with >= 1 OK row instead
    of silently rendering an empty table."""
    import benchmarks.roofline as roofline

    monkeypatch.setattr(roofline, "DRYRUN_DIR", str(tmp_path / "dryrun"))
    _, rows = roofline.run()
    ok = sum(1 for r in rows if r and r[3] != "FAIL")
    assert ok >= 1, rows
