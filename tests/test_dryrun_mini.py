"""Mini dry-run in a subprocess: the dryrun driver's build_step path on an
8-virtual-device mesh with reduced configs — one arch per family plus the
collective-bytes parser unit tests."""

import os
import subprocess
import sys

import pytest

from repro.launch.dryrun import collective_bytes

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_host_mesh
from repro.launch.dryrun import build_step, collective_bytes

mesh = make_host_mesh(data=2, model=4)
cases = [
    ("qwen3-14b", InputShape("t", 256, 8, "train")),
    ("deepseek-v3-671b", InputShape("t", 256, 8, "train")),
    ("xlstm-125m", InputShape("d", 256, 8, "decode")),
    ("hymba-1.5b", InputShape("p", 256, 8, "prefill")),
]
for arch, shape in cases:
    cfg = get_config(arch, reduced=True)
    if cfg.moe:
        from dataclasses import replace
        cfg = replace(cfg, moe=replace(cfg.moe, impl="capacity"))
    fn, arg_specs, (ins, outs), donate = build_step(cfg, mesh, shape)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                           donate_argnums=donate).lower(*arg_specs).compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    assert cost.get("flops", 0) > 0, (arch, cost)
    print(f"MINI_OK {arch} {shape.kind} flops={cost.get('flops'):.3e} "
          f"coll={sum(coll.values()):.3e}")
print("ALL_MINI_OK")
"""


@pytest.mark.slow
def test_mini_dryrun_per_family():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert "ALL_MINI_OK" in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


def test_collective_bytes_parser():
    hlo = """
  %ar = bf16[16,128]{1,0} all-reduce(bf16[16,128]{1,0} %x), replica_groups={}
  %ag.1 = f32[64,256]{1,0} all-gather(f32[16,256]{1,0} %y), dimensions={0}
  %rs = f32[4,256]{1,0} reduce-scatter(f32[16,256]{1,0} %z), dimensions={0}
  %cp = u8[1024]{0} collective-permute(u8[1024]{0} %w)
  %add = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 128 * 2
    assert got["all-gather"] == 64 * 256 * 4
    assert got["reduce-scatter"] == 4 * 256 * 4
    assert got["collective-permute"] == 1024
    assert "add" not in got


def test_collective_bytes_empty():
    assert collective_bytes("%x = f32[2] add(...)") == {}
