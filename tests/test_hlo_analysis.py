"""HLO roofline analyzer: flops/trip-count/collective parsing validated
against analytic counts on small lowered programs (subprocess: needs >1
device for SPMD collectives)."""
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.launch.hlo_analysis import (
    _shape_dims,
    _shapes_bytes,
    analyze_hlo,
)


def test_shape_parsing():
    assert _shapes_bytes("f32[16,256]{1,0}") == 16 * 256 * 4
    assert _shapes_bytes("bf16[8]{0}") == 16
    assert _shapes_bytes("(f32[4,4]{1,0}, s32[2]{0})") == 64 + 8
    assert _shape_dims("f32[16,256]{1,0}") == ("f32", [16, 256])
    assert _shapes_bytes("pred[]") == 1


def test_wire_factors_on_synthetic_hlo():
    hlo = textwrap.dedent("""
    ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {
      %p0 = f32[64,64]{1,0} parameter(0)
      %ag = f32[64,64]{1,0} all-gather(%p0), replica_groups=[4,4]<=[16]
      %ar = f32[64,64]{1,0} all-reduce(%ag), replica_groups=[2,8]<=[16]
      ROOT %out = f32[64,64]{1,0} add(%ar, %ag)
    }
    """)
    st = analyze_hlo(hlo, total_devices=16)
    b = 64 * 64 * 4
    expect = b * (3 / 4) + b * 2 * (7 / 8)
    assert abs(st.collective_bytes - expect) < 1e-6
    assert st.collective_counts == {"all-gather": 1, "all-reduce": 1}


def test_while_trip_count_scaling():
    hlo = textwrap.dedent("""
    %body.1 (p: f32[8,8]) -> f32[8,8] {
      %p = f32[8,8]{1,0} parameter(0)
      ROOT %d = f32[8,8]{1,0} dot(%p, %p), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }
    %cond.1 (p: f32[8,8]) -> pred[] {
      %p = f32[8,8]{1,0} parameter(0)
      ROOT %c = pred[] constant(false)
    }
    ENTRY %main.2 (p0: f32[8,8]) -> f32[8,8] {
      %p0 = f32[8,8]{1,0} parameter(0)
      ROOT %w = f32[8,8]{1,0} while(%p0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
    }
    """)
    st = analyze_hlo(hlo, default_trip_count=1)
    assert st.flops == 5 * 2 * 8 * 8 * 8  # 5 trips × 2MNK


_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def f(ws, x):
    def body(x, w):
        return jax.nn.relu(x @ w), None
    x, _ = jax.lax.scan(body, x, ws)
    return x.sum()

g = jax.jit(jax.grad(f), in_shardings=(
    NamedSharding(mesh, P(None, "data", "model")), NamedSharding(mesh, P("data", None))))
L, B, D = 4, 32, 64
comp = g.lower(jax.ShapeDtypeStruct((L, D, D), jnp.float32),
               jax.ShapeDtypeStruct((B, D), jnp.float32)).compile()
st = analyze_hlo(comp.as_text(), default_trip_count=L, total_devices=8)
# analytic: fwd L×2BDD; bwd ≈ 2×fwd (dx + dw per layer) → 3× total, /8 devices
analytic = 3 * L * 2 * B * D * D / 8
ratio = st.flops / analytic
assert 0.6 < ratio < 1.7, (st.flops, analytic, ratio)
assert st.collective_bytes > 0
print("ratio ok", ratio)
"""


def test_scan_flops_match_analytic_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUB], capture_output=True,
                         text=True, cwd=Path(__file__).resolve().parents[1],
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
