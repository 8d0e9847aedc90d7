"""Ahead-of-time compiles for a TPU v5e at real widths, with no chip attached.

The TPU compiler is installed with jax, so it can compile for a described
(``v5e:2x2``) topology and refuse what the chip would refuse: a block past
VMEM, a slice off the tiling. The topology is described inside a fixture,
never at import, so that only the pytest worker given this file loads the
TPU library; keep every such test in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, segment_rf
from repro.kernels import span_reorder as SRK

# One region of chip_smoke.py's pack: IncrementalOrderer's slots_per_region
# for its 8,446,088 distinct edges at k=8 (50% slack, 25% headroom, 256-aligned).
SMOKE_SPAN_CAP = 1_979_648
SMOKE_VERTICES = 1 << 19


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("width", [1 << 12, 1 << 20, 1 << 25])
def test_segment_rf_compiles_for_v5e(one_chip, width):
    ids = jax.ShapeDtypeStruct((3, width), jnp.int32, sharding=one_chip)
    fn = functools.partial(segment_rf.segment_distinct_counts, interpret=False)
    compiled = jax.jit(fn).lower(ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_span_repair_objective_compiles_for_v5e_at_smoke_span(one_chip, monkeypatch):
    # The program asks the backend whether to interpret the kernel; here the
    # backend is the CPU, so tell it the answer the chip would give.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cap = SMOKE_SPAN_CAP
    i32 = functools.partial(jax.ShapeDtypeStruct, (cap,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one_chip)
    fn = functools.partial(
        SRK.select_span_order_device,
        num_vertices=SMOKE_VERTICES,
        ks=SRK.eval_ks(4, 128),
        use_pallas=True,
    )
    compiled = jax.jit(fn).lower(i32(), i32(), valid, candidate=i32()).compile()
    assert "tpu_custom_call" in compiled.as_text()
