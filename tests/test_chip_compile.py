"""The main-path Pallas kernels compile for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed here and compiles for a
chip that is described, not attached. Interpret-mode tests cannot see what
these catch — a kernel over its scoped-VMEM budget, a slice off the
tiling. The kernels compile in their on-TPU default form (bf16 MXU-REDC)
at the verify path's lane width (128-lane blocks).

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and the driver runs this
suite in several xdist workers.
"""

import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.ops import tfield as tf

NB = tf.NB
LANES = 256  # two 128-lane blocks: the grid tiles like the verify path


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def bf16_form(monkeypatch):
    """The on-TPU default form, with the persistent cache off around the
    compile (a described-chip executable cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("LIGHTHOUSE_TPU_MXU_REDC", "bf16")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    assert tf.use_mxu_redc() == "bf16"
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes > 0
    return mem


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_miller_kernel_compiles_bf16(one_chip, bf16_form):
    """The fused Miller loop needs ~21 MiB of scoped VMEM in this form —
    over the 16 MiB default scope; the kernel raises its scope."""
    from lighthouse_tpu.ops.pallas_miller import miller_loop_pallas

    p = (_sds(one_chip, (1, NB, LANES)), _sds(one_chip, (1, NB, LANES)))
    q = (_sds(one_chip, (2, NB, LANES)), _sds(one_chip, (2, NB, LANES)))
    mem = _compile(lambda p, q: miller_loop_pallas(p, q, block_b=128), p, q)
    assert mem.output_size_in_bytes >= 12 * NB * LANES * 4  # tile-padded


@pytest.mark.parametrize("group", ["G1", "G2"])
def test_window_ladder_compiles_bf16(one_chip, bf16_form, group):
    from lighthouse_tpu.ops.pallas_ladder import ladder_pallas

    w = 1 if group == "G1" else 2
    pt = tuple(_sds(one_chip, (w, NB, LANES)) for _ in range(3))
    bits = _sds(one_chip, (64, LANES))
    mem = _compile(
        lambda pt, bits: ladder_pallas(
            pt, bits, group_name=group, block_b=128
        ),
        pt,
        bits,
    )
    assert mem.output_size_in_bytes >= 3 * w * NB * LANES * 4  # tile-padded
