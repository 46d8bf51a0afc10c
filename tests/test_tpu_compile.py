"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs here: each test lowers a kernel for one chip of a ``v5e:2x2``
topology that is described, not attached, and compiles it with the TPU's
own compiler, which refuses what interpret mode accepts (unaligned block
shapes, gathers Mosaic cannot lower, blocks that overflow scoped VMEM).
The topology is described inside a fixture, never at import, so that only
the test worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

# entity banks at e5-mistral's width and a deployment's row count
D, N, Q = 4096, 65536, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("k", [16, 128])
def test_topk_fp32_compiles_for_v5e(one_chip, k):
    from repro.kernels.topk_similarity import topk_similarity
    _compile(lambda q, db, v: topk_similarity(q, db, v, k), one_chip,
             ((Q, D), jnp.float32), ((N, D), jnp.float32), ((N,), jnp.int32))


# the integer kernels must not take up an ambient "highest" precision,
# which Mosaic refuses for int8 operands
@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("kprime", [64, 128])
def test_topk_int8_phase1_compiles_for_v5e(one_chip, kprime, precision):
    from repro.kernels.topk_similarity_i8 import Int8Rows, topk_i8_phase1
    with jax.default_matmul_precision(precision):
        _compile(lambda q, t, c, s, e, v: topk_i8_phase1(
            q, t, Int8Rows(c, s, e), v, kprime), one_chip,
            ((Q, D), jnp.int8), ((Q,), jnp.float32), ((N, D), jnp.int8),
            ((N,), jnp.float32), ((N,), jnp.float32), ((N,), jnp.int32))


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("kprime", [64, 128])
def test_topk_int4_phase1_compiles_for_v5e(one_chip, kprime, precision):
    from repro.kernels.topk_similarity_i4 import Int4Rows, topk_i4_phase1
    with jax.default_matmul_precision(precision):
        _compile(lambda q, t, p, s, e, v: topk_i4_phase1(
            q, t, Int4Rows(p, s, e), v, kprime), one_chip,
            ((Q, D), jnp.int8), ((Q,), jnp.float32), ((N, D // 2), jnp.int8),
            ((N,), jnp.float32), ((N,), jnp.float32), ((N,), jnp.int32))


def test_flash_attention_compiles_for_v5e_at_verifier_prefill(one_chip):
    """Qwen2.5-VL-7B's prefill: 16 candidates of 1024 patches + 24 tokens,
    28 query heads over 4 KV heads of width 128."""
    from repro.kernels.flash_attention import flash_attention
    B, S = 16, 1048
    _compile(lambda q, k, v, qp, kp: flash_attention(q, k, v, qp, kp),
             one_chip, ((B, S, 28, 128), jnp.bfloat16),
             ((B, S, 4, 128), jnp.bfloat16), ((B, S, 4, 128), jnp.bfloat16),
             ((B, S), jnp.int32), ((B, S), jnp.int32))
