"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see the TPU's block-shape
and layout rules; the TPU compiler, which is installed here, compiles for
a chip that is described rather than attached.  Widths: qwen-distill-1.5b
(12 query / 2 KV heads, head dim 128, page 128) for the attention kernels,
xlstm-1.3b (4 heads, head dim 512) for the mLSTM scan.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.ssm_scan.ops import mlstm_scan

H, HKV, D, PAGE = 12, 2, 128, 128          # qwen-distill-1.5b attention
XH, XD = 4, 512                            # xlstm-1.3b mLSTM heads


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_decode_attention_compiles(one_chip):
    B, maxp, P = 32, 4, 129
    text = _compiled_text(
        lambda q, k, v, bt, ln: paged_decode_attention(q, k, v, bt, ln,
                                                       interpret=False),
        one_chip, ((B, H, D), jnp.bfloat16),
        ((P, PAGE, HKV, D), jnp.bfloat16), ((P, PAGE, HKV, D), jnp.bfloat16),
        ((B, maxp), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_forward_compiles(one_chip):
    B, S = 4, 1024
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, True, None, None, None, None,
                                        False),
        one_chip, ((B, S, H, D), jnp.bfloat16), ((B, S, HKV, D), jnp.bfloat16),
        ((B, S, HKV, D), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(one_chip):
    B, C = 8, 2048
    text = _compiled_text(
        lambda q, k, v, qp, kp: decode_attention(q, k, v, qp, kp,
                                                 interpret=False),
        one_chip, ((B, H, D), jnp.bfloat16), ((B, C, HKV, D), jnp.bfloat16),
        ((B, C, HKV, D), jnp.bfloat16), ((B,), jnp.int32),
        ((B, C), jnp.int32))
    assert "tpu_custom_call" in text


def test_mlstm_scan_compiles(one_chip):
    B, S = 2, 1024
    text = _compiled_text(
        lambda q, k, v, i, f: mlstm_scan(q, k, v, i, f, interpret=False),
        one_chip, ((B, S, XH, XD), jnp.bfloat16),
        ((B, S, XH, XD), jnp.bfloat16), ((B, S, XH, XD), jnp.bfloat16),
        ((B, S, XH), jnp.float32), ((B, S, XH), jnp.float32))
    assert "tpu_custom_call" in text
