"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

No chip is attached here: the TPU compiler builds each program for a
*described* v5e (`topologies.get_topology_desc`), which refuses what
the chip would refuse — block shapes Mosaic cannot tile, more VMEM than
a kernel may use, a program that does not fit HBM.  Interpret-mode
parity (`test_kernels.py`, `test_kernel_properties.py`) cannot see
any of that.

Shapes are Qwen3-8B's (`configs/rcllm_qwen3_8b`: 32 query / 8 kv heads
of 128) in the dtypes serving passes: bf16 activations, float32 cached
keys and paged arenas, int masks.  The decode step is the 8-layer cut
the chip smoke serves, built from `jax.eval_shape` shapes.

The topology is described inside a module fixture, never at import:
the TPU library admits one process at a time, and every pytest-xdist
worker imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.rcllm_qwen3_8b import CONFIG as QWEN3_8B
from repro.core import engine as ENG
from repro.kernels.flash_attention.ops import mha_flash
from repro.kernels.paged_attention.ops import paged_decode_mha
from repro.kernels.selective_attention.ops import selective_mha

HQ, HKV, DH = QWEN3_8B.n_heads, QWEN3_8B.n_kv_heads, QWEN3_8B.resolved_head_dim
PAGE = 16
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip lands in the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the program"
    return compiled


def test_flash_with_kv_valid_compiles(one_chip):
    """The padded full-prefill attention (`engine._batched_forward`)."""
    B, S = 4, 512
    _compile(
        lambda q, k, v, m: mha_flash(q, k, v, kv_valid=m,
                                     q_block=ENG.PALLAS_Q_BLOCK,
                                     kv_block=ENG.PALLAS_KV_BLOCK),
        _spec((B, S, HQ, DH), BF16, one_chip),
        _spec((B, S, HKV, DH), BF16, one_chip),
        _spec((B, S, HKV, DH), BF16, one_chip),
        _spec((B, S), jnp.bool_, one_chip),
    )


def test_selective_batched_compiles(one_chip):
    """The batched selective step (`engine._sel_attn`): per-request
    query positions, key-validity masks and a block-liveness map."""
    B, S, R = 4, 512, 320
    nq = -(-R // ENG.PALLAS_Q_BLOCK)
    nk = -(-S // ENG.PALLAS_KV_BLOCK)
    _compile(
        lambda q, qp, k, v, m, lv: selective_mha(
            q, qp, k, v, m, live=lv, window=0,
            q_block=ENG.PALLAS_Q_BLOCK, kv_block=ENG.PALLAS_KV_BLOCK),
        _spec((B, R, HQ, DH), BF16, one_chip),
        _spec((B, R), jnp.int32, one_chip),
        _spec((B, S, HKV, DH), F32, one_chip),
        _spec((B, S, HKV, DH), BF16, one_chip),
        _spec((B, S), jnp.int8, one_chip),
        _spec((B, nq, nk), jnp.int32, one_chip),
    )


def test_paged_decode_compiles(one_chip):
    """The fused paged-decode kernel over a (P, L, Hkv, page, Dh) arena."""
    N, P, L, PMAX = 8, 2048, 8, 40
    _compile(
        lambda q, ak, av, pid, sp: paged_decode_mha(
            q, ak, av, pid, sp, layer=L - 1, rope_theta=QWEN3_8B.rope_theta,
            q_block=8),
        _spec((N, HQ, DH), BF16, one_chip),
        _spec((P, L, HKV, PAGE, DH), F32, one_chip),
        _spec((P, L, HKV, PAGE, DH), F32, one_chip),
        _spec((N, PMAX), jnp.int32, one_chip),
        _spec((N, PMAX, PAGE), jnp.int32, one_chip),
    )


def test_decode_step_compiles(one_chip, monkeypatch):
    """The jitted serving decode step of the chip smoke's 8-layer bf16
    cut, paged kernel and donated 2 GiB arenas included."""
    from repro.models import transformer as T
    from repro.serving import batch_engine as BE

    # the step asks the default backend whether to interpret its
    # kernels; here that is the CPU, so steer it to the chip's answer
    monkeypatch.setattr(BE, "default_interpret", lambda: False)
    cfg = dataclasses.replace(QWEN3_8B, n_layers=8, dtype="bfloat16", remat=False,
                              attn_backend="pallas")
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip), params)
    N, S, P = 8, 512, 2048
    pmax = S // PAGE + 4
    arena = _spec((P, cfg.n_layers, HKV, PAGE, DH), F32, one_chip)
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)  # noqa: E731
    compiled = BE._decode_step_jit(True).lower(
        params, i32(N), i32(N, S), i32(N), i32(N), i32(N), i32(N, pmax),
        i32(N, pmax, PAGE), arena, arena, cfg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # weights + two arenas (donated: updated in place) fit one v5e's HBM
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used
