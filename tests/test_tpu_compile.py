"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing here runs: each test lowers a kernel with ``interpret=False``
and hands it to the TPU compiler that ships with jaxlib, for a chip that
is described rather than attached.  That catches what interpret mode
cannot — block shapes Mosaic's tiling rules refuse, VMEM overruns — at
the widths the engine and the eval plane use.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen2_1_5b import CONFIG as QWEN2
from repro.kernels.decode_attention.kernel import (decode_attention,
                                                   decode_attention_paged)
from repro.kernels.matmul.kernel import matmul
from repro.search.tasks import TASKS

# qwen2-1.5b decode at the engine's batch: 12 query heads over 2 KV
# heads of 128, 16-token pages, a 4096-page arena, 2048-token rows
B, PAGE, PAGES, MAX_LEN = 16, 16, 4096, 2048
H, KV, DH = QWEN2.num_heads, QWEN2.num_kv_heads, QWEN2.head_dim


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # a Mosaic kernel, not the interpreter's XLA loop
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("tile", [(128, 128, 128), (256, 256, 512)])
def test_matmul_template_compiles_at_task_size(one_chip, tile):
    task = TASKS["T6"]
    bm, bn, bk = tile
    _compile(lambda a, b: matmul(a, b, bm=bm, bn=bn, bk=bk,
                                 mask=task.mask, interpret=False),
             one_chip, ((task.M, task.K), jnp.float32),
             ((task.K, task.N), jnp.float32))


def test_matmul_template_refuses_unaligned_tile(one_chip):
    """(64, 64, 32) breaks the (8, 128) tiling rule on the last block
    dimension; the eval plane reports this as a build failure."""
    task = TASKS["T6"]
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _compile(lambda a, b: matmul(a, b, bm=64, bn=64, bk=32,
                                     mask=task.mask, interpret=False),
                 one_chip, ((task.M, task.K), jnp.float32),
                 ((task.K, task.N), jnp.float32))


def test_paged_decode_kernel_compiles_at_qwen2_widths(one_chip):
    nb = MAX_LEN // PAGE
    compiled = _compile(
        lambda q, k, v, t, n: decode_attention_paged(q, k, v, t, n,
                                                     interpret=False),
        one_chip, ((B, H, DH), jnp.bfloat16),
        ((PAGES, PAGE, KV, DH), jnp.bfloat16),
        ((PAGES, PAGE, KV, DH), jnp.bfloat16),
        ((B, nb), jnp.int32), ((B,), jnp.int32))
    # the arenas are read in place: no relayout copy of either one
    arena = 2 * PAGES * PAGE * KV * DH * 2
    assert compiled.memory_analysis().temp_size_in_bytes < arena // 8


def test_dense_decode_kernel_compiles_at_qwen2_widths(one_chip):
    _compile(lambda q, k, v, n: decode_attention(q, k, v, n,
                                                 interpret=False),
             one_chip, ((B, H, DH), jnp.bfloat16),
             ((B, MAX_LEN, KV, DH), jnp.bfloat16),
             ((B, MAX_LEN, KV, DH), jnp.bfloat16), ((B,), jnp.int32))
