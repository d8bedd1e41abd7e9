"""The FL path's Pallas kernels compile for a TPU v5e at the paper CNN's width.

Interpret mode (every other test) runs the blocked algorithm on the CPU and
cannot see what the TPU compiler refuses: blocks that break the (8, 128)
tiling, tiles that overflow VMEM, arrays that overflow HBM, ops with no
Mosaic lowering.  These tests compile each kernel with ``interpret=False``
for a *described* ``v5e:2x2`` topology — no chip attached — and check that
the kernel is really in the program (``tpu_custom_call``).

D is the parameter count of ``PaperCNN(side=32, channels=3, num_classes=10,
num_fc=3)``, P the paper's cohort size.  The MoE kernel compiles at
DeepSeek-V2-Lite's expert widths for a 16,384-token cohort step, and MLA's
causal attention at its head sizes for the same step.  The topology is described inside a
module fixture (never at import), so only the worker that runs this file
loads the TPU compiler.
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import aggregate, gram, mla_attention, moe_gmm, topk_mask

D = 595_914      # PaperCNN CIFAR-10 variant
P = 10           # clients per round


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    devices = np.asarray(topo.devices).reshape(2, 2)
    return Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_gram_compiles(one_chip):
    _assert_kernel(gram.gram.lower(_f32((P, D), one_chip), interpret=False))


def test_cross_gram_compiles_one_chip(one_chip):
    """The relationship block's ⟨u_k, v_j⟩ over the M=100 update map."""
    _assert_kernel(gram.cross_gram.lower(
        _f32((P, D), one_chip), _f32((100, D), one_chip), interpret=False,
    ))


def test_cross_gram_compiles_fleet_map_d_sharded(mesh4):
    """M=10⁴: the (M, D) map is 22 GiB, so it lives D-sharded over four
    chips, and each chip runs the kernel on its (M, D/4) shard — the shape
    ``sharded_relationship_dots`` hands it.  The row tiling keeps one V tile
    in VMEM whatever M is."""
    axes = ("data", "model")
    d_pad = -(-D // 4) * 4
    cols = NamedSharding(mesh4, PartitionSpec(None, axes))

    def local(u, v):
        return jax.lax.psum(gram.cross_gram(u, v, interpret=False), axes)

    program = jax.jit(jax.shard_map(
        local, mesh=mesh4, in_specs=(PartitionSpec(None, axes),) * 2,
        out_specs=PartitionSpec(None, None), check_vma=False,
    ))
    _assert_kernel(program.lower(_f32((P, d_pad), cols), _f32((10_000, d_pad), cols)))


def test_weighted_aggregate_compiles(one_chip):
    _assert_kernel(aggregate.weighted_aggregate.lower(
        _f32((D,), one_chip), _f32((P, D), one_chip), _f32((P,), one_chip),
        interpret=False,
    ))


@pytest.mark.parametrize("rows", [1, P])
def test_topk_mask_rows_compiles(one_chip, rows):
    """Fedcom's update transform (P rows) and the 1-D form (one row)."""
    _assert_kernel(topk_mask.topk_mask_rows.lower(
        _f32((rows, D), one_chip), keep_frac=0.1, interpret=False,
    ))


@pytest.mark.parametrize("k,n,transpose", [(2048, 1408, False), (1408, 2048, False),
                                           (2048, 1408, True), (1408, 2048, True)])
def test_moe_gmm_compiles_at_deepseek_widths(one_chip, k, n, transpose):
    """DeepSeek-V2-Lite's grouped expert products: 16,384 tokens x top-6
    rows over 64 experts; gate/up (2048 -> 1408) and down (1408 -> 2048),
    and the backward pass's transposed dX products."""
    m = moe_gmm.padded_rows(16_384 * 6)
    rhs = (64, n, k) if transpose else (64, k, n)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    _assert_kernel(moe_gmm.gmm.lower(
        bf16((m, k)), bf16(rhs), jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip),
        transpose_rhs=transpose, interpret=False,
    ))


def test_mla_attention_compiles_at_deepseek_widths(one_chip):
    """DeepSeek-V2-Lite's causal attention in a cohort step: 4 clients x 2 x
    2,048 tokens, 16 heads, qk 192, v 128, forward and backward.  The
    forward, dQ and dK/dV kernels are in the program, and no (S, S) or
    (S, H, C) score buffer is (C = 512, the blocks the parent scanned)."""
    s, c = 2048, 512
    q = jax.ShapeDtypeStruct((4, 2, s, 16, 192), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((4, 2, s, 16, 128), jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        f = lambda q, k, v: mla_attention.mla_attention(q, k, v, scale=0.11472,
                                                        interpret=False)
        return jnp.sum(jax.vmap(f)(q, k, v))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, q, v).compile().as_text()
    assert "tpu_custom_call" in text
    for kernel in ("mla_attention_fwd", "mla_attention_dq", "mla_attention_dkv"):
        assert f"%{kernel}." in text
    dims = [tuple(int(d) for d in m.split(",")) for m in re.findall(r"\[([\d,]+)\]", text)]
    assert not [d for d in dims if d.count(s) >= 2 or (s in d and c in d)]
