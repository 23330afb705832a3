"""AOT compiles of the main-path Pallas kernels for a DESCRIBED v5e (no chip).

Interpret mode cannot see what Mosaic refuses: block shapes that break the
(8, 128) tiling rule, kernels that outgrow VMEM. ``flash_attention_tiled`` passed
every interpret-mode test for fifteen PRs and had never lowered for a TPU. These
cases hand the chip's own compiler the real widths and assert a
``tpu_custom_call`` comes out. Nothing runs: a compile that passes is not a chip
run (``chip_smoke.py`` is).

The topology is described inside a fixture of THIS file only (one process may
load libtpu; see the on-chip-measurement guide §2): never at import, in a
``skipif`` or in ``parametrize`` arguments.
"""

import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from replay_tpu.nn.attention import dot_product_attention
from replay_tpu.nn.moe import SparseExperts, grouped_matmul
from replay_tpu.ops.flash_attention import flash_attention
from replay_tpu.ops.flash_tiled import flash_attention_tiled
from replay_tpu.ops.fused_ce import fused_lse
from replay_tpu.parallel import sharded_fused_lse

pytestmark = pytest.mark.jax

bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    # a compile for a described chip is written to the persistent cache but can
    # never be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def assert_mosaic(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def fwd_or_grad(fn, grad, argnums):
    if not grad:
        return fn
    return jax.grad(lambda *args: jnp.sum(fn(*args).astype(f32)), argnums=argnums)


# (rows, embed, items, table dtype): notebook-09 SASRec on the ML-20M catalog
# (B512·L50 rows), BERT4Rec notebook-10 width (B512·L100, d=300 — the VMEM guard
# must shrink item_tile), the million-item north star, and the bf16-compute /
# f32-master-table split Trainer(precision="bf16") actually feeds the kernel
FUSED_CE_SHAPES = [
    (25600, 64, 27278, bf16),
    (51200, 300, 27279, bf16),
    (25600, 64, 1_000_000, bf16),
    (25600, 64, 27278, f32),
]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("rows,embed,items,table_dtype", FUSED_CE_SHAPES)
def test_fused_lse_lowers(one_chip, rows, embed, items, table_dtype, grad):
    hidden = jax.ShapeDtypeStruct((rows, embed), bf16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((items, embed), table_dtype, sharding=one_chip)
    assert_mosaic(fwd_or_grad(fused_lse, grad, (0, 1)), hidden, table)


# (B, H, L, D): the SASRec bench shape, two mid lengths, and the long-context
# shape chip_smoke.py runs; every one was refused before the [B, 1, Lk] bias
TILED_SHAPES = [(512, 2, 50, 32), (64, 2, 200, 32), (64, 2, 1024, 64), (8, 2, 4096, 64)]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("shape", TILED_SHAPES, ids=lambda s: f"L{s[2]}")
def test_flash_tiled_lowers(one_chip, shape, grad):
    qkv = jax.ShapeDtypeStruct(shape, bf16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((shape[0], shape[2]), f32, sharding=one_chip)
    assert_mosaic(fwd_or_grad(flash_attention_tiled, grad, (0, 1, 2)), qkv, qkv, qkv, bias)


# the fused route at Mellum2's published widths, one row of 8,192 positions: 32
# query heads over 4 key/value heads of 128, the sliding layers' window and none
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("window", [1024, None], ids=["window1024", "full"])
def test_grouped_banded_route_lowers_at_the_published_widths(one_chip, window, grad):
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), bf16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), bf16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, 8192), f32, sharding=one_chip)
    route = partial(flash_attention_tiled, causal=True, block_q=512, block_k=512, window=window)
    assert_mosaic(fwd_or_grad(lambda q, k, v, b: route(q, k, v, b), grad, (0, 1, 2)), q, kv, kv, bias)


# the fused route at Moonlight's published latent-attention widths, one row of 4,096
# positions (the cell's) and of 8,192 (the published context): 16 heads, scores over
# 192 (128 + the 64-wide rotary part), values and output 128 wide
@pytest.mark.parametrize("length,grad", [(4096, False), (4096, True), (8192, True)],
                         ids=["L4096-fwd", "L4096-grad", "L8192-grad"])
def test_latent_route_lowers_at_the_published_192_128_widths(one_chip, length, grad):
    qk = jax.ShapeDtypeStruct((1, 16, length, 192), bf16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 16, length, 128), bf16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, length), f32, sharding=one_chip)
    route = partial(flash_attention_tiled, causal=True, block_q=512, block_k=512)
    assert_mosaic(fwd_or_grad(lambda q, k, v, b: route(q, k, v, b), grad, (0, 1, 2)), qk, qk, v, bias)


# (rows, contraction, columns): the expert layer's two grouped products at the
# published widths (d 2048, expert width 1536), 8 x 1024 positions x 4 picks of rows
GROUPED_SHAPES = [(32768, 2048, 1536), (32768, 1536, 2048)]


@pytest.mark.parametrize("rows,inner,cols", GROUPED_SHAPES)
def test_grouped_matmul_lowers_forward_and_backward(one_chip, rows, inner, cols):
    lhs = jax.ShapeDtypeStruct((rows, inner), bf16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, inner, cols), bf16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    # the gradient's program holds the forward kernel and both backward ones
    compiled = partial(grouped_matmul, interpret=False)  # as on the chip: here the CPU would interpret
    assert_mosaic(fwd_or_grad(compiled, True, (0, 1)), lhs, rhs, sizes)


def test_the_expert_layer_lowers_on_every_rung_at_the_published_widths(one_chip, monkeypatch):
    """lfm2's sparse layer (d 2048, expert width 1536, 8 held of 64, top 4) on 2,048
    tokens, forward and backward: four rungs of 512 to 8,192 rows, each pass's
    switch, the unwritten rows past a rung, the products outside the switches."""
    import importlib

    kernels = importlib.import_module("replay_tpu.ops.flash_attention")
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)  # as on the chip
    layer = SparseExperts(num_experts=64, experts_held=8, expert_offset=0, top_k=4,
                          hidden_dim=1536, dtype=bf16)
    x = jax.ShapeDtypeStruct((1, 2048, 2048), bf16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 2048), jnp.bool_, sharding=one_chip)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x, mask)["params"]
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)

    def loss(p, x, m):
        out, _ = layer.apply({"params": p}, x, m, mutable=["counters"])
        return jnp.sum(out.astype(f32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x, mask).compile().as_text()
    assert text.count("tpu_custom_call") >= 9  # three products, two kernels back for each
    assert "conditional(" in text


def test_flash_single_block_lowers_at_its_bound(one_chip):
    qkv = jax.ShapeDtypeStruct((8, 2, 1024, 64), bf16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((8, 1, 1024, 1024), f32, sharding=one_chip)
    assert_mosaic(flash_attention, qkv, qkv, qkv, mask)


def test_single_block_route_refuses_long_sequences():
    """Past the bound the chip's compiler refuses the kernel (VMEM); the route
    says so itself and names the tiled kernel."""
    qkv = jax.ShapeDtypeStruct((8, 2, 4096, 64), bf16)
    mask = jax.ShapeDtypeStruct((8, 1, 4096, 4096), f32)
    with pytest.raises(ValueError, match="use_flash='tiled'"):
        jax.eval_shape(
            lambda q, k, v, m: dot_product_attention(q, k, v, m, use_flash=True),
            qkv, qkv, qkv, mask,
        )


# (mesh axes, their sizes, rows, table's spec): DP2 x TP2 with the catalog sharded,
# and the four-chip benchmark cell's data=4 mesh (512 x 50 rows a chip, the table
# replicated): the route CE takes under a mesh (nn.loss.ce.full_softmax_route)
SHARDED_CE_MESHES = [
    (("data", "model"), (2, 2), 25600, P("model", None)),
    (("data", "model", "seq"), (4, 1, 1), 102400, P(None, None)),
]


@pytest.mark.parametrize("axes,sizes,rows,table_spec", SHARDED_CE_MESHES, ids=["dp2tp2", "dp4"])
def test_sharded_fused_lse_grad_lowers_on_2x2(topo, axes, sizes, rows, table_spec):
    mesh = Mesh(np.array(topo.devices).reshape(sizes), axes)
    hidden = jax.ShapeDtypeStruct(
        (rows, 64), bf16, sharding=NamedSharding(mesh, P("data", None))
    )
    table = jax.ShapeDtypeStruct(
        (27278, 64), f32, sharding=NamedSharding(mesh, table_spec)
    )
    assert_mosaic(
        fwd_or_grad(lambda h, w: sharded_fused_lse(h, w, mesh), True, (0, 1)), hidden, table
    )
