"""Compile rehearsals: the serving kernels at qwen1.5-0.5b widths, compiled
by the TPU compiler for a described (not attached) TPU v5e.  Nothing runs;
what this catches is what interpret mode cannot — Mosaic refusing a tile
that is not aligned, a kernel that needs more VMEM than it may use, a
shard_map'd kernel that will not partition.

Only one process at a time may load the TPU compiler library, and the test
runner imports this file in every worker, so the topology is described in
a module-scoped fixture, never at import: only the worker that runs these
tests loads the library, and it skips them where it cannot be loaded.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import nvfp4
from repro.kernels import nvfp4_matmul as mm
from repro.kernels import paged_attention as pa

CFG = configs.get_config("qwen1.5-0.5b")
D, FF, HD = CFG.d_model, CFG.d_ff, CFG.head_dim
QKV = (CFG.n_heads + 2 * CFG.n_kv_heads) * HD
TP = 4
SLOTS, BLOCK_SIZE = 4, 16
# chip_smoke.py's pool geometry: 1024-token prompts + 32 generated tokens
PAGES = -(-(1024 + 32 - 1) // BLOCK_SIZE)

# (name, K, N): one chip, then what each of 4 TP shards holds (row-parallel
# wo/wd split K, column-parallel wqkv/wg split N)
ONE_CHIP = [("wqkv", D, QKV), ("wo", D, D), ("wg", D, FF), ("wd", FF, D)]
PER_SHARD = [("wqkv/tp4", D, QKV // TP), ("wo/tp4", D // TP, D),
             ("wg/tp4", D, FF // TP), ("wd/tp4", FF // TP, D)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _packed(k, n, groups=0):
    """Shapes of a packed [K, N] weight (or a [groups, K, N] stack)."""
    lead = (groups,) if groups else ()
    w = jax.ShapeDtypeStruct((*lead, k, n), jnp.float32)
    return jax.eval_shape(
        lambda w: nvfp4.pack(jnp.swapaxes(w, -1, -2), n_lead=len(lead)), w)


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("name,k,n", ONE_CHIP + PER_SHARD,
                         ids=[c[0] for c in ONE_CHIP + PER_SHARD])
@pytest.mark.parametrize("m", [SLOTS, 1024], ids=["decode", "prefill"])
def test_nvfp4_matmul_compiles(one_chip, name, k, n, m):
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    _mosaic(lambda x, p: mm.nvfp4_matmul(x, p, interpret=False), x,
            _on(_packed(k, n), one_chip))


def test_nvfp4_matmul_grouped_compiles(one_chip):
    """qwen2-moe-a2.7b expert gate/up: 60 experts, K 2048, N 1408."""
    moe = configs.get_config("qwen2-moe-a2.7b")
    g, k, n = moe.n_experts, moe.d_model, moe.moe_d_ff
    x = jax.ShapeDtypeStruct((g, 8, k), jnp.bfloat16, sharding=one_chip)
    _mosaic(lambda x, p: mm.nvfp4_matmul_grouped(x, p, interpret=False), x,
            _on(_packed(k, n, groups=g), one_chip))


@pytest.mark.parametrize("q_len", [1, 4], ids=["decode", "verify_k3"])
def test_paged_attention_compiles(one_chip, q_len):
    n_blocks = SLOTS * PAGES + 2
    nkv = CFG.n_kv_heads

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = s((SLOTS, q_len, CFG.n_heads, HD), jnp.bfloat16)
    # the whole pool in its stored layout; the kernel reads one layer
    pages = s((CFG.n_layers, n_blocks, BLOCK_SIZE, nkv * HD), jnp.bfloat16)
    bt = s((SLOTS, PAGES), jnp.int32)
    pos = s((SLOTS,) if q_len == 1 else (SLOTS, q_len), jnp.int32)
    layer = s((), jnp.int32)
    _mosaic(lambda q, k, v, bt, pos, layer: pa.paged_attention(
        q, k, v, bt, pos, layer, interpret=False), q, pages, pages, bt, pos,
        layer)


def test_decode_step_updates_pool_in_place(one_chip, monkeypatch):
    """The donated decode step with the fused kernel at qwen1.5-0.5b's
    attention widths (two layers, BF16 weights): the pool rides in the
    layer scan's carry and the kernel reads its layer from the whole pool,
    so the step holds no copy of the pool or of one layer, and temporaries
    under 5% of the pool's bytes."""
    from test_paged_carry import assert_pool_updated_in_place

    from repro.core import qconfig
    from repro.kernels import ops
    from repro.models import decoder

    cfg = dataclasses.replace(CFG, n_layers=2)
    params = _on(jax.eval_shape(
        lambda: decoder.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    pool = _on(jax.eval_shape(
        lambda: decoder.init_paged_pool(cfg, 512, BLOCK_SIZE)), one_chip)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = jax.jit(
        lambda params, pool, bt, lens, active, toks:
        decoder.decode_step_paged(cfg, params, pool, bt, lens, active,
                                  {"tokens": toks}, qconfig.BF16,
                                  fused=True),
        donate_argnums=(1,))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ops.interpret_default.cache_clear()
    try:
        compiled = step.lower(params, pool, s((SLOTS, PAGES)), s((SLOTS,)),
                              s((SLOTS,), jnp.bool_),
                              s((SLOTS, 1))).compile()
    finally:
        ops.interpret_default.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()
    assert_pool_updated_in_place(compiled, pool)


@pytest.mark.parametrize("parallelism,k,n",
                         [("row", FF, D), ("column", D, QKV)],
                         ids=["wd_row", "wqkv_column"])
def test_nvfp4_matmul_tp_compiles_on_four_chips(topo, parallelism, k, n):
    """The shard_map'd kernel over a (data 1, model 4) mesh of the described
    chips; the row-parallel split carries the psum."""
    mesh = Mesh(np.array(topo.devices[:TP]).reshape(1, TP),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    packed = _packed(k, n)
    w_spec = P(None, "model") if parallelism == "row" else P("model", None)
    x_spec = P(None, "model") if parallelism == "row" else P()
    x = jax.ShapeDtypeStruct((SLOTS, k), jnp.bfloat16,
                             sharding=NamedSharding(mesh, x_spec))
    packed = nvfp4.PackedNVFP4(
        _on(packed.codes, NamedSharding(mesh, w_spec)),
        _on(packed.scales, NamedSharding(mesh, w_spec)),
        _on(packed.tensor_scale, NamedSharding(mesh, P())),
        orig_k=packed.orig_k)
    text = _mosaic(lambda x, p: mm.nvfp4_matmul_tp(x, p, mesh, parallelism,
                                                   interpret=False),
                   x, packed)
    assert ("all-reduce" in text) == (parallelism == "row")
