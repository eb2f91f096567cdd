"""Tensor-parallel serving: shard_map'd packed GEMMs, sharded memory
pricing, warn-once fallback, and (subprocess, forced 2-host-device) engine
token parity vs the single-device engine."""
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.distributed.sharding import ShapeOnlyMesh
from repro.models.common import ParamSpec

TP_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
""")


def _run(script: str, timeout: int = 900, devices: int = 2):
    r = subprocess.run([sys.executable, "-c",
                        TP_PRELUDE.format(n=devices) + script],
                       capture_output=True, text=True, cwd=".",
                       timeout=timeout)
    return r


# ------------------------------------------------- rules engine (no devices)


def test_resolve_packed_column_row_kinds():
    """wqkv-like specs shard the packed N dim (column), wo/wd-like specs
    shard the packed K dim in whole blocks (row)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import make_rules, resolve_packed
    mesh = ShapeOnlyMesh({"data": 1, "model": 2})
    rules = make_rules(mesh, "tp_only")
    wqkv = ParamSpec((2, 64, 192), ("layers", "embed", "qkv"), kind="attn",
                     contract_axis=1)
    c, s, t = resolve_packed(wqkv, mesh, rules)
    assert c == P(None, "model", None) and s == c and t == P()
    wd = ParamSpec((2, 96, 64), ("layers", "mlp", "embed"), kind="mlp",
                   contract_axis=1)
    c, s, _ = resolve_packed(wd, mesh, rules)
    assert c == P(None, None, "model") and s == c


def test_resolve_packed_whole_block_fallback():
    """A K dim whose scales dim (K/16) does not divide the shards drops the
    mesh axis — a 16-element NVFP4 block never splits."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import make_rules, resolve_packed
    mesh = ShapeOnlyMesh({"data": 1, "model": 4})
    rules = make_rules(mesh, "tp_only")
    # K = 48 -> scales dim 3, indivisible by 4 -> replicated K
    wo = ParamSpec((48, 64), ("qkv", "embed"), kind="attn", contract_axis=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        c, s, _ = resolve_packed(wo, mesh, rules)
    assert c == P(None, None)


def test_tp_shard_mode_mirrors_resolve():
    from repro.core import nvfp4
    w = np.random.RandomState(0).randn(64, 96).astype(np.float32)
    packed = nvfp4.pack(np.ascontiguousarray(w.T))   # codes [96, 32], K=64
    assert nvfp4.tp_shard_mode(packed, 2, "column") == "column"
    assert nvfp4.tp_shard_mode(packed, 2, "row") == "row"
    # K/16 = 4 indivisible by 8 -> no row sharding
    assert nvfp4.tp_shard_mode(packed, 8, "row") is None
    # N = 96 indivisible by 64
    assert nvfp4.tp_shard_mode(packed, 64, "column") is None
    assert nvfp4.tp_shard_mode(packed, 1, "column") is None
    assert nvfp4.tp_shard_mode(packed, 2, None) is None


def test_resolve_fallback_warns_once_per_param():
    from repro.distributed import sharding as shd
    mesh = ShapeOnlyMesh({"data": 16, "model": 16})
    rules = shd.make_rules(mesh, "fsdp_tp")
    spec = ParamSpec((128, 40, 128), ("layers", "heads", "none"))
    shd._FALLBACK_WARNED.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        shd.resolve(spec, mesh, rules, name="wq_test")
        shd.resolve(spec, mesh, rules, name="wq_test")
        shd.resolve(spec, mesh, rules, name="wq_test")
    hits = [w for w in rec if "wq_test" in str(w.message)]
    assert len(hits) == 1, [str(w.message) for w in rec]
    assert "heads" in str(hits[0].message)


# ------------------------------------------------- analytic sharded pricing


def test_serve_memory_report_sharded_section():
    from repro import configs
    from repro.configs import SHAPES
    from repro.launch import specs
    rep = specs.serve_memory_report(configs.get_config("qwen1.5-0.5b"),
                                    SHAPES["decode_32k"], n_blocks=256,
                                    tp=8)
    sh = rep["sharded"]
    assert sh["tp"] == 8
    # packed weights split close to 1/8 (replicated norms/scales keep it >)
    assert sh["weight_bytes_packed_per_device"] < rep["weight_bytes_packed"] / 4
    assert sh["weight_bytes_packed_per_device"] > rep["weight_bytes_packed"] / 9
    # KV pool shards exactly by kv heads (16 % 8 == 0)
    assert sh["kv_pool_bytes_per_device"] * 8 == rep["kv_pool_bytes"]
    # dense cache likewise, modulo the replicated scalar "pos" leaf
    assert abs(sh["kv_bytes_recipe_per_device"] * 8
               - rep["kv_bytes_recipe"]) <= 64
    # without a model axis there is no section
    assert "sharded" not in specs.serve_memory_report(
        configs.get_config("qwen1.5-0.5b"), SHAPES["decode_32k"])


# ------------------------------------- subprocess, 2 forced host devices


def test_packed_gemm_shard_map_parity():
    """Column-parallel shard_map GEMM is BITWISE the single-device kernel
    (full K per shard); row-parallel is psum'd fp32 partials (tolerance)."""
    r = _run(textwrap.dedent("""
        from repro.kernels import ops
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_parallel=2)            # (1, 2)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (5, 64), jnp.bfloat16)
        w = jax.random.normal(jax.random.fold_in(rng, 1), (64, 96),
                              jnp.float32)
        packed = ops.pack_weight(w)
        ref = np.asarray(ops.nvfp4_matmul(x, packed, out_dtype=jnp.float32))
        col = np.asarray(ops.nvfp4_matmul_tp(x, packed, mesh, "column",
                                             out_dtype=jnp.float32))
        np.testing.assert_array_equal(col, ref)
        row = np.asarray(ops.nvfp4_matmul_tp(x, packed, mesh, "row",
                                             out_dtype=jnp.float32))
        np.testing.assert_allclose(row, ref, rtol=2e-5, atol=2e-5)
        # M=1 decode shape through both layouts
        x1 = jax.random.normal(rng, (1, 64), jnp.bfloat16)
        r1 = np.asarray(ops.nvfp4_matmul(x1, packed, out_dtype=jnp.float32))
        c1 = np.asarray(ops.nvfp4_matmul_tp(x1, packed, mesh, "column",
                                            out_dtype=jnp.float32))
        np.testing.assert_array_equal(c1, r1)
        print("GEMM_TP_OK")
    """))
    assert "GEMM_TP_OK" in r.stdout, r.stdout + r.stderr


def test_engine_tp_token_parity_dense_packed():
    """2-device TP engine == 1-device engine token-for-token on packed
    dense; packed codes/scales carry a model-sharded NamedSharding; both
    pools drain."""
    r = _run(textwrap.dedent("""
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.launch import serve
        from repro.launch.mesh import make_host_mesh
        from repro.serve import Engine

        cfg = configs.get_smoke("qwen1.5-0.5b")
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "packed")
        mesh = make_host_mesh(model_parallel=2)
        rules = shd.make_rules(mesh, "tp_only")
        prompts = serve.mixed_prompts(jax.random.PRNGKey(1), 4, 4, 12,
                                      cfg.vocab_size)

        def run(m, r):
            eng = Engine(cfg, params, qcfg, n_slots=3, block_size=8,
                         n_blocks=12, max_blocks_per_slot=4, mesh=m, rules=r)
            rids = [eng.submit(np.asarray(p), 6) for p in prompts]
            outs = eng.drain(max_steps=500)
            return eng, {i: outs[i].tolist() for i in rids}

        e1, o1 = run(None, None)
        e2, o2 = run(mesh, rules)
        assert o1 == o2, (o1, o2)
        assert e1.pool.used_blocks == 0 and e2.pool.used_blocks == 0
        rep = serve.tp_shard_report(e2)
        assert rep["packed_sharded"] == rep["packed_total"] > 0, rep
        assert rep["kv_sharded"], rep
        assert rep["weight_bytes_per_device"] < rep["weight_bytes_total"]
        assert rep["kv_pool_bytes_per_device"] * 2 == rep["kv_pool_bytes_total"]
        print("TP_ENGINE_OK")
    """))
    assert "TP_ENGINE_OK" in r.stdout, r.stdout + r.stderr


def test_tp_logit_gap_is_rounding_and_a_misaligned_shard_is_not():
    """What ``chip_smoke.py --chips 4`` rests on, at 2 layers and 4 host
    devices: the TP=4 engine's own logits, teacher-forced along the
    one-device engine's stream, against the one-device engine's, in units
    of the logits' std.  TP and a harmless reordering (the dequant-einsum
    backend instead of the Pallas kernel) stay far inside the smoke's
    tolerance of 0.5; a row-parallel shard reading its neighbour's block
    scales lands far outside it, with BF16 or NVFP4 activations."""
    r = _run(textwrap.dedent("""
        import dataclasses
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.kernels import ops
        from repro.launch import serve
        from repro.launch.mesh import make_host_mesh
        from repro.serve import Engine

        cfg = dataclasses.replace(configs.get_smoke("qwen1.5-0.5b"),
                                  d_model=256, d_ff=768, vocab_size=2048)
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "packed")
        mesh = make_host_mesh(model_parallel=4)
        rules = shd.make_rules(mesh, "tp_only")
        prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (24,),
                                               4, cfg.vocab_size))
        kernel_tp = ops._nvfp4_matmul_tp

        def misaligned_tp(x, packed, mesh, parallelism, **kw):
            if parallelism == "row":      # each shard's scales off by a block
                s = packed.scales
                lead, n = s.shape[:-1], dict(mesh.shape)["model"]
                s = jnp.roll(s.reshape(*lead, n, -1), 1, -1).reshape(s.shape)
                packed = dataclasses.replace(packed, scales=s)
            return kernel_tp(x, packed, mesh, parallelism, **kw)

        def logits(q, forced=None, tp=False, backend=None, fault=False):
            if backend:
                q = dataclasses.replace(q, packed_backend=backend)
            ops._nvfp4_matmul_tp = misaligned_tp if fault else kernel_tp
            eng = Engine(cfg, params, q, n_slots=2, block_size=8,
                         n_blocks=16, max_blocks_per_slot=6,
                         mesh=mesh if tp else None, rules=rules if tp else None,
                         fused_kernels="off")
            rid = eng.submit(prompt, 8, forced=forced, keep_logits=True)
            out = eng.drain(max_steps=500)
            ops._nvfp4_matmul_tp = kernel_tp
            return out[rid], eng.logits(rid)

        gap = {}
        for acts in ("bf16", "nvfp4"):
            q = dataclasses.replace(qcfg,
                                    quantize_activations=(acts == "nvfp4"))
            stream, one = logits(q)
            for name, kw in [("tp", dict(tp=True)),
                             ("reordered", dict(backend="dequant")),
                             ("misaligned", dict(tp=True, fault=True))]:
                got = logits(q, stream, **kw)[1]
                gap[acts, name] = serve.teacher_forced_gap(got, one)["rel"]
        print(gap)
        for acts in ("bf16", "nvfp4"):
            assert gap[acts, "tp"] < 0.05, gap
            assert gap[acts, "misaligned"] > 1.0, gap
        assert gap["bf16", "reordered"] < 0.1, gap
        print("TP_SETTLED_OK")
    """), devices=4)
    assert "TP_SETTLED_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_engine_tp_token_parity_moe_fp8():
    """TP parity on the FP8-KV MoE arch (head-sharded FP8 pages + scale
    planes, expert-sharded dequant path) + pool drain under TP."""
    r = _run(textwrap.dedent("""
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.launch import serve
        from repro.launch.mesh import make_host_mesh
        from repro.serve import Engine

        cfg = configs.get_smoke("arctic-480b")
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "qdq")
        mesh = make_host_mesh(model_parallel=2)
        rules = shd.make_rules(mesh, "tp_only")
        prompts = serve.mixed_prompts(jax.random.PRNGKey(1), 3, 4, 10,
                                      cfg.vocab_size)

        def run(m, r):
            eng = Engine(cfg, params, qcfg, n_slots=2, block_size=8,
                         n_blocks=10, max_blocks_per_slot=4, mesh=m, rules=r)
            rids = [eng.submit(np.asarray(p), 5) for p in prompts]
            outs = eng.drain(max_steps=500)
            return eng, {i: outs[i].tolist() for i in rids}

        e1, o1 = run(None, None)
        e2, o2 = run(mesh, rules)
        assert o1 == o2, (o1, o2)
        assert e1.pool.used_blocks == 0 and e2.pool.used_blocks == 0
        assert e2.pool.fp8
        kv_sh = any("model" in str(a.sharding)
                    for a in jax.tree.leaves(e2.pool.data))
        assert kv_sh
        print("TP_MOE_OK")
    """))
    assert "TP_MOE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_spec_engine_tp_token_parity():
    """Greedy speculative decode under TP == the plain single-device
    engine token-for-token (losslessness survives the parallelism layer)."""
    r = _run(textwrap.dedent("""
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.launch import serve
        from repro.launch.mesh import make_host_mesh
        from repro.serve import Engine
        from repro.spec import SpecEngine

        cfg = configs.get_smoke("qwen1.5-0.5b")
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "packed")
        mesh = make_host_mesh(model_parallel=2)
        rules = shd.make_rules(mesh, "tp_only")
        prompts = serve.mixed_prompts(jax.random.PRNGKey(2), 3, 4, 10,
                                      cfg.vocab_size)
        kw = dict(n_slots=2, block_size=8, n_blocks=12,
                  max_blocks_per_slot=4)

        def drain(eng):
            rids = [eng.submit(np.asarray(p), 6) for p in prompts]
            outs = eng.drain(max_steps=500)
            return {i: outs[i].tolist() for i in rids}

        o_plain = drain(Engine(cfg, params, qcfg, **kw))
        spec = SpecEngine(cfg, params, qcfg, draft_k=3, draft="self-qdq",
                          mesh=mesh, rules=rules, **kw)
        o_spec = drain(spec)
        assert o_spec == o_plain, (o_spec, o_plain)
        assert spec.pool.used_blocks == 0
        assert spec.stats()["acceptance_rate"] > 0.9
        print("TP_SPEC_OK")
    """))
    assert "TP_SPEC_OK" in r.stdout, r.stdout + r.stderr
