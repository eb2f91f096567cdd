"""Sharding rules engine + HLO analyzer units + small-mesh integration."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch import hlo_analysis
from repro.models.common import ParamSpec


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _rules(mesh, mode="fsdp_tp"):
    from repro.distributed.sharding import make_rules
    return make_rules(mesh, mode)


def test_resolve_divisible_dims():
    from repro.distributed.sharding import resolve
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = ParamSpec((2048, 8192), ("embed", "mlp"))
    p = resolve(spec, mesh, _rules(mesh))
    assert p == __import__("jax").sharding.PartitionSpec("data", "model")


def test_resolve_fallback_indivisible():
    """40 heads don't divide model=16 -> unsharded, no crash."""
    from repro.distributed.sharding import resolve
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = ParamSpec((128, 40, 128), ("layers", "heads", "none"))
    p = resolve(spec, mesh, _rules(mesh))
    assert p[1] is None


def test_resolve_no_axis_reuse():
    """model axis used by dim0 cannot be reused by dim1."""
    from repro.distributed.sharding import resolve
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = ParamSpec((128, 4864, 7168), ("expert", "mlp", "embed"))
    p = resolve(spec, mesh, _rules(mesh))
    assert p[0] == "model"
    assert p[1] is None               # mlp wanted model; taken
    assert p[2] == "data"


def test_resolve_multi_pod_partial_prefix():
    """dim divisible by pod*data only partially -> greedy prefix."""
    from repro.distributed.sharding import resolve
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    # 2*16=32 divides 64; embed rule = ("pod","data")
    spec = ParamSpec((64,), ("embed",))
    p = resolve(spec, mesh, _rules(mesh))
    assert p[0] == ("pod", "data")
    # 2 divides only the pod prefix (single axes normalize to bare names)
    spec2 = ParamSpec((2,), ("embed",))
    p2 = resolve(spec2, mesh, _rules(mesh))
    assert p2[0] == "pod"


def test_vocab_odd_unsharded():
    """whisper's vocab 51865 is indivisible -> falls back cleanly."""
    from repro.distributed.sharding import resolve
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = ParamSpec((51865, 384), ("vocab", "embed"))
    p = resolve(spec, mesh, _rules(mesh))
    assert p[0] is None and p[1] == "data"


# ------------------------------------------------------- HLO analyzer


HLO_SAMPLE = textwrap.dedent("""\
    HloModule test

    %body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
      %w = f32[16,16]{1,0} constant({...})
      %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,16]) tuple(%i2, %dot.1)
    }

    %cond (p: (s32[], f32[8,16])) -> pred[] {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(12)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    ENTRY %main (a: f32[8,16]) -> f32[8,16] {
      %a = f32[8,16]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,16]) tuple(%zero, %a)
      %loop = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
      %res = f32[8,16]{1,0} get-tuple-element(%loop), index=1
      %ag = f32[8,64]{1,0} all-gather(%res), channel_id=1, replica_groups=[4,4]<=[16], dimensions={1}
      ROOT %out = f32[8,16]{1,0} slice(%ag), slice={[0:8],[0:16]}
    }
    """)


def test_hlo_while_trip_count_scaling():
    stats = hlo_analysis.analyze_hlo(HLO_SAMPLE, 16)
    # dot in a 12-trip loop: 2*8*16*16 * 12
    assert stats["flops_per_device"] == 2 * 8 * 16 * 16 * 12
    assert stats["n_while_loops"] == 1


def test_hlo_collective_bytes():
    stats = hlo_analysis.analyze_hlo(HLO_SAMPLE, 16)
    # all-gather out 8*64*4 bytes, group 4 -> (n-1)/n factor
    want = 8 * 64 * 4 * 3 / 4
    assert abs(stats["collective_bytes_per_device"] - want) < 1e-6


def test_hlo_slice_bytes_model():
    """dynamic-slice reads the slice, not its (stacked) operand; DUS in a
    k-trip loop touches its buffer once overall."""
    from repro.launch.hlo_analysis import Op, op_mem_bytes
    big = Op("w", "parameter", [("f32", [88, 1024, 1024])], [], "", "main")
    sl = Op("s", "dynamic-slice", [("f32", [1, 1024, 1024])], ["w"], "", "b")
    ops = {"w": big, "s": sl}
    assert op_mem_bytes(sl, ops, 88) == 2 * 1024 * 1024 * 4
    dus = Op("d", "dynamic-update-slice", [("f32", [88, 64])], ["w"], "", "b")
    assert op_mem_bytes(dus, ops, 88) == 2 * 88 * 64 * 4 / 88
    sc = Op("c", "scatter", [("f32", [50304, 64])], ["t", "i", "u"], "", "m")
    ops2 = {"u": Op("u", "x", [("f32", [128, 64])], [], "", "m"), "c": sc}
    assert op_mem_bytes(sc, ops2, 1) == 3 * 128 * 64 * 4


def test_hlo_collective_factors():
    from repro.launch.hlo_analysis import Op, _collective_cost
    op = Op("x", "all-reduce", [("f32", [128])], [], "", "main")
    line = "replica_groups={{0,1,2,3,4,5,6,7}}"
    got = _collective_cost(op, line, 8)
    assert abs(got - 2 * 512 * 7 / 8) < 1e-6


# ---------------------------------------------- 8-device GSPMD integration


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the published table for the chip jax reports; an
    unknown kind is an error, never another chip's numbers."""
    from repro.launch import roofline
    assert roofline.hw("TPU v5 lite")["peak_flops"] == 197e12
    assert roofline.hw("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="cpu"):
        roofline.hw("cpu")


@pytest.mark.slow
def test_small_mesh_train_step_runs():
    """Real (host-emulated 8-device) pjit execution of a QAD train step —
    numerics must match the single-device run.  Subprocess because XLA
    device count is locked at first jax init."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.core import qad
        from repro.data import DataConfig, make_batch
        from repro.distributed import sharding as shd, ctx
        from repro.launch import specs
        from repro.launch.mesh import make_host_mesh
        from repro.models import get_model, common
        from repro.optim import AdamW

        cfg = configs.get_smoke("olmo-1b")
        model = get_model(cfg)
        opt = AdamW(lr=1e-3)
        qcfg = specs.recipe_qconfig(cfg)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
        batch = make_batch(dcfg, 0)

        state = qad.init_state(model, cfg, jax.random.PRNGKey(0), opt)
        step = qad.make_train_step(model, cfg, qcfg, opt)
        _, m_single = jax.jit(step)(state, batch)   # 1-logical-device baseline

        mesh = make_host_mesh(model_parallel=2)            # (4, 2)
        rules = shd.make_rules(mesh, "fsdp_tp")
        shard_p = shd.tree_shardings(model.param_specs(cfg), mesh, rules)
        with ctx.use(mesh, rules):
            state_sh = qad.TrainState(
                step=state.step,
                student=jax.device_put(state.student, shard_p),
                teacher=jax.device_put(state.teacher, shard_p),
                opt_state=jax.tree.map(lambda x: x, state.opt_state))
            _, m_mesh = jax.jit(step)(state_sh, batch)
        kl_a, kl_b = float(m_single["kl"]), float(m_mesh["kl"])
        assert np.isfinite(kl_b)
        np.testing.assert_allclose(kl_a, kl_b, rtol=5e-2, atol=1e-4)
        print("MESH_OK", kl_a, kl_b)
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=".", timeout=600)
    assert "MESH_OK" in r.stdout, r.stdout + r.stderr
