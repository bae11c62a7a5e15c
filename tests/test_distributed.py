"""Distribution tests: planner rules + a subprocess dry-run on 8 fake devices
(XLA_FLAGS must be set before jax import, so these lower in a child python).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

def test_planner_divisibility_fallbacks():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import ExecutionPlan, get_config
    from repro.distributed.planner import Planner, pick

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # pick() itself
    assert tuple(pick(mesh, (64, 32), [P("data", "model")])) == ("data", "model")

    mesh16 = None
    # logical divisibility checks against the production shape without
    # building a 256-device mesh: use a fake mesh-shape shim
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    fm = FakeMesh()
    # granite vocab 49155 is not 16-divisible -> embed falls back to d_model
    spec = pick(fm, (49155, 4096), [P("model", None), P(None, "model")])
    assert tuple(spec) == (None, "model")
    # gemma2 kv proj 4*256=1024 divides 16 -> column parallel holds
    spec = pick(fm, (2304, 1024), [P(None, "model")])
    assert tuple(spec) == (None, "model")
    # granite-moe 40 experts don't divide 16 -> fall back to per-expert d_ff
    spec = pick(fm, (40, 1536, 512),
                [P("model", None, None), P(None, None, "model")])
    assert tuple(spec) == (None, None, "model")
    # deepseek 256 experts divide -> expert parallel
    spec = pick(fm, (256, 7168, 2048),
                [P("model", None, None), P(None, None, "model")])
    assert tuple(spec) == ("model", None, None)


def test_all_param_leaves_get_specs():
    import jax
    from repro.configs import ALL_ARCHS, ExecutionPlan, get_config, smoke_config
    from repro.distributed.planner import Planner
    from repro.models import init_params

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = ExecutionPlan()
    for arch in ALL_ARCHS:
        cfg = smoke_config(arch)
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        planner = Planner(mesh, cfg, plan)
        specs = planner.tree_specs(shapes)
        n_specs = len(jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval")
            or x.__class__.__name__ == "PartitionSpec"))
        n_leaves = len(jax.tree.leaves(shapes))
        assert n_specs == n_leaves, arch


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("gemma2-2b", "train_4k"),
    ("granite-moe-3b-a800m", "decode_32k"),
])
def test_dryrun_cell_compiles_on_8_devices(arch, shape):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.configs import get_config, SHAPES
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_cell
        mesh = make_mesh((2, 4), ("data", "model"))
        cell = build_cell(get_config("{arch}"), SHAPES["{shape}"], mesh)
        co = jax.jit(cell.step, donate_argnums=cell.donate).lower(*cell.args).compile()
        cost = co.cost_analysis()
        print(json.dumps({{"flops": cost.get("flops", 0.0)}}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "flops" in out.stdout


def test_collective_parser_on_synthetic_hlo():
    from repro.utils import hlo as H
    text = textwrap.dedent("""\
    HloModule jit_f

    %cond (p: (s32[], f32[8])) -> pred[] {
      %gte = s32[] get-tuple-element(%p), index=0
      %c = s32[] constant(10)
      ROOT %lt = pred[] compare(%gte, %c), direction=LT
    }

    %body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
      %gte0 = s32[] get-tuple-element(%p), index=0
      %gte1 = f32[8]{0} get-tuple-element(%p), index=1
      %ar = f32[8]{0} all-reduce(%gte1), replica_groups={}, to_apply=%sum
      ROOT %t = (s32[], f32[8]) tuple(%gte0, %ar)
    }

    ENTRY %main (x: f32[8]) -> f32[8] {
      %init = (s32[], f32[8]) tuple(s32[] constant(0), %x)
      %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
      %big = f32[16,128]{1,0} all-gather(%x), dimensions={0}
      ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
    }
    """)
    stats = H.collective_stats(text)
    assert stats["all-reduce"]["count"] == 10          # trip-multiplied
    assert stats["all-reduce"]["bytes"] == 10 * 32
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 16 * 128 * 4
