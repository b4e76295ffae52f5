"""Distribution-layer tests: sharding rules, compression, pipeline,
fault tolerance, small-mesh pjit execution on host devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_smoke_config
from repro.distributed import sharding as shd
from repro.distributed.compression import (compress_with_feedback,
                                           dequantize_int8, init_error_state,
                                           quantize_int8)
from repro.distributed.fault_tolerance import StragglerPolicy, TrainRunner
from repro.distributed.pipeline import bubble_fraction, pipeline_stages
from repro.models import Model


def test_resolve_spec_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    # (shape divisible check) — 1-device mesh: everything divides
    spec = shd.resolve_spec(("model", None), (7, 3), mesh)
    assert spec == P("model", None)


def test_param_specs_rules():
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    model = Model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(shapes, fsdp=True)
    # embed: (V, d) -> ("model", "data")
    assert tuple(specs["embed"]) == ("model", "data")
    # moe experts stacked under unit: leading None + E over model
    moe_spec = specs["unit"][0]["moe"]["w_gate"]
    assert moe_spec[0] is None and moe_spec[1] == "model"
    # norms replicated
    assert all(s is None for s in specs["final_norm"])


def test_constrain_noop_without_mesh():
    shd.set_mesh(None)
    x = jnp.ones((4, 4))
    assert shd.constrain(x, ("data", None)) is x


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-7


def test_error_feedback_is_unbiased_over_time():
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal((64,)), jnp.float32) * 0.01
    err = init_error_state({"w": g_true})["w"]
    acc = jnp.zeros_like(g_true)
    for _ in range(50):
        deq, err = compress_with_feedback({"w": g_true}, {"w": err})
        deq = deq["w"]
        err = err
        acc = acc + deq
    # mean of compressed grads converges to the true gradient
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g_true),
                               atol=2e-4)


def test_pipeline_stages_single_stage_identity():
    def stage(p, x):
        return x * p

    pipelined = pipeline_stages(stage, n_stages=1, n_microbatches=3,
                                axis_name="pod")
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    f = jax.shard_map(pipelined, mesh=mesh, in_specs=(P(), P()),
                      out_specs=P(), check_vma=False)
    x = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    out = f(jnp.asarray(2.0), x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2)


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)


@pytest.mark.slow
def test_small_mesh_pjit_forward_matches_single_device():
    """pjit the forward on a 1x1 'production-shaped' mesh (host device) and
    compare against plain eager execution — proves the sharding annotations
    do not alter numerics."""
    cfg = get_smoke_config("yi-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    ref = model.forward(params, toks)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with shd.mesh_context(mesh):
        shardings = shd.param_shardings(params, mesh)
        p_sh = jax.device_put(params, shardings)
        out = jax.jit(lambda p, t: model.forward(p, t))(p_sh, toks)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_straggler_policy_drains_and_recovers():
    sp = StragglerPolicy(n_replicas=4, threshold=2.0, alpha=1.0)
    for i in range(4):
        sp.record(i, 1.0)
    sp.record(2, 10.0)   # replica 2 becomes a straggler
    assert 2 not in sp.healthy_replicas()
    picks = {sp.pick(s) for s in range(8)}
    assert 2 not in picks
    for _ in range(12):
        sp.record(2, 1.0)
    assert 2 in sp.healthy_replicas()


def test_train_runner_restarts_from_checkpoint(tmp_path):
    from repro.checkpoint import Checkpointer
    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        if calls["n"] == 3:       # one transient failure
            raise RuntimeError("injected fault")
        return {"w": state["w"] + 1}, {"loss": jnp.asarray(0.0)}

    ck = Checkpointer(str(tmp_path), keep=3, every=1)
    runner = TrainRunner(step_fn, ck, {"w": jnp.zeros(())})

    def batches():
        while True:
            yield {}

    state = runner.run(batches(), num_steps=5)
    # 5 successful steps despite the injected failure
    assert runner.step == 5
    assert float(state["w"]) == 5.0
