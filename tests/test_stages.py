"""The compressed train step names its stages inside the compiled program:
every scatter, gather, sort and collective of the step's HLO carries a
``stage.<name>`` scope in its ``op_name`` metadata, so a profiler trace's
device time can be put under the stage that spent it."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.api import CompressionConfig
from repro.core.stages import MOE_STAGES, STAGES, stage
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.models.common import split_params
from repro.optim.optimizers import adam
from repro.train import step as step_lib

_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%[\w.\-]+\s*=\s.*?\s"
                          r"([a-z][a-z0-9\-]*)\(")
_STAGE = re.compile(r"stage\.(\w+)")
NOTABLE = {"scatter", "gather", "sort", "custom-call", "all-gather",
           "all-reduce"}


def _tiny_cfg():
    return tf.ModelConfig(
        name="stages", vocab=128, d_model=64, pattern=("attn_sw",),
        num_periods=2, num_heads=4, num_kv_heads=2, head_dim=16, window=16,
        d_ff=128, act="gelu", norm="rms", remat="none", dtype=jnp.bfloat16)


def _tiny_lite_cfg():
    """The registry's deepseek-v2-lite smoke config with 4 of 8 routed
    experts held: MLA without q-LoRA, latent norm, YaRN, held experts."""
    from repro.configs import registry
    cfg = registry.get("deepseek-v2-lite").smoke
    return dataclasses.replace(
        cfg, dtype=jnp.bfloat16, num_periods=2,
        moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=3,
                                experts_held=4, expert_offset=2))


def _stages_by_opcode(text: str) -> dict:
    """``{opcode: [innermost stage or None of each instruction]}`` over
    every instruction of a compiled module's HLO text."""
    out: dict = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op_name = re.search(r'\bop_name="([^"]*)"', line)
        found = _STAGE.findall(op_name.group(1)) if op_name else []
        out.setdefault(m.group(1), []).append(found[-1] if found else None)
    return out


def test_stage_names():
    assert len(set(STAGES)) == len(STAGES) == 12
    assert STAGES[8:] == MOE_STAGES
    with pytest.raises(ValueError, match="unknown stage"):
        stage("collective")
    text = jax.jit(lambda x: stage("apply")(jnp.sin)(x)).lower(
        jnp.ones(4)).as_text(debug_info=True)
    assert "stage.apply" in text


@pytest.mark.parametrize("model,exchange,backend", [
    ("dense", "sync", "pallas"), ("dense", "overlap", "pallas"),
    ("dense", "sync", "reference"), ("lite", "sync", "pallas")])
def test_compressed_step_names_every_stage(model, exchange, backend):
    """The step's own eight stages in every case; a model with MoE layers
    adds route (its router matmul), dispatch (the sort and gathers into the
    held experts' slots), experts (their matmuls) and combine (the gather
    back)."""
    cfg = _tiny_cfg() if model == "dense" else _tiny_lite_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(name="gspar", rho=0.05, wire="gather",
                             wire_layout="auto", backend=backend,
                             error_feedback=True, exchange=exchange,
                             min_leaf_size=1024)
    opt = adam(3e-4)
    params = split_params(jax.eval_shape(
        lambda k: tf.init_model(k, cfg), jax.random.key(0)))[0]
    state = (params, jax.eval_shape(opt.init, params),
             jax.eval_shape(lambda: step_lib.init_compressed_feedback(
                 cfg, comp, mesh)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    with jax.set_mesh(mesh):
        step = jax.jit(step_lib.make_compressed_train_step(
            cfg, comp, opt, mesh, dict(shd.DP_RULES)))
        text = step.lower(*state, batch, jax.random.key(1)).compile().as_text()
    by_op = _stages_by_opcode(text)
    for op in NOTABLE & set(by_op):
        assert None not in by_op[op], op
    assert {"scatter", "gather", "all-gather"} <= set(by_op)
    found = {s for stages in by_op.values() for s in stages}
    want = set(STAGES) - (set(MOE_STAGES) if model == "dense" else set())
    assert want <= found, want - found
    if model == "lite":
        assert {"route", "experts"} <= set(by_op["dot"])
        assert {"dispatch", "combine"} <= set(by_op["gather"])
        assert "dispatch" in by_op["sort"]
    else:
        assert not set(MOE_STAGES) & found


def test_fsdp_step_names_its_stages():
    cfg = _tiny_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(name="gspar", rho=0.1, backend="reference")
    opt = adam(3e-4)
    params = split_params(jax.eval_shape(
        lambda k: tf.init_model(k, cfg), jax.random.key(0)))[0]
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    with jax.set_mesh(mesh):
        step = jax.jit(step_lib.make_fsdp_train_step(
            cfg, comp, opt, mesh, dict(shd.DP_RULES)))
        text = step.lower(params, jax.eval_shape(opt.init, params), batch,
                          jax.random.key(1)).compile().as_text()
    found = {s for stages in _stages_by_opcode(text).values() for s in stages}
    assert {"model", "compress", "optimizer"} <= found
