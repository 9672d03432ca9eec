"""The system under test, built from a cell's files as
``repro.launch.train.build`` builds it: ``DP_RULES`` plus the arch's
overrides, ``make_compressed_train_step`` jitted with the whole state
donated, the state placed by ``compressed_state_shardings`` and the batch
split over ``data``. ``build`` there reads only the registry's configs, so
this module makes the ``ModelConfig`` from the configuration file itself."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import families, weights

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(conf: dict):
    """``(arch spec, ModelConfig)``: the registry arch with the file's
    replaced fields (a nested group by its own fields), checked against
    the sizes the file states."""
    from repro.configs import registry
    spec = registry.get(conf["program"]["arch"])
    replace = {k: dataclasses.replace(getattr(spec.model, k), **v)
               if isinstance(v, dict) else v
               for k, v in conf["program"]["replace"].items()}
    cfg = dataclasses.replace(spec.model, name=conf["name"],
                              dtype=DTYPES[conf["dtype"]], **replace)
    check_sizes(conf, cfg)
    return spec, cfg


def check_sizes(conf: dict, cfg) -> None:
    """The program must run the sizes the file states: the reference reads
    them from the file, never from the program."""
    sizes = families.get(conf["family"]).program_sizes(cfg)
    bad = {k: (conf[k], v) for k, v in sizes.items() if conf[k] != v}
    if bad:
        raise SystemExit(f"{conf['name']}: the program's config differs from "
                         f"the file (file, program): {bad}")


@dataclasses.dataclass
class Job:
    mesh: jax.sharding.Mesh
    step: object                  # jitted (*state, batch, key) -> (*state, m)
    init: object                  # jitted key -> state, placed
    param_paths: list             # leaf paths of params, flatten order
    batch_sharding: object
    global_batch: int
    seq: int


def build(conf: dict, traffic: dict, mesh_shape: tuple, devices) -> Job:
    from repro.core.api import CompressionConfig
    from repro.dist import sharding as shd
    from repro.models import transformer as tf
    from repro.models.common import split_params
    from repro.optim.optimizers import adam, init_feedback
    from repro.train import step as step_lib

    spec, cfg = model_config(conf)
    mesh = jax.make_mesh(tuple(mesh_shape), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices)
    rules = dict(shd.DP_RULES)
    rules.update(spec.rules_overrides)
    if traffic["optimizer"] != "adam":
        raise SystemExit(f"optimizer {traffic['optimizer']!r}: only adam "
                         "has a reference here")
    opt = adam(traffic["lr"], moment_dtype=DTYPES[conf["moment_dtype"]])
    comp = CompressionConfig(**traffic["compression"])
    if not comp.error_feedback:
        raise SystemExit("the harness drives the error-feedback step")
    workers = step_lib.mesh_workers(mesh)

    shapes = split_params(jax.eval_shape(lambda k: tf.init_model(k, cfg),
                                         jax.random.key(0)))[0]
    paths = weights.paths_of(shapes)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    shape_of = {p: s.shape for p, s in zip(paths, leaves)}

    def make_state(key):
        made = weights.make(conf["init"], key, shape_of, cfg.dtype)
        params = jax.tree_util.tree_unflatten(treedef,
                                              [made[p] for p in paths])
        return (params, opt.init(params), init_feedback(params, workers))

    abstract = jax.eval_shape(make_state, jax.random.key(0))
    shardings = step_lib.compressed_state_shardings(mesh, abstract)
    init = jax.jit(make_state, out_shardings=shardings)
    step = jax.jit(step_lib.make_compressed_train_step(
        cfg, comp, opt, mesh, rules), donate_argnums=(0, 1, 2))
    return Job(mesh=mesh, step=step, init=init,
               param_paths=paths,
               batch_sharding=NamedSharding(mesh, P("data")),
               global_batch=traffic["batch_per_chip"] * workers,
               seq=traffic["seq"])
