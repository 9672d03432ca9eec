"""Compiles of the compressed step's kernels for a described v5e chip.

The TPU compiler is installed even where no chip is attached, so these
tests lower the emit pipeline at the gemma-2b MLP row width (2048 x 16384
coordinates) and ask the chip's compiler to accept it: what interpret mode
cannot show (Mosaic lowering gaps, tiling, VMEM limits) fails here at no
chip cost. Nothing runs, so nothing here says anything about results or
times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import codecs as codecs_lib
from repro.core.api import CompressionConfig, _map_rows
from repro.core.sparse import PallasBackend
from repro.kernels.sparsify import kernel as K
from repro.kernels.sparsify import ops

MLP_ROW = 2048 * 16384            # gemma-2b d_model x d_ff, one layer
RHO = 0.05


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs go to /tmp
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernels(hlo: str, *names: str):
    assert "tpu_custom_call" in hlo
    for name in names:
        assert name in hlo, f"kernel {name!r} missing from the compiled HLO"


def _tile_args(one_chip, d=MLP_ROW, dtype=jnp.bfloat16):
    rows = d // K.BLOCK_C
    return (_sds((rows, K.BLOCK_C), dtype, one_chip),
            _sds((rows, K.BLOCK_C), jnp.float32, one_chip))


def test_stats_kernels_compile_at_mlp_row(one_chip):
    g, _ = _tile_args(one_chip)

    def stats(g):
        l1, mx = K.stats_l1max_2d(g)
        n, l1b = K.tail_stats_2d(g, 1.0 / mx)
        return l1, n, l1b

    _assert_kernels(_hlo(stats, g), "gspar_stats_l1max", "gspar_tail_stats")


def test_prng_sparsify_kernel_compiles_at_mlp_row(one_chip):
    """The on-core PRNG variant: its uniforms come from a logical shift of
    the hardware's int32 bits, which Mosaic must lower."""
    g, _ = _tile_args(one_chip, dtype=jnp.float32)
    seed = _sds((), jnp.int32, one_chip)
    hlo = _hlo(lambda g, s: K.sparsify_prng_2d(g, 0.5, s), g, seed)
    _assert_kernels(hlo, "gspar_sparsify_prng")


@pytest.mark.parametrize("pkind", ["lam", "rho", "bern", "topk"])
def test_two_pass_kernels_compile_at_mlp_row(one_chip, pkind):
    g, u = _tile_args(one_chip)
    t = g.shape[0] // K.BLOCK_R

    def two_pass(g, u):
        up, lo = K.prefix_operands()
        tiles, _, _ = K.select_stats_2d(g, u, 0.5, 3.0, up, lo, pkind=pkind)
        offsets = jnp.cumsum(tiles, axis=1) - tiles
        return K.compact_emit_2d(g, u, 0.5, 3.0, offsets, up, lo,
                                 pkind=pkind, k_cap=4096, ef=True)

    hlo = _hlo(two_pass, g, u)
    _assert_kernels(hlo, f"select_stats_{pkind}", f"compact_emit_{pkind}")
    assert t == 512


def test_gspar_f32_ef_emit_compiles_at_mlp_row(one_chip):
    """The whole fused selector as the backend runs it: stats, greedy
    lambda, both passes, the compact select, the in-pass EF residual.
    The select sorts nothing: no ``sort`` instruction in the program."""
    cfg = CompressionConfig(name="gspar", rho=RHO, wire="gather",
                            error_feedback=True, backend="pallas")
    backend = PallasBackend(interpret=False)
    k_cap = cfg.capacity(MLP_ROW)
    g = _sds((2048, 16384), jnp.bfloat16, one_chip)

    def emit(key, g):
        sg, res = backend.compress_sparse_ef(cfg, key, g, k_cap)
        return sg.values, sg.idx, sg.nnz, res

    key = jax.random.key(0)
    hlo = _hlo(emit, _sds(key.shape, key.dtype, one_chip), g)
    _assert_kernels(hlo, "select_stats_lam", "compact_emit_lam")
    assert not re.search(r"\ssort\(", hlo)


def test_qsgd8_rice_emit_compiles_at_mlp_row(one_chip):
    """An integer codec under the RICE layout: encode on the compact
    buffer, the scatter-subtract residual, and the Golomb-Rice packing."""
    from repro.comm import wire_layout
    cfg = CompressionConfig(name="gspar", codec="qsgd8", rho=RHO,
                            wire="gather", wire_layout="rice",
                            error_feedback=True, backend="pallas")
    backend = PallasBackend(interpret=False)
    k_cap = cfg.capacity(MLP_ROW)
    g = _sds((MLP_ROW,), jnp.bfloat16, one_chip)

    def emit(key, g):
        sg, res = backend.compress_sparse_ef(cfg, key, g, k_cap)
        assert sg.layout == "rice"
        vals, words, used = wire_layout.pack(sg, wire_layout.plan(sg))
        return vals, words, used, res

    key = jax.random.key(0)
    hlo = _hlo(emit, _sds(key.shape, key.dtype, one_chip), g)
    _assert_kernels(hlo, "select_stats_lam", "compact_emit_lam")
    assert codecs_lib.get("qsgd8").wire_dtype(jnp.bfloat16) == jnp.int16


def test_vmapped_group_emit_compiles(one_chip):
    """One shape group as the tree plan drives it: three MLP rows of one
    layer stacked [rows, d] and emitted by one vmapped dispatch, which
    extends each kernel's grid by the row axis."""
    cfg = CompressionConfig(name="gspar", rho=RHO, wire="gather",
                            error_feedback=True, backend="pallas")
    backend = PallasBackend(interpret=False)
    k_cap = cfg.capacity(MLP_ROW)
    rows = 3
    stack = _sds((rows, MLP_ROW), jnp.bfloat16, one_chip)
    keys = jax.random.split(jax.random.key(0), rows)

    def group(keys, stack):
        sg, res = _map_rows(backend, lambda k, g: backend.compress_sparse_ef(
            cfg, k, g, k_cap), keys, stack)
        return sg.values, sg.idx, res

    hlo = _hlo(group, _sds(keys.shape, keys.dtype, one_chip), stack)
    _assert_kernels(hlo, "select_stats_lam", "compact_emit_lam")


def test_emit_op_is_interpret_free_when_compiled(one_chip):
    """``interpret=False`` is what a TPU resolves to: the emitted HLO holds
    the Mosaic kernels and no interpreter loop over the grid."""
    g = _sds((MLP_ROW,), jnp.float32, one_chip)
    u = _sds((MLP_ROW,), jnp.float32, one_chip)
    hlo = _hlo(lambda g, u: ops.gspar_emit(
        g, u, k_cap=CompressionConfig(rho=RHO).capacity(MLP_ROW))[0].idx,
        g, u)
    _assert_kernels(hlo, "gspar_stats_l1max", "select_stats_lam",
                    "compact_emit_lam")
