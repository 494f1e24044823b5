"""Compile-only rehearsals of the served-path Pallas kernels for a TPU v5e.

Nothing runs here: each test lowers a kernel at the served widths for a
*described* (not attached) v5e chip and asserts that Mosaic produced a
native kernel (``tpu_custom_call``) — what interpret mode cannot show
(unsupported primitives, tiling, VMEM limits).  The topology is described
inside a module fixture, never at import, and every such compile lives in
this one file: the TPU compiler library belongs to one process at a time,
so with several test workers only the worker given this file loads it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import LNNConfig, lnn_init
from repro.core.hetero import ENTITY_TYPE_NAMES
from repro.kernels.csr_spmm import csr_spmm_pallas
from repro.kernels.edge_softmax import edge_softmax_agg_pallas
from repro.kernels.hgt import hgt_edge_softmax_pallas, hgt_relation_project
from repro.kernels.stage2_score import (
    flatten_hgt_stage2_params,
    flatten_stage2_params,
    stage2_score_hgt_pallas,
    stage2_score_pallas,
)

# served widths: configs/lnn_fraud.SERVICE over the synthetic checkout
# stream (12 raw features), k_max=8 slots, community_size=4096 stage-1
# bins at max_deg=32
H, K, F = 64, 8, 12
N_STAGE1, D_STAGE1 = 4096, 32
# the lnn-hgt configuration: HGT's published width and heads
H_HGT, HEADS_HGT, F_HGT = 256, 8, 48


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_native(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("typed", [False, True], ids=["untyped", "typed"])
@pytest.mark.parametrize("gnn_type", ["gcn", "gat", "sage"])
@pytest.mark.parametrize("bucket", [1, 2, 4, 16])
def test_stage2_score_compiles_for_v5e(one_chip, gnn_type, bucket, typed):
    cfg = LNNConfig(gnn_type=gnn_type, num_gnn_layers=3, hidden_dim=H,
                    mlp_dims=(64, 32), feat_dim=F,
                    entity_types=ENTITY_TYPE_NAMES if typed else ())
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(functools.partial(lnn_init, cfg=cfg),
                       jax.random.PRNGKey(0)))

    def fused(p, emb, mask, feats, st):
        return stage2_score_pallas(
            emb, mask, feats, flatten_stage2_params(p, gnn_type),
            gnn_type=gnn_type, interpret=False, slot_type=st, typed=typed)

    args = (params, _spec(one_chip, (bucket, K, H)),
            _spec(one_chip, (bucket, K)), _spec(one_chip, (bucket, F)),
            _spec(one_chip, (bucket, K), jnp.int32) if typed else None)
    _assert_native(jax.jit(fused).lower(*args))


def test_csr_spmm_compiles_for_v5e(one_chip):
    lowered = jax.jit(functools.partial(csr_spmm_pallas, interpret=False)).lower(
        _spec(one_chip, (N_STAGE1, H)),
        _spec(one_chip, (N_STAGE1, D_STAGE1), jnp.int32),
        _spec(one_chip, (N_STAGE1, D_STAGE1)))
    _assert_native(lowered)


def test_edge_softmax_compiles_for_v5e(one_chip):
    tile = (N_STAGE1, D_STAGE1)
    lowered = jax.jit(
        functools.partial(edge_softmax_agg_pallas, interpret=False)).lower(
        _spec(one_chip, (N_STAGE1, H)), _spec(one_chip, (N_STAGE1,)),
        _spec(one_chip, (N_STAGE1,)), _spec(one_chip, tile, jnp.int32),
        _spec(one_chip, tile), _spec(one_chip, tile))
    _assert_native(lowered)


@pytest.mark.parametrize("bucket", [1, 2, 16])
def test_stage2_score_hgt_compiles_for_v5e(one_chip, bucket):
    cfg = LNNConfig(gnn_type="hgt", num_gnn_layers=3, hidden_dim=H_HGT,
                    num_heads=HEADS_HGT, mlp_dims=(64, 32), feat_dim=F_HGT)
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(functools.partial(lnn_init, cfg=cfg),
                       jax.random.PRNGKey(0)))

    def fused(p, emb, mask, feats, dt):
        return stage2_score_hgt_pallas(emb, mask, feats, dt,
                                       flatten_hgt_stage2_params(p),
                                       interpret=False)

    _assert_native(jax.jit(fused).lower(
        params, _spec(one_chip, (bucket, K, H_HGT)),
        _spec(one_chip, (bucket, K)), _spec(one_chip, (bucket, F_HGT)),
        _spec(one_chip, (bucket, K), jnp.int32)))


def test_hgt_relation_project_compiles_for_v5e(one_chip):
    lowered = jax.jit(functools.partial(hgt_relation_project, interpret=False)
                      ).lower(_spec(one_chip, (N_STAGE1, H_HGT)),
                              _spec(one_chip, (6, H_HGT, H_HGT)),
                              _spec(one_chip, (6, H_HGT)))
    _assert_native(lowered)


def test_hgt_edge_softmax_compiles_for_v5e(one_chip):
    edge = (N_STAGE1, D_STAGE1, H_HGT)
    lowered = jax.jit(
        functools.partial(hgt_edge_softmax_pallas, interpret=False)).lower(
        _spec(one_chip, (N_STAGE1, H_HGT)), _spec(one_chip, edge),
        _spec(one_chip, edge),
        _spec(one_chip, (N_STAGE1, D_STAGE1, HEADS_HGT)),
        _spec(one_chip, (N_STAGE1, D_STAGE1)))
    _assert_native(lowered)
