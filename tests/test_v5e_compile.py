"""Compile the served scan kernels and the query tower step for a described
TPU v5e, at the widths of the ``recall-imagebind`` configuration.

Nothing here runs: each test lowers and compiles with the TPU compiler
against a ``v5e:2x2`` topology description, which is what catches what the
Pallas interpreter accepts and Mosaic refuses (unlowerable primitives,
unaligned lane slices, more VMEM than a kernel may use). The topology is
described inside a module fixture, never at import, so every xdist worker
collects the same tests and only the worker given this file loads the TPU
compiler.
"""
from __future__ import annotations

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.device_bank import DeviceBank
from repro.kernels.retrieval_topk import kernel as K
from repro.kernels.retrieval_topk import ops as O

# round-1 scan of one query_batch drain: B=64 queries x 3 granularities
Q, E, K_TOP = 192, 1024, 10
N = 2 ** 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    return compiled


def test_int4_exhaustive_kernel_compiles(one_chip):
    fn = functools.partial(K.retrieval_topk_int4_pallas, k=K_TOP,
                           interpret=False, block_n=4096)
    c = _compile(lambda q, p, s, n: fn(q, p, s, n_valid=n),
                 _sds((Q, E), jnp.float32, one_chip),
                 _sds((N, E // 2), jnp.int8, one_chip),
                 _sds((N, 1), jnp.float32, one_chip),
                 _sds((), jnp.int32, one_chip))
    # the scan streams the slab: no bank-sized temporary
    assert c.memory_analysis().temp_size_in_bytes < N


def test_dense_kernel_compiles(one_chip):
    fn = functools.partial(K.retrieval_topk_pallas, k=K_TOP, interpret=False)
    _compile(lambda q, b, n: fn(q, b, n_valid=n),
             _sds((Q, E), jnp.float32, one_chip),
             _sds((2 ** 16, E), jnp.float32, one_chip),
             _sds((), jnp.int32, one_chip))


def test_gathered_kernel_compiles(one_chip):
    L = 8192
    fn = functools.partial(K.retrieval_topk_int4_gathered_pallas, k=K_TOP,
                           interpret=False)
    _compile(lambda q, gp, gs, ids, n: fn(q, gp, gs, ids, n_valid=n),
             _sds((Q, E), jnp.float32, one_chip),
             _sds((Q, L, E // 2), jnp.int8, one_chip),
             _sds((Q, L, 1), jnp.float32, one_chip),
             _sds((Q, L), jnp.int32, one_chip),
             _sds((), jnp.int32, one_chip))


def test_union_rows_entry_compiles(one_chip):
    impl, kwt = O._int4_dispatch_key("pallas", False, {"block_n": 4096})
    fn = O._jitted_int4_rows(impl, K_TOP, False, kwt)
    bucket = O.pow2_bucket(40_000)
    c = fn.lower(_sds((Q, E), jnp.float32, one_chip),
                 _sds((N, E // 2), jnp.int8, one_chip),
                 _sds((N, 1), jnp.float32, one_chip),
                 _sds((bucket,), jnp.int32, one_chip),
                 _sds((), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def bank4(topo):
    bank = DeviceBank(E, devices=topo.devices, impl="pallas",
                      interpret=False)
    assert bank.n_shards == 4
    return bank


def test_sharded_bank_search_compiles(bank4):
    rep = NamedSharding(bank4.mesh, P())
    rows = NamedSharding(bank4.mesh, P("bank"))
    fn = bank4._sharded_search_fn(K_TOP, bank4.impl, N)
    c = fn.lower(_sds((Q, E), jnp.float32, rep),
                 _sds((N, E // 2), jnp.int8, rows),
                 _sds((N, 1), jnp.float32, rows),
                 _sds((), jnp.int32, rep)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_sharded_bank_union_rows_compiles(bank4):
    rep = NamedSharding(bank4.mesh, P())
    rows = NamedSharding(bank4.mesh, P("bank"))
    width = O.pow2_bucket(10_000)
    fn = bank4._sharded_rows_fn(K_TOP, K_TOP, bank4.impl, N, width)
    c = fn.lower(_sds((Q, E), jnp.float32, rep),
                 _sds((N, E // 2), jnp.int8, rows),
                 _sds((N, 1), jnp.float32, rows),
                 _sds((4, width), jnp.int32, rows),
                 _sds((4,), jnp.int32, rows)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_sharded_bank_gathered_compiles(bank4):
    rep = NamedSharding(bank4.mesh, P())
    rows = NamedSharding(bank4.mesh, P("bank"))
    L = 2048
    fn = bank4._sharded_gathered_fn(K_TOP, bank4.impl, N, L)
    c = fn.lower(_sds((Q, E), jnp.float32, rep),
                 _sds((N, E // 2), jnp.int8, rows),
                 _sds((N, 1), jnp.float32, rows),
                 _sds((Q, L), jnp.int32, rep),
                 _sds((), jnp.int32, rep)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_text_tower_all_exits_step_compiles(one_chip):
    from repro.configs.base import get_arch
    from repro.models import imagebind as IB
    spec = get_arch("recall-imagebind")
    cfg, recall = spec.model, spec.recall
    B = spec.shape("query_batch").global_batch
    abstract = jax.eval_shape(functools.partial(IB.mem_init, cfg=cfg,
                                                recall=recall),
                              jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          abstract)
    tokens = _sds((B, cfg.tower("text").n_tokens), jnp.int32, one_chip)

    def step(p, x):
        return IB.mem_embed_all_exits(p, cfg, recall, "text", x)["exit_embs"]

    c = jax.jit(step).lower(params, tokens).compile()
    n_exits = len(recall.exit_layers(cfg.tower("text").n_layers))
    assert c.out_info.shape == (n_exits, B, cfg.embed_dim)
