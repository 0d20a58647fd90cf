"""Compile the served path's device programs for one described v5e chip.

No chip is attached: the TPU compiler, which is installed, compiles for a
topology that is only described, and refuses what the chip would refuse
(illegal block shapes, VMEM overflow, programs that do not fit HBM).
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture and never while a
module is imported: only one process at a time may load the TPU library,
and under several pytest workers only the worker given this file loads
it.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.faas import FunctionSpec, compile_batched_handler
from repro.core.store import merge_many_fn, store_new
from repro.core.versioning import MAX_NODES
from repro.kernels.enoki_merge.kernel import enoki_merge_rows, merge_geometry

SMOKE_ARENA = (262_144, 256)        # chip_smoke.py: 1 KiB records
WIDEST = 2560                       # widest float32 payload admitted
HBM_BYTES = 16 * 2**30              # one v5e chip


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes its logs to a fixed shared directory
        # unless told otherwise: keep them in this session's temp tree
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu")))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but can never be read back: keep the cache off meanwhile
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _arena(slots, width, sharding):
    shapes = jax.eval_shape(lambda: store_new(slots, width, MAX_NODES))
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), shapes)


@pytest.mark.parametrize("slots,width", [(8, 4), (64, 8), (64, 64),
                                         SMOKE_ARENA])
def test_fused_aligned_merge_compiles(one_chip, slots, width):
    """The K=4 fused delivery merge at the test arenas and the smoke's
    arena runs the Pallas kernel, not an interpreted loop."""
    acc = _arena(slots, width, one_chip)
    compiled = merge_many_fn(True).lower(acc, (acc,) * 4).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("slots,width,padded_arena", [
    (100, 8, False), (1001, 8, False), (1000, 64, False),
    (3000, 256, True), (3000, WIDEST, True), SMOKE_ARENA + (False,)])
def test_merge_kernel_compiles(one_chip, slots, width, padded_arena):
    """Slot counts that are no multiple of 8 (100, 1001) or of 128 (1000)
    in one whole-arena tile, arenas padded to the row tile (3000), the
    widest payload a keygroup may declare, and the smoke's arena."""
    padded, tile = merge_geometry(slots, width, 4)
    assert padded % tile == 0 and (padded > slots) == padded_arena
    val = _spec((slots, width), jnp.float32, one_chip)
    ver = _spec((slots,), jnp.int32, one_chip)
    compiled = jax.jit(enoki_merge_rows).lower(val, ver, val, ver).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _acc(kv, x):
    cur, _ = kv.get("acc")
    kv.set("acc", cur + x)
    return cur + x


def _peek(kv, x):
    vals, _ = kv.scan([f"user{i}" for i in range(8)])
    return vals[jnp.clip(x[0].astype(jnp.int32), 0, 7)]


@pytest.mark.parametrize("handler,bucket,x_width", [(_acc, 64, 256),
                                                    (_peek, 256, 1)])
def test_handler_compiles_at_smoke_arena(one_chip, handler, bucket,
                                         x_width):
    """The smoke's read-modify-write fold (``jit_scan``) and its widest
    read batch (``jit_map``) compile at the smoke's arena and fit HBM."""
    slots, width = SMOKE_ARENA
    spec = FunctionSpec(name=f"chip_{handler.__name__}", handler=handler,
                        keygroups=["chipkg"], codec_width=width)
    bh = compile_batched_handler(spec, 0, jnp.zeros((x_width,), jnp.float32))
    store = _arena(slots, width, one_chip)
    clock = _spec((), jnp.int32, one_chip)
    xs = _spec((bucket, x_width), jnp.float32, one_chip)
    if bh.read_only:
        lowered = bh.jit_map.lower(store, clock, xs)
    else:
        lowered = bh.jit_scan.lower(store, clock, xs,
                                    _spec((bucket,), jnp.bool_, one_chip))
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES // 2, mem


YCSB_ARENA = (262_144, 250)         # 10 fields x 25 float32 a record


def _keyed_read(kv, h):
    record, _ = kv.get(h)
    return record


def _field_update(kv, x):
    h, f, payload = x
    record, _ = kv.get(h)
    record = jax.lax.dynamic_update_slice(record, payload, (f * 25,))
    ok = kv.set(h, record)
    return jnp.where(ok, kv.state[1], -1)


@pytest.mark.parametrize("handler,bucket", [(_keyed_read, 256),
                                            (_field_update, 256)])
def test_request_keyed_handler_compiles_at_ycsb_arena(one_chip, handler,
                                                      bucket):
    """Handlers that take the key hash from the request: the read map
    (``jit_map``) and the one-field read-modify-write fold (``jit_scan``)
    at the widest bucket compile at 262,144 records of 250 floats and fit
    HBM, and the aligned merge of that arena runs the Pallas kernel."""
    slots, width = YCSB_ARENA
    spec = FunctionSpec(name=f"chip_{handler.__name__}", handler=handler,
                        keygroups=["chipkg"], codec_width=width)
    i32 = jnp.zeros((), jnp.int32)
    example = (i32 if handler is _keyed_read
               else (i32, i32, jnp.zeros((25,), jnp.float32)))
    bh = compile_batched_handler(spec, 0, example)
    store = _arena(slots, width, one_chip)
    clock = _spec((), jnp.int32, one_chip)
    xs = jax.tree.map(lambda a: _spec((bucket,) + a.shape, a.dtype,
                                      one_chip), example)
    if bh.read_only:
        lowered = bh.jit_map.lower(store, clock, xs)
    else:
        lowered = bh.jit_scan.lower(store, clock, xs,
                                    _spec((bucket,), jnp.bool_, one_chip))
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES // 2, mem
    compiled = merge_many_fn(True).lower(store, (store,) * 4).compile()
    assert "tpu_custom_call" in compiled.as_text()
