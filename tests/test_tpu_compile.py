"""The kernels as the TPU compiler sees them: every kernel entry of
``kernels/ops.py`` compiled for a described v5e chip at deployment widths
(2^26-slot tables, Q = 65536, a 4096-entry rebuild chunk), without a chip.

The process itself runs on the CPU, where the kernels would be emulated;
``on_tpu`` points the platform resolver at the TPU for these compiles only.
The topology is described inside a fixture (never at import: only one
process may load the TPU library at a time), and the persistent compilation
cache is off around the compiles (a described chip's executable cannot be
read back).  Kernels Mosaic refuses are strict xfails carrying the error
that ``backend.BucketBackend.tpu_refusal`` records, so a kernel that starts
compiling fails this file until its backend's refusal is lifted.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import backend, dhash, engine, hashing
from repro.kernels import ops, probe

C, Q, CH = 1 << 26, 1 << 16, 4096
MP, MC = 128, 64          # the linear probe and chain bounds of dhash.make
I32, B = jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """One table per chip of the described host, along the axis ``shard``."""
    return NamedSharding(Mesh(np.asarray(topo.devices), ("shard",)),
                         P("shard"))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(probe, "platform", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _args(sharding, name):
    """Deployment-width operand shapes of ops entry ``name``."""
    def s(shape, dt=I32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    q, m = s((Q,)), s((Q,), B)
    tab = (s((C,)),) * 3
    hz = (s((CH,)), s((CH,)), s((CH,), B))
    tct = (s((C // 8, 8)),) * 3                 # twochoice, W = 8
    n, nb = C // 2, C // 32                     # chain arena and buckets
    chain = ((s((n,)),) * 3, (s((n,)), s((nb,))),
             (s((nb,)), s((nb,)), s(()), s(())))
    return {
        "probe_lookup": (*tab, q, q),
        "probe_delete": (*tab, q, q, m),
        "probe_insert": (*tab, q, q, q, m),
        "insert_retry_share": (tab[0], tab[2], q, q, m),
        "ordered_lookup": (tab, tab, *hz, q, q, q),
        "ordered_lookup_fused": (tab, tab, *hz, q, q, q),
        "ordered_delete_fused": (tab, tab, *hz, q, q, q, m),
        "extract_chunk_fused": (*tab, s(())),
        "twochoice_lookup": (*tct, q, q, q),
        "twochoice_insert": (*tct, q, q, q, q, m),
        "twochoice_delete": (*tct, q, q, q, m),
        "twochoice_ordered_lookup": (tct, tct, *hz, q, q, q, q, q),
        "twochoice_ordered_delete": (tct, tct, *hz, q, q, q, q, q, m),
        "chain_lookup_fused": (*chain, q, q),
        "chain_delete_fused": (*chain, q, q, m),
        "chain_insert_fused": (*chain, s((n,)), s(()), q, q, q, m),
        "chain_ordered_lookup": (*chain, *chain, *hz, q, q, q),
        "chain_ordered_delete": (*chain, *chain, *hz, q, q, q, m),
    }[name]


_CHAIN_ENTRIES = ("chain_lookup_fused", "chain_delete_fused",
                  "chain_insert_fused", "chain_ordered_lookup",
                  "chain_ordered_delete")
_STATIC = {"extract_chunk_fused": {"chunk": CH},
           **{n: {"max_chain": MC} for n in _CHAIN_ENTRIES},
           **{n: {"max_probes": MP} for n in (
               "probe_lookup", "probe_delete", "probe_insert",
               "insert_retry_share", "ordered_lookup",
               "ordered_lookup_fused", "ordered_delete_fused")}}


def _refused(name, error):
    return pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=error,
        reason=backend.get("chain" if name.startswith("chain")
                           else "twochoice").tpu_refusal))


@pytest.mark.parametrize("name", [
    "probe_lookup", "probe_delete", "probe_insert", "insert_retry_share",
    "ordered_lookup", "ordered_lookup_fused", "ordered_delete_fused",
    "extract_chunk_fused",
    *(_refused(n, ValueError) for n in (
        "twochoice_lookup", "twochoice_insert", "twochoice_delete",
        "twochoice_ordered_lookup", "twochoice_ordered_delete")),
    *(_refused(n, NotImplementedError) for n in _CHAIN_ENTRIES),
])
def test_kernel_entry_compiles_for_v5e(one_chip, on_tpu, name):
    fn = functools.partial(getattr(ops, name), **_STATIC.get(name, {}))
    compiled = jax.jit(fn).lower(
        *_args(one_chip, name)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _full_table_ops(compiled) -> dict:
    """``{(computation, op): count}`` of the whole-table (``s32[C]``, or
    ``s32[1,C]``: one shard of a stack) ``copy`` and ``select`` ops in a
    compiled program, fusions included."""
    counts, comp = {}, None
    for line in compiled.as_text().splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        op = re.search(rf"= s32\[(?:1,)?{C}\]\{{[^}}]*\}} (copy|select)\(",
                       line)
        if op:
            counts[comp, op.group(1)] = counts.get((comp, op.group(1)), 0) + 1
    return counts


def _deployment_state(sharding, shards=None):
    """The paper cell's table (2^26 slots, chunk 4096, the jnp op set) as
    abstract operands on the described chip, or a stack of ``shards`` of
    them laid one per chip."""
    st = jax.eval_shape(
        lambda: dhash.make("linear", capacity=C // 2, chunk=CH, seed=1,
                           fused=False) if shards is None else
        dhash.make_stack(shards, "linear", capacity=C // 2, chunk=CH,
                         seed=1, fused=False))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        st)


@pytest.mark.parametrize("program", ["step", "step_continuous", "lookup"])
def test_engine_programs_move_no_whole_table(one_chip, on_tpu, program):
    """The census of whole-table work: the engine's step (8192 inserts and
    deletes, 65536 lookups), with and without continuous rebuild, and the
    jitted lookup hold no whole-table ``select`` (the step holds no epoch
    swap) and no whole-table ``copy`` (no table passes through a
    conditional branch that hands it on unwritten: the insert and the
    landing run their claim loops outside any)."""
    def s(n, dt=I32):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    st, ins = _deployment_state(one_chip), 8192
    if program == "lookup":
        fn, args = jax.jit(dhash.lookup), (st, s(Q))
    else:
        from repro.core.engine import DHashEngine
        eng = DHashEngine(dhash.make("linear", capacity=64, chunk=CH,
                                     fused=False),
                          continuous_rebuild=program == "step_continuous")
        fn = eng._step_fn
        args = (st, s(Q), s(ins), s(ins), s(ins), s(ins, B), s(ins, B))
    assert _full_table_ops(fn.lower(*args).compile()) == {}


@pytest.mark.parametrize("program", ["step", "continuous_start"])
def test_routed_programs_move_no_whole_table(four_chips, on_tpu, program):
    """The census for the table hash-partitioned over the described
    v5e:2x2 (four chips, 2^26 slots per shard, chunk 4096): the sharded
    engine's step (65536 lookups and 8192 inserts and deletes per chip,
    each routed at full width) holds no whole-table ``select`` or
    ``copy``; nor does the start that continuous rebuild runs at the poll
    to open each epoch on every shard, which clears the standby table in
    place (it copies and allocates nothing).  The step is one program
    with or without continuous rebuild."""
    s = four_chips.mesh.size
    st = _deployment_state(four_chips, shards=s)
    if program == "step":
        def op(n, dt=I32):
            return jax.ShapeDtypeStruct((s * n,), dt, sharding=four_chips)
        ins = 8192
        fn = engine.routed_step_fn(four_chips, hashing.fresh("mix32", 1))
        args = (st, op(Q), op(ins), op(ins), op(ins), op(ins, B), op(ins, B))
    else:
        fn = engine.routed_start_fn(four_chips)
        args = (st, jax.ShapeDtypeStruct((), I32))
    compiled = fn.lower(*args).compile()
    assert _full_table_ops(compiled) == {}
    if program == "continuous_start":
        # every table array's output is its donated input: none is new
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 4 * C


@pytest.mark.parametrize("name", ["linear", "twochoice", "cuckoo", "chain"])
def test_make_fused_refuses_backends_the_tpu_cannot_compile(monkeypatch,
                                                            name):
    monkeypatch.setattr(probe, "platform", lambda: "tpu")
    refusal = backend.get(name).tpu_refusal
    if refusal is None:
        assert dhash.make(name, 64, chunk=16, fused=True).fused
        return
    with pytest.raises(ValueError, match="no fused kernels that compile"):
        dhash.make(name, 64, chunk=16, fused=True)
    assert not dhash.make(name, 64, chunk=16, fused=False).fused


def test_platform_resolver_refuses_other_platforms(monkeypatch):
    assert probe.platform() == jax.default_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="no Pallas kernel path"):
        probe.platform()
