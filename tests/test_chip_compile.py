"""Compiles for a described TPU v5e, without the chip.

The TPU compiler is installed beside jax and compiles for a topology that
is described, not attached: it refuses what the chip would refuse (VMEM
over the kernel's scoped limit, a kernel that cannot be partitioned, a
shard_map body whose types do not check).  Nothing runs here.

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so no import, ``parametrize`` or ``skipif``
may touch it.  Keep every such compile in this one file.
"""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.matmul import matmul
from repro.kernels.matmul.grouped import gmm_pallas
from repro.kernels.matmul.kernel import vmem_working_set_bytes, zorder_matmul
from repro.mesh import make_mesh
from repro.models.registry import build_model
from repro.plan import build_plan, lower_shard_map
from repro.runtime.serve import compile_bucket
from repro.tune import candidate_space


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU library otherwise logs to a fixed directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


# (m, k, n, out dtype): h2o-danube-3-4b's linears of a 4096-row prefill
# (the MLP gate/up and down projections, q/o, and k/v, whose 960 columns
# are no multiple of 128), and granite-20b's Cannon qkv block on a 2x2
# torus (K = 3072, N = 3200, float32 partial sums)
@pytest.mark.parametrize("m,k,n,out_dtype", [
    (4096, 3840, 10240, jnp.bfloat16),
    (4096, 3840, 960, jnp.float32),
    (4096, 3840, 3840, jnp.bfloat16),
    (4096, 3840, 960, jnp.bfloat16),
    (4096, 10240, 3840, jnp.bfloat16),
    (2048, 3072, 3200, jnp.float32),
])
def test_kernel_default_blocks_compile(one_chip, m, k, n, out_dtype):
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    hlo = _hlo(functools.partial(matmul, out_dtype=out_dtype), a, b)
    assert "tpu_custom_call" in hlo
    # the default blocks divide these shapes: no operand is padded
    assert not re.search(r"\spad\(", hlo)


def test_cannon_2x2_program_holds_kernel(topo, monkeypatch):
    # jax.default_backend() is the CPU here, so steer the local-multiply
    # decision to the branch the chip takes
    import repro.dist.local as local

    monkeypatch.setattr(
        local, "_pallas_eligible",
        lambda a, b: a.ndim == 2 and b.ndim == 2
        and min(a.shape[0], a.shape[1], b.shape[1]) >= 128)
    mesh = make_mesh((2, 2), ("x", "y"), devices=topo.devices)
    m, k, n = 4096, 3840, 10240
    plan = build_plan(m, n, k, mesh=mesh, strategy="cannon",
                      a_dtype=jnp.bfloat16, b_dtype=jnp.bfloat16,
                      out_dtype=jnp.float32, use_cache=False)
    rep = NamedSharding(mesh, P())
    hlo = _hlo(lower_shard_map(plan),
               jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=rep),
               jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=rep))
    assert "tpu_custom_call" in hlo
    assert "collective-permute" in hlo


def test_largest_fp32_candidate_compiles(one_chip):
    # the tuner may pick any candidate it admits: its largest fp32 working
    # set must fit the kernel's scoped VMEM
    m = n = k = 4096
    cands = candidate_space(m, n, k, 4, out_dtype_bytes=4)
    bm, bn, bk, order = max(
        cands, key=lambda c: vmem_working_set_bytes(c[0], c[1], c[2], 4, 4))
    f = functools.partial(zorder_matmul, block_m=bm, block_n=bn, block_k=bk,
                          order=order, out_dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(f, x, x)


# (rows, k, n): deepseek-moe's expert GEMMs over its 8 held experts, at the
# engine cell's 30720-row dispatch buffer (gate/up, then down) and a decode
# step's 48 rows
@pytest.mark.parametrize("m,k,n", [(30720, 2048, 1408), (30720, 1408, 2048),
                                   (48, 2048, 1408)])
def test_grouped_kernel_compiles(one_chip, m, k, n):
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    hlo = _hlo(gmm_pallas, x, w, sizes)
    assert "tpu_custom_call" in hlo and "grouped_matmul" in hlo


# -- the serving pair: the KV cache updated in place -------------------------

_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\((.*)$")
_ARRAY = re.compile(r"([a-z]+\d*)\[([\d,]*)\]\{([^}]*)\}")
# ops that write no new buffer: the async starts' results hold their operand
_NO_WRITE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "conditional", "call", "constant", "copy-start", "slice-start"}


def _computations(hlo):
    """The entry's name, and {computation: {op: (type, opcode, rest)}}."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            m = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
            cur = m and m.group(2)
            if m:
                comps[cur] = {}
                entry = cur if m.group(1) else entry
        elif cur and (m := _OP.match(line)):
            comps[cur][m.group(1)] = m.groups()[1:]
    return entry, comps


def _size(dims):
    return math.prod(int(d) for d in dims.split(",") if d)


def _cache_writes(hlo, layer, max_seq):
    """(whether in the entry computation, op name) of each op outside a
    fusion's body that writes a bf16 buffer to HBM (no memory space in its
    layout), of the cache's sequence length and a multiple of a layer,
    other than a dynamic-update-slice, or a fusion whose first one, that
    writes less than a layer into the cache: the token's or the prompt's
    slots."""
    entry, comps = _computations(hlo)
    fused = {c for ops in comps.values() for _, opcode, rest in ops.values()
             if opcode == "fusion"
             for c in re.findall(r"calls=%([\w.\-]+)", rest)}

    def small(comp, rest):
        update = re.findall(r"%([\w.\-]+)", rest)[1]
        return all(_size(d) < layer for _, d, _ in
                   _ARRAY.findall(comps[comp][update][0]))

    def small_update(comp):
        for typ, opcode, rest in comps[comp].values():
            if opcode == "dynamic-update-slice":
                return small(comp, rest)
            if opcode == "fusion":
                inner = small_update(re.search(r"calls=%([\w.\-]+)", rest)[1])
                if inner is not None:
                    return inner
        return None

    out = []
    for comp, ops in comps.items():
        if comp in fused:
            continue
        for name, (typ, opcode, rest) in ops.items():
            if opcode in _NO_WRITE or not any(
                    dt == "bf16" and _size(d) % layer == 0
                    and str(max_seq) in d.split(",") and "S(" not in lay
                    for dt, d, lay in _ARRAY.findall(typ)):
                continue
            call = re.search(r"calls=%([\w.\-]+)", rest)
            if opcode == "fusion" and small_update(call[1]):
                continue
            if opcode == "dynamic-update-slice" and small(comp, rest):
                continue
            out.append((comp == entry, name))
    return out


# h2o-danube-3-4b as the serve cell runs it (no window), and deepseek-moe-16b
# at one chip's share of its experts; three layers for dsmoe, so that behind
# its leading dense layer the MoE stack is a loop as at full depth (a stack
# of one layer is not looped, and XLA then stages its cache through VMEM)
@pytest.mark.parametrize("config,changes", [
    ("h2o-danube-3-4b", dict(num_layers=2, window=0, tie_embeddings=False)),
    ("deepseek-moe-16b", dict(num_layers=3, experts_held=8)),
])
def test_serving_pair_updates_cache_in_place(one_chip, config, changes):
    batch, seq, max_seq = 8, 512, 2048
    cfg = dataclasses.replace(get_config(config), dtype="bfloat16", **changes)
    model = build_model(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    progs = compile_bucket(model, params, batch, seq, max_seq, offsets=True)
    cache = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    leaves = jax.tree.leaves(cache)
    layer = batch * max_seq * cfg.num_kv_heads * cfg.head_dim
    for c in (progs.step, progs.prefill):
        # the donated cache is the output's buffer, all of it
        assert c.memory_analysis().alias_size_in_bytes >= sum(
            a.size * a.dtype.itemsize for a in leaves)
        assert c.input_formats[0][1] == c.output_formats[1]
    # one format, the default, from the cache's making to its last step
    assert progs.init_cache.output_formats == progs.prefill.input_formats[0][1]
    assert progs.prefill.input_formats[0][1] == progs.step.input_formats[0][1]
    # neither program writes a layer of the cache anywhere: only the
    # token's slot, or the prompt's; a stack of one layer (dsmoe's dense
    # one) is staged through VMEM and written back once
    for c in (progs.step, progs.prefill):
        writes = _cache_writes(c.as_text(), layer, max_seq)
        assert len(writes) <= ("dense_layers" in cache), writes
