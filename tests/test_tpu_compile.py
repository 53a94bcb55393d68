"""Compile for one described TPU v5e chip: every Pallas kernel of the
preprocessing path, ``flash_attention_fwd``, ``convert_jit``, and the
windowed pointer build and the whole-graph GAT at ogbn-products' size.

Nothing runs here. The TPU compiler compiles for a chip that is described,
not attached, so this catches what Mosaic or XLA:TPU would refuse (block
shapes, layouts, unlowerable primitives, device memory) at no chip time.
A kernel Mosaic refuses is a strict xfail with the compiler's reason, so a
kernel that starts to compile fails here until it leaves
``kernels.ops.MOSAIC_REFUSALS``.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import scopes
from repro.core.costmodel import EngineConfig
from repro.core.graph import COO, CSC
from repro.engine.service import convert_jit, gat_infer_jit
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.pointer_window import windowed_pointer_array
from repro.launch import hlo_analysis
from repro.models.gnn import GNNConfig, gnn_init

N = 1 << 16  # elements per kernel call: 16 tiles of the default w_upe
T = 4096     # targets / queries per call


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of any cache a caller configured."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _kernel_case(name):
    """(fn, [(shape, dtype)]) for kernel ``name`` at real widths."""
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    return {
        "set_count_less": (
            lambda e, t: ops.set_count_less(e, t),
            [((N,), i32), ((T,), i32)]),
        "filter_tree_lookup": (
            lambda k, p, t: ops.filter_tree_lookup(k, p, t),
            [((N,), i32), ((N,), i32), ((T,), i32)]),
        "segment_sum_sorted": (
            lambda d, m: ops.segment_sum_sorted(d, m, n_nodes=T),
            [((N,), i32), ((N, 128), f32)]),
        "flash_attention_fwd": (
            lambda q, k, v: flash_attention_fwd(q, k, v),
            [((8, 2048, 128), bf16)] * 3),
        "prefix_partition": (
            lambda v, c: ops.prefix_partition(v, c),
            [((N,), i32), ((N,), jnp.bool_)]),
        "radix_sort_chunks": (
            lambda k, v: ops.radix_sort_chunks(k, v, chunk=T, key_bits=16),
            [((N,), i32), ((N,), i32)]),
        "radix_sort_chunks_keys": (
            lambda k: ops.radix_sort_chunks_keys(k, chunk=T, key_bits=16),
            [((N,), i32)]),
        "global_digit_pass": (
            lambda k, v: ops.global_digit_pass(k, v, shift=0, tile=T),
            [((N,), i32), ((N,), i32)]),
        "fused_merge_rounds": (
            lambda k, v: ops.fused_merge_rounds(k, v, run=T)[:2],
            [((N,), i32), ((N,), i32)]),
        "rank_search_tiles": (
            lambda a, q: ops.rank_search_tiles(a, q),
            [((N,), i32), ((T,), i32)]),
        "reindex_rename_tiles": (
            lambda a, t, q: ops.reindex_rename_tiles(a, t, q),
            [((N,), i32), ((N,), i32), ((T,), i32)]),
    }[name]


COMPILING = ["set_count_less", "filter_tree_lookup", "segment_sum_sorted",
             "flash_attention_fwd"]


def _refused(name):
    return pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=NotImplementedError,
        reason=f"Mosaic: {ops.MOSAIC_REFUSALS[name]}"))


@pytest.mark.parametrize(
    "name", COMPILING + [_refused(n) for n in ops.MOSAIC_REFUSALS])
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the HLO"


def _coo_spec(sharding, n_nodes, capacity):
    s = jax.ShapeDtypeStruct((capacity,), jnp.int32, sharding=sharding)
    return COO(dst=s, src=s,
               n_edges=jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
               n_nodes=n_nodes)


@pytest.mark.parametrize("cfg", [
    EngineConfig(),
    EngineConfig(use_pallas=True, sort_strategy="xla_sort",
                 reindex_strategy="unfused"),
], ids=["default", "pallas_pointer_build"])
def test_convert_jit_compiles_for_v5e(cfg, one_chip, no_compile_cache):
    coo = _coo_spec(one_chip, 20_000, 1 << 20)
    compiled = convert_jit.lower(coo, cfg=cfg).compile()
    mem = compiled.memory_analysis()
    # two int32 edge arrays in, one out: the program holds O(E) state
    assert mem.argument_size_in_bytes >= 2 * 4 * (1 << 20)
    # one kernel either way, the pointer build's: the windowed SCR count
    # by default, the all-pairs SCR count under use_pallas; it carries
    # the scope that pointer_ms.convert reads
    text = compiled.as_text()
    kernels = [m.group(1) for m in map(hlo_analysis._INSTR_RE.match,
                                       text.splitlines())
               if m and 'custom_call_target="tpu_custom_call"' in m.string]
    assert len(kernels) == 1
    assert hlo_analysis.op_scopes(text)[kernels[0]] == scopes.CONVERT_POINTER


def test_two_pass_convert_runs_one_sort_for_v5e(one_chip, no_compile_cache):
    """300,000 nodes are 19-bit ids, too wide for the packed key, so the
    default convert orders by the two-pass scheme on ``xla_sort``: ONE
    two-key sort over the (dst, src) columns in ``convert.ordering``,
    with no ``iota`` operand (XLA:TPU's way to make a sort stable) where
    two stable LSD passes would sort three arrays each."""
    compiled = convert_jit.lower(_coo_spec(one_chip, 300_000, 1 << 20),
                                 cfg=EngineConfig()).compile()
    text = compiled.as_text()
    scope = hlo_analysis.op_scopes(text)
    sorts = [line for line in text.splitlines()
             if (m := hlo_analysis._INSTR_RE.match(line))
             and " sort(" in line
             and scope[m.group(1)] == scopes.CONVERT_ORDERING]
    assert len(sorts) == 1, sorts
    operands = re.findall(r"%([\w.\-]+)",
                          sorts[0].split(" sort(", 1)[1].split(")", 1)[0])
    assert len(operands) == 2, sorts[0]
    assert not any(o.startswith("iota") for o in operands), sorts[0]


def test_windowed_pointer_build_compiles_at_products_size(one_chip,
                                                          no_compile_cache):
    """ogbn-products: 2^27 edge slots, 2,449,029 nodes. Targets or output
    as an (N+1, 1) column pad 128-fold along lanes, 2.5 GB of temp."""
    dst = jax.ShapeDtypeStruct((1 << 27,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda d: windowed_pointer_array(
        d, 2_449_029)).lower(dst).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_whole_graph_gat_compiles_at_products_size(one_chip,
                                                   no_compile_cache):
    """PyG's OGB GAT (3 layers, 4 heads of 128, skips) over ogbn-products:
    2,449,029 nodes, 2^27 edge slots. A layer holds its input-and-output
    buffer and z (5.04 GB each at 512 wide, padded rows) and one step's
    chunks; a layer that kept its input beside its output would need
    15 GB of temp and not fit the chip."""
    n, cap = 2_449_029, 1 << 27
    cfg = GNNConfig(name="gat-products", kind="gat", n_layers=3,
                    d_hidden=128, n_heads=4, out_heads=4, skip=True)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    csc = CSC(ptr=spec((n + 1,)), idx=spec((cap,)), n_edges=spec(()),
              n_nodes=n)
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), jax.eval_shape(
        lambda: gnn_init(cfg, jax.random.PRNGKey(0), d_in=100,
                         n_classes=47)))
    with jax.default_matmul_precision("high"):
        compiled = gat_infer_jit.lower(csc, spec((n, 100), jnp.float32),
                                       params, cfg=cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 12e9
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 14e9
    text = compiled.as_text()
    assert hlo_analysis.op_counts(text).get("scatter", 0) == 0
    assert set(hlo_analysis.op_scopes(text).values()) - {None} == {
        scopes.INFER_PROJECT, scopes.INFER_EDGE}


@pytest.mark.parametrize("name", sorted(ops.MOSAIC_REFUSALS))
def test_refused_kernel_raises_named_error_on_tpu(name, monkeypatch):
    """On a TPU backend a refused kernel is never interpreted or replaced
    by the jnp reference: asking for it raises, naming the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stand_in = ops.refused_on_tpu(name, lambda *a: None)
    with pytest.raises(NotImplementedError, match=name):
        stand_in()


def test_pipeline_routes_refuse_on_tpu(monkeypatch):
    from repro.core.pipeline import kernel_fns
    cfg = EngineConfig(use_pallas=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chunk, count, merge, digit, rank, rename = kernel_fns(cfg)
    assert count is ops.pallas_count_fn  # the SCR count kernel compiles
    for fn, name in [(chunk, "radix_sort_chunks"),
                     (merge, "fused_merge_rounds"),
                     (digit, "global_digit_pass"),
                     (rank, "rank_search_tiles"),
                     (rename, "reindex_rename_tiles")]:
        with pytest.raises(NotImplementedError, match=name):
            fn()
