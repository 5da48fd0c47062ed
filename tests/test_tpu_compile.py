"""Ahead-of-time compiles of the Bloom main path for a TPU v5e.

Interpret mode runs a Pallas kernel body in Python and accepts block shapes
and DMAs that the chip's compiler refuses, and the CPU accepts layouts that
need more than a chip's memory. These tests compile the fused popcount
pass, the Bloom build, the jnp TC fold and the 4-clique path (triangle
listing, 3-way AND over the list) for a described (not attached)
``v5e:2x2`` at the shapes of
the Graph500 Kronecker graph at scale 16, edge factor 16 (n = 65,536,
m = 910,200, d_max = 9,729), whose Bloom rows are W = 8, 116 and 462 words
at storage budgets 0.25, 4 and 16. Nothing runs: a compile that passes says
the chip's compiler accepts the program, not that its results are right.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the one given this file loads
the TPU compiler. Where no topology can be described, the tests skip.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine as ENG
from repro.core import triangle_count
from repro.core.graph import Graph
from repro.core.sketches import SketchSet, build_bloom
from repro.engine import setexpr

N, M, D_MAX = 65_536, 910_200, 9_729        # Graph500 scale 16, edge factor 16
WIDTHS = (8, 116, 462)                       # budgets 0.25, 4, 16 at scale 16
TUPLES = 1 << 16
SWEEP_ROWS = 8 * 512                         # 8 seeds x sweep_cap 512
T_CAP = 1 << 24                              # pow2_bucket of its 15.6M triangles


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off (a
    compile for a described chip is written to it but cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_hlo(fn, *args) -> str:
    """Compile ``fn`` for ``args`` and return the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("w", WIDTHS)
def test_fused_gather_compiles_for_v5e(one_chip, w, k):
    """The k-way AND in gather form, as the engine's kernel path runs it."""
    ce = setexpr.compile_expr(setexpr.and_all(*setexpr.rows(k)),
                              use_kernel=True, interpret=False)
    hlo = _mosaic_hlo(ce.ones, _sds((N, w), jnp.uint32, one_chip),
                      _sds((TUPLES, k), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("w", WIDTHS)
def test_fused_rows_sweep_cut_compiles_for_v5e(one_chip, w):
    """The dense form the sweep cut feeds with its computed prefix rows."""
    u, v = setexpr.rows(2)
    ce = setexpr.compile_expr(u & v, use_kernel=True, interpret=False)
    row = _sds((SWEEP_ROWS, w), jnp.uint32, one_chip)
    assert "tpu_custom_call" in _mosaic_hlo(ce.ones_rows, row, row)


def _graph(sharding) -> Graph:
    """The scale-16 graph's shapes, placed on ``sharding``."""
    i32 = jnp.int32
    return Graph(indptr=_sds((N + 1,), i32, sharding),
                 indices=_sds((2 * M,), i32, sharding),
                 adj=_sds((N, D_MAX), i32, sharding),
                 deg=_sds((N,), i32, sharding),
                 edges=_sds((M, 2), i32, sharding),
                 n_vertices=N, n_edges=M, d_max=D_MAX)


def test_bloom_build_fits_v5e(one_chip):
    """The device Bloom build of the scale-16 graph at the widest budget
    works from the edge list: its temporaries stay under 1.25 GiB (a
    bool[n, 32W] bitmap alone is 0.97 GB at W = 462, and a [rows, d_max, b]
    position layout once asked for 20 GB), and no instruction but a
    parameter has the padded adjacency's width d_max in its shape."""
    compiled = jax.jit(lambda g: build_bloom(g, WIDTHS[-1])).lower(
        _graph(one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30
    assert mem.temp_size_in_bytes < 1.25 * (1 << 30)
    padded = _d_max_instructions(compiled)
    assert not padded, padded[:3]


def _d_max_instructions(compiled) -> list:
    """Instructions other than parameters with d_max in their shape."""
    instruction = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = ")
    d_max_shape = re.compile(rf"\[(?:\d+,)*{D_MAX}(?:,\d+)*\]")
    return [line.strip() for line in compiled.as_text().splitlines()
            if instruction.match(line) and "parameter(" not in line
            and d_max_shape.search(line)]


def test_four_clique_programs_fit_v5e(one_chip, monkeypatch):
    """The 4-clique path of the scale-16 graph: the oriented CSR, its edges
    grouped by row width, the closing vertices of every oriented edge at
    the graph's widest oriented row (247 -> 256), the flat list of T_CAP
    rows, and the 3-way AND over the list on the jnp and the Mosaic path.
    They read ``indptr``/``indices``, never the padded adjacency: no
    instruction but a parameter has d_max in its shape. Temporaries stay
    under 1.25 GiB (the closing-vertex table alone is 0.9 GiB)."""
    from repro.core.algorithms import cliques as CL
    from repro.core.graph import degree_oriented_csr
    from repro.kernels import fused_expr

    def vec(*shape):
        return _sds(shape, jnp.int32, one_chip)

    rows = M + CL.EDGE_CHUNK
    classes = math.isqrt(2 * M).bit_length() + 2
    programs = [
        degree_oriented_csr.lower(vec(N + 1), vec(2 * M)),
        CL._edge_classes.lower(vec(N + 1), vec(M), vec(M)),
        CL._closing_vertices.lower(vec(N + 1), vec(M), vec(M), vec(M),
                                   vec(M), vec(classes), None, width=256,
                                   num_hashes=0, seed=0),
        CL._triangle_rows.lower(vec(M), vec(M), vec(rows, 256), vec(rows),
                                capacity=T_CAP, cols=128),
    ]
    graph = _graph(one_chip)
    sketch = SketchSet(data=_sds((N, WIDTHS[1]), jnp.uint32, one_chip),
                       kind="bf", num_hashes=2, k=0, seed=0, n=N)
    plan = ENG.plan_for(graph, sketch)
    # the engine's compiled AND, lowered for the chip and not interpreted
    monkeypatch.setattr(fused_expr, "default_interpret", lambda: False)
    setexpr.cache_clear()
    try:
        for use_kernel in (False, True):
            programs.append(CL._bloom_triple_sums.lower(
                sketch, vec(T_CAP, 3), vec(),
                plan=plan.with_(use_kernel=use_kernel),
                chunk=1 << (plan.edge_chunk.bit_length() - 1)))
    finally:
        setexpr.cache_clear()
    for lowered in programs:
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * (1 << 30)
        padded = _d_max_instructions(compiled)
        assert not padded, padded[:3]
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_tc_fold_compiles_for_v5e(one_chip):
    """The whole jnp TC fold over all scale-16 edges at budget 4 fits."""
    graph = _graph(one_chip)
    sketch = SketchSet(data=_sds((N, WIDTHS[1]), jnp.uint32, one_chip),
                       kind="bf", num_hashes=2, k=0, seed=0, n=N)
    plan = ENG.plan_for(graph, sketch)
    compiled = jax.jit(lambda g, s: triangle_count(g, s, plan=plan)).lower(
        graph, sketch).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
