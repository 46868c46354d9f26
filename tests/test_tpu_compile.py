"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

JAX's TPU compiler compiles for a chip that is described, not attached, so
these tests run on a CPU host and catch what the interpret-mode parity
tests cannot: Mosaic lowering gaps (``cumsum``, 1-D gathers), 64-bit values
in a kernel body under the program's ``jax_enable_x64``, and blocks that
miss XLA's tiling.  Widths are the real ones: 2^23 rows, 2048 and 4096
segments or code domains — the gate in ``tensor_engine.use_pallas``.

The topology is described inside a fixture: only the worker that runs this
file loads the TPU library, and a host that cannot describe it skips.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tensor_engine
from repro.kernels.segment_join import ops

ROWS = 1 << 23


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler logs under /tmp unless told otherwise
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("num_segments", [2048, 4096])
def test_segment_sum_compiles(one_chip, num_segments):
    _compile(lambda s, v: ops.segment_sum(s, v, num_segments), one_chip,
             ((ROWS,), jnp.int32), ((ROWS,), jnp.float32))


def test_radix_partition_compiles(one_chip):
    _compile(lambda b: ops.radix_partition(b, 5), one_chip,
             ((ROWS,), jnp.int32))


@pytest.mark.parametrize("domain", [2048, 4096])
def test_radix_hash_probe_compiles(one_chip, domain):
    _compile(lambda b, p: ops.radix_hash_probe(b, p, domain), one_chip,
             ((domain,), jnp.int32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("rows,domain_pad", [
    (2048, 3072),    # SSB's supplier: 2,048 codes and the dead slot
    (8192, 5120),    # the 4096 gate: several row tiles per domain block
])
def test_join_table_build_compiles(one_chip, rows, domain_pad):
    """The table build, domain blocks outer and row tiles inner, so each
    output block accumulates on consecutive grid steps."""
    from repro.kernels.segment_join import kernel

    _compile(lambda b, r: kernel.join_table_build_pallas(b, r, domain_pad),
             one_chip, ((rows,), jnp.int32), ((rows,), jnp.int32))


@pytest.mark.parametrize("max_abs,takes_kernel", [
    (50, False),   # SSB-like measure: 2^23 * 50 > 2^24, jnp core
    (1, True),     # 0/1 flags: 2^23 * 1 < 2^24, the f32 kernel is exact
])
def test_segment_sum_rule_exact_at_scale(monkeypatch, max_abs, takes_kernel):
    """The dispatch rule picks the kernel only where its f32 sum is exact,
    and the path it picks matches numpy exactly at 2^23 rows (the kernel
    in interpret mode here; the chip runs the same f32 arithmetic)."""
    monkeypatch.setenv("REPRO_PALLAS", "1")
    rng = np.random.default_rng(max_abs)
    seg = rng.integers(0, 2048, ROWS)
    vals = rng.integers(-max_abs, max_abs + 1, ROWS).astype(np.int64)
    rule = tensor_engine.segment_sum_uses_kernel(2048, ROWS, vals.dtype,
                                                 max_abs)
    assert rule is takes_kernel
    got = tensor_engine.segment_sum_dispatch(
        jnp.asarray(vals, jnp.float64), jnp.asarray(seg, jnp.int32), 2048,
        rule)
    want = np.bincount(seg, weights=vals, minlength=2048)
    np.testing.assert_array_equal(np.asarray(got), want)


@functools.lru_cache(maxsize=None)
def _sorted_program_text(one_chip, build_rows, probe_rows,
                         probe_order=False):
    """The compiled sorted-core program's text; cached, because a compile
    at the SF1 buckets takes minutes on a CPU host."""
    from repro.core import fused

    spec = fused.FusedSpec("k", None, (), ("b_v", "sum"))
    prog = fused._build_program(spec, "k", probe_rows,
                                probe_order=probe_order)

    def shape(n):
        return jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    return prog.lower({"k": shape(build_rows), "v": shape(build_rows)},
                      {"k": shape(probe_rows)}, {}, {}, {}, {}, scalar,
                      scalar, scalar).compile().as_text()


def test_fused_join_core_keeps_its_scopes_on_the_tpu(one_chip):
    """The TPU compiler keeps the join core's ``jax.named_scope`` names in
    the compiled program's ``op_name`` metadata, the prefix sum and the
    running max included (they are written as reduce-windows in scope)."""
    text = _sorted_program_text(one_chip, 1 << 12, 1 << 14)
    for path in ("join.sorted.sort/", "join.sorted.search/",
                 "join.prefix_sum/reduce_window_sum",
                 "join.expand/reduce_window_max"):
        assert f'op_name="jit(program)/{path}' in text, path


@pytest.mark.parametrize("build_rows,probe_rows,merged", [
    (1 << 20, ROWS, True),      # TPC-H Q9's partsupp and lineitem buckets
    (1 << 14, 1 << 6, False),   # few probes: the binary search is cheaper
])
def test_sorted_join_aligns_by_sort_where_probes_are_many(
        one_chip, build_rows, probe_rows, merged):
    """At the SF1 lineitem ⋈ partsupp buckets the sorted core aligns by
    one merged sort: ``join.sorted.search`` holds sorts and no ``while``
    (the binary search's loop); a small probe side keeps the search.  The
    program is the probe-order form, which unique build keys run."""
    text = _sorted_program_text(one_chip, build_rows, probe_rows, True)
    scope = 'op_name="jit(program)/join.sorted.search/'
    ops = [ln for ln in text.splitlines() if scope in ln]
    assert ops
    assert any(" sort(" in ln for ln in ops) is merged
    assert any(" while(" in ln for ln in ops) is not merged


def test_probe_order_core_expands_nothing_on_the_tpu(one_chip):
    """At the SF1 lineitem ⋈ partsupp buckets (2^20 build, 2^23 probe) the
    probe-order sorted core compiles with no scatter under ``join.expand``
    and no array of ``capacity + 1`` slots: the expanding core's int64
    seed slots, which the chip carries as pairs of u32."""
    text = _sorted_program_text(one_chip, 1 << 20, ROWS, True)
    expand = [ln for ln in text.splitlines()
              if 'op_name="jit(program)/join.expand/' in ln]
    assert expand
    assert not any(" scatter(" in ln for ln in expand)
    assert f"[{ROWS + 1}]" not in text
