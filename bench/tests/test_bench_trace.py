"""The trace reduction and the per-layer readers, on synthetic event lists
and on a small trace recorded on the CPU."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

S = 1e9   # nanoseconds per second


def synthetic():
    """Two query spans over 10 s; device ops leave three idle gaps."""
    spans = [("query:Q1.1", 0, 6 * S), ("query:Q2.1", 4 * S, 10 * S)]
    ops = [("fusion.1", 0.5 * S, 2 * S, 0),
           ("fusion.1", 1.5 * S, 3 * S, 0),          # overlaps the first
           ("radix_hash_probe.3 custom-call (s32[8388608], s32[8388608]) "
            "tpu_custom_call", 5 * S, 5.5 * S, 0),
           ("while.3", 7 * S, 9 * S, 0),
           ("while.3", 9.5 * S, 11 * S, 0)]           # runs past the window
    return tr.Trace(ops=ops, spans=spans)


def test_union_clip_and_gaps():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.clip([(0, 5), (6, 8)], 1, 7) == [(1, 5), (6, 7)]
    assert tr.idle_gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.idle_gaps([], 0, 5) == [(0, 5)]


def test_summary_of_synthetic_trace():
    s = tr.summarize(synthetic())
    assert s.window_s == pytest.approx(10.0)
    # busy: [0.5, 3] + [5, 5.5] + [7, 9] + [9.5, 10] = 5.5 s
    assert s.busy_s == pytest.approx(5.5)
    assert s.idle_share == pytest.approx(0.45)
    assert s.op_seconds["fusion.1"] == pytest.approx(3.0)
    assert s.op_seconds["while.3"] == pytest.approx(2.5)
    assert s.seconds_where(lambda op: "tpu_custom_call" in op) == \
        pytest.approx(0.5)
    assert s.queries == ["Q1.1", "Q2.1"]
    # gaps, longest first, each named by the spans over its midpoint
    assert s.gaps[0] == ("query:Q1.1+query:Q2.1", pytest.approx(2.0))
    assert ("query:Q1.1", pytest.approx(0.5)) in s.gaps
    assert ("query:Q2.1", pytest.approx(1.5)) in s.gaps
    b = s.breakdown(top=2)
    assert b["device_ops"] == [["fusion.1", pytest.approx(3.0)],
                               ["while.3", pytest.approx(2.5)]]
    assert len(b["idle_gaps"]) == 2


def test_busy_time_is_averaged_over_devices():
    t = tr.Trace(ops=[("a", 0, 4 * S, 0), ("b", 0, 2 * S, 1)],
                 spans=[("query:Q", 0, 4 * S)], devices=2)
    s = tr.summarize(t)
    assert s.busy_s == pytest.approx(3.0)
    assert s.gaps == []


def test_op_names_from_tpu_hlo_text():
    probe = ('%radix_hash_probe.3 = (s32[8388608]{0:T(1024)S(1)}, '
             's32[8388608]{0:T(1024)}) custom-call(s32[8388608]{0:T(1024)} '
             '%and_select_fusion, s32[3072]{0:T(1024)S(1)} %copy-done.22), '
             'custom_call_target="tpu_custom_call", operand_layout_constraints'
             '={s32[8388608]{0}}')
    assert tr.op_name(probe) == ("radix_hash_probe.3 custom-call "
                                 "(s32[8388608], s32[8388608]) "
                                 "tpu_custom_call")
    loop = ('%while.8 = (u32[]{:T(128)}, s32[8388608]{0:T(1024)}) while(('
            'u32[]{:T(128)}, s32[8388608]{0:T(1024)}) %tuple.246), '
            'condition=%region_4')
    assert tr.op_name(loop) == "while.8 while (u32[], s32[8388608])"
    assert tr.op_name("copy.3") == "copy.3"
    reader = run._load_module(BENCH / "metrics"
                              / "kernels.segment_join_roofline.py", "k")
    assert reader.is_kernel(tr.op_name(probe))
    assert not reader.is_kernel(tr.op_name(loop))


def test_empty_trace():
    s = tr.summarize(tr.Trace(ops=[], spans=[]))
    assert s.window_s == 0 and s.idle_share is None


def test_xplane_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.cumsum(x * 3))
    f(jnp.arange(1 << 16)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for name in ("Q1.1", "Q2.1"):
        with jax.profiler.TraceAnnotation(f"query:{name}"):
            f(jnp.arange(1 << 16)).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    # on the CPU the host plane stands in for the device plane
    t = tr.load(path, device_plane=tr.HOST_PLANE, op_line="")
    assert [n for n, _, _ in t.spans] == ["query:Q1.1", "query:Q2.1"]
    assert t.ops
    s = tr.summarize(t)
    assert s.queries == ["Q1.1", "Q2.1"]
    assert 0 < s.busy_s <= s.window_s
    # a TPU plane is named /device:TPU:n; this trace has none
    assert tr.load(path).ops == []


class _Cfg:
    @staticmethod
    def query_bytes(t, rows):
        return 819_000_000          # 1 ms at 819 GB/s

    @staticmethod
    def kernel_bytes(t, rows):
        return 81_900_000 if t == "Q2.1" else 0


def _run(trace=None, kind="TPU v5 lite"):
    qs = [run.QueryRecord("Q1.1", latency_s=2.0, op_wall_s=1.5,
                          mem_wait_s=0.1, queue_wait_s=0.4, h2d_bytes=3e6),
          run.QueryRecord("Q2.1", latency_s=4.0, op_wall_s=3.9,
                          mem_wait_s=0.0, queue_wait_s=0.5, h2d_bytes=1e6)]
    return run.Run(config=_Cfg, table_rows={}, device_kind=kind,
                   queries=qs, window_s=6.0, trace=trace)


def test_counter_readers():
    r = _run()
    assert run.load_reader("frontend.host_ms")(r) == pytest.approx(250.0)
    assert run.load_reader("broker.wait_pct")(r) == pytest.approx(
        100 * 1.0 / 6.0)
    assert run.load_reader("fused.h2d_mb_per_query")(r) == pytest.approx(2.0)
    empty = run.Run(config=_Cfg, table_rows={}, device_kind="x",
                    queries=[], window_s=0.0)
    for m in ("frontend.host_ms", "broker.wait_pct",
              "fused.h2d_mb_per_query"):
        assert run.load_reader(m)(empty) is None


def test_trace_readers():
    s = tr.summarize(synthetic())
    r = _run(trace=s)
    # 2 queries x 1 ms of least time over 5.5 s busy
    assert run.load_reader("fused.roofline_pct")(r) == pytest.approx(
        100 * 2e-3 / 5.5)
    # 0.1 ms of kernel work over 0.5 s of kernel time
    assert run.load_reader("kernels.segment_join_roofline")(r) == \
        pytest.approx(100 * 1e-4 / 0.5)
    assert run.load_reader("device.idle_pct")(r) == pytest.approx(45.0)
    # no trace, or no kernel event: no reading, never 0
    for m in ("fused.roofline_pct", "kernels.segment_join_roofline",
              "device.idle_pct"):
        assert run.load_reader(m)(_run()) is None
    no_kernel = tr.Trace(ops=[o for o in synthetic().ops
                              if "custom" not in o[0]],
                         spans=synthetic().spans)
    assert run.load_reader("kernels.segment_join_roofline")(
        _run(trace=tr.summarize(no_kernel))) is None
    with pytest.raises(KeyError):
        run.load_reader("fused.roofline_pct")(_run(trace=s, kind="cpu"))
