"""The trace reduction: interval arithmetic, kernel events by name, idle
gaps by host span, and the host spans of a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

from bench import common
from bench import trace as tr


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]


def test_busy_and_idle_gaps_inside_the_window():
    ops = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (9.0, 12.0)]
    assert tr.busy_seconds(ops, 1.0, 10.0) == pytest.approx(1.0 + 1.0 + 1.0)
    gaps = tr.idle_gaps(ops, 1.0, 10.0)
    assert gaps == [(4.0, 9.0), (2.0, 3.0)]


def _reduced():
    ops = {"/device:TPU:0": [
        ("fusion.1", "", 0.0, 1.0),
        ("fused_ns_rhs", "", 1.0, 3.0),
        ("fused_ns_rhs.7", "tpu_custom_call", 4.0, 5.0),
        ("copy-start.3", "copy-start(%fused_ns_rhs.7)", 5.0, 5.5),
        ("fusion.2", "", 8.0, 9.0),
    ]}
    spans = {tr.WINDOW_SPAN: [(0.5, 10.0)],
             "iteration.wait": [(5.0, 8.5)], "serve.flush": [(9.0, 10.0)]}
    return tr.Reduced(window=(0.5, 10.0), ops=ops, spans=spans)


def test_kernel_events_by_their_own_name_not_their_operands():
    secs, count = _reduced().kernel("fused_ns_rhs")
    assert (secs, count) == (3.0, 2)
    assert _reduced().kernel("absent") == (0.0, 0)


def test_busy_share_top_ops_and_gap_labels():
    r = _reduced()
    assert r.window_s == pytest.approx(9.5)
    assert r.busy_s == pytest.approx(0.5 + 2.0 + 1.5 + 1.0)
    top = dict(r.top_ops())
    assert top["fused_ns_rhs"] == pytest.approx(2.0)
    assert top["fusion.1"] == pytest.approx(0.5)
    gaps = r.top_gaps()
    assert gaps[0] == ["iteration.wait", pytest.approx(2.5)]
    assert gaps[1][0] == "none" and gaps[2][0] == "serve.flush"


def test_reduce_reads_host_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = common.Spans(tracing=True)
    jax.profiler.start_trace(str(tmp_path))
    with spans("bench.window"):
        for _ in range(3):
            with spans("iteration.wait"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce(str(tmp_path), common.SPAN_NAMES)
    assert len(r.spans["iteration.wait"]) == 3
    assert r.window_s > 0
    lo, hi = r.window
    assert all(lo <= s <= e <= hi for s, e in r.spans["iteration.wait"])
    assert spans.total("iteration.wait")[1] == 3
