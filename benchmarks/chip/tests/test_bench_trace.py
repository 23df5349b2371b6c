"""The trace reduction on a synthetic event list."""
import pytest

from benchmarks.chip import trace


def ms(x):
    return x * 1e6


# window 0..100 ms; host: batch 0-10, dispatch 10-12, batch 50-60, wait 90-100
SPANS = [
    ("bench.window", ms(0), ms(100)),
    ("bench.batch", ms(0), ms(10)),
    ("bench.dispatch", ms(10), ms(2)),
    ("bench.batch", ms(50), ms(10)),
    ("bench.dispatch", ms(60), ms(2)),
    ("bench.wait", ms(90), ms(10)),
]
DEVICES = {
    # busy 12-50 and 62-90, idle 0-12, 50-62, 90-100
    "/device:TPU:0": [
        ("fusion.1", ms(12), ms(20)),
        ("all-reduce-start.3", ms(32), ms(8)),   # 32-40 exposed
        ("all-gather-done.1", ms(40), ms(10)),   # 40-50 exposed
        ("fusion.1", ms(62), ms(28)),
    ],
    # busy 12-90 without collectives
    "/device:TPU:1": [("fusion.1", ms(12), ms(78))],
}


def test_union_merges_and_sorts():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 10)]) == [
        (0, 3), (5, 9)]


def test_subtract_leaves_uncovered_parts():
    assert trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) \
        == [(0, 2), (4, 8), (22, 25), (26, 30)]


def test_busy_idle_and_exposed_collectives():
    s = trace.summarize(DEVICES, SPANS)
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(0.1)
    # device 0 busy 38 + 28 = 66 ms, device 1 busy 78 ms
    assert s["busy_s"] == pytest.approx((0.066 + 0.078) / 2)
    assert s["idle_share"] == pytest.approx(1 - 0.072 / 0.1)
    assert s["collective_ops"] == 2
    # device 0: 8 + 10 ms exposed, device 1: none
    assert s["exposed_collective_s"] == pytest.approx(0.018 / 2)


def test_top_ops_average_over_devices():
    s = trace.summarize(DEVICES, SPANS)
    top = dict(s["top_ops"])
    assert top["fusion.1"] == pytest.approx((0.048 + 0.078) / 2)
    assert s["top_ops"][0][0] == "fusion.1"


def test_idle_gaps_named_by_host_span():
    s = trace.summarize(DEVICES, SPANS)
    gaps = s["idle_gaps"]
    # device 0: 0-12 (batch), 50-62 (batch), 90-100 (wait);
    # device 1: 0-12 (batch), 90-100 (wait)
    assert [g[0] for g in gaps[:3]] == ["bench.batch"] * 3
    assert gaps[0][1] == pytest.approx(0.012)
    assert sorted(g[0] for g in gaps) == ["bench.batch"] * 3 + ["bench.wait"] * 2


def test_idle_inside_host_spans():
    s = trace.summarize(DEVICES, SPANS)
    # device 0 idle 0-12, 50-62, 90-100; device 1 idle 0-12, 90-100.
    # batch spans 0-10 and 50-60, dispatch 10-12 and 60-62, wait 90-100
    assert s["idle_in_span_s"] == pytest.approx({
        "bench.batch": (0.020 + 0.010) / 2,
        "bench.dispatch": (0.004 + 0.002) / 2,
        "bench.wait": (0.010 + 0.010) / 2})


def test_operations_outside_the_window_do_not_count():
    devices = {"/device:TPU:0": [("fusion.9", ms(-50), ms(60)),
                                 ("fusion.9", ms(95), ms(50))]}
    s = trace.summarize(devices, SPANS)
    assert s["busy_s"] == pytest.approx(0.015)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize(DEVICES, SPANS[1:])
    with pytest.raises(ValueError):
        trace.summarize({}, SPANS)


@pytest.mark.parametrize("name,coll", [
    ("all-reduce.1", True), ("all-gather-start.2", True),
    ("reduce-scatter.4", True), ("collective-permute-done", True),
    ("all-to-all.7", True), ("fusion.12", False), ("copy-start.1", False),
])
def test_collective_names(name, coll):
    assert trace.is_collective(name) is coll


def test_hlo_text_names_and_nested_loops():
    body = "bf16[4,8]{1,0:T(8,128)(2,1)}"
    devices = {"/device:TPU:0": [
        # a loop spanning its body: only the body's operations count
        (f"%while.5 = (s32[]{{:T(128)}}, {body}) while((s32[]) %t), "
         "condition=%c, body=%b", ms(10), ms(80)),
        (f"%fusion.7 = {body} fusion({body} %p), kind=kLoop", ms(10), ms(30)),
        (f"%all-reduce.2 = {body} all-reduce({body} %fusion.7), "
         "replica_groups={{0,1}}", ms(45), ms(20)),
        (f"%ar.fused = {body} all-gather-start({body} %x)", ms(70), ms(10)),
    ]}
    s = trace.summarize(devices, SPANS)
    assert s["busy_s"] == pytest.approx(0.060)
    assert s["collective_ops"] == 2
    assert s["exposed_collective_s"] == pytest.approx(0.030)
    assert dict(s["top_ops"]) == pytest.approx(
        {"fusion.7": 0.030, "all-reduce.2": 0.020, "ar.fused": 0.010})


def test_leaves_drop_events_that_contain_others():
    evs = [("outer", 0, 100), ("a", 0, 10), ("inner", 20, 50), ("b", 30, 5),
           ("c", 120, 5)]
    assert [e[0] for e in trace.leaves(evs)] == ["a", "b", "c"]
