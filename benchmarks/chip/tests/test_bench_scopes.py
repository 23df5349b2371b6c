"""The reduction by the program's names, on a synthetic event list."""
import pytest

from benchmarks.chip import scopes


def ms(x):
    return x * 1e6


BODY = "bf16[4,8]{1,0:T(8,128)(2,1)}"


def op(name, path=None):
    """An event named by its HLO text, with the op name where given."""
    meta = f', metadata={{op_name="{path}" source_line=7}}' if path else ""
    return f"%{name} = {BODY} fusion({BODY} %p), kind=kLoop{meta}"


# window 0..100 ms; the runner's call 10-90 with its lookup and dispatch
SPANS = [
    ("bench.window", ms(0), ms(100)),
    ("bench.batch", ms(0), ms(10)),
    ("repro.partition.call", ms(10), ms(80)),
    ("repro.partition.lookup", ms(10), ms(2)),
    ("repro.partition.dispatch", ms(12), ms(78)),
    ("repro.data.batch_at", ms(2), ms(6)),
]
ATTN = "jit(local_fn)/jvp()/while/body/closed_call/attention/dot_general"
ATTN_BWD = ("jit(local_fn)/transpose(jvp())/while/body/closed_call/"
            "checkpoint/rematted_computation/attention/ab,bc->ac/dot_general")


@pytest.mark.parametrize("path,scope", [
    (ATTN, "attention"),
    (ATTN_BWD, "attention"),
    ("jit(local_fn)/transpose(jvp(mlp))/mul", "mlp"),
    ("jit(local_fn)/jvp(head)/reduce_max", "head"),
    ("jit(local_fn)/optimizer/sqrt", "optimizer"),
    ("jit(local_fn)/mlp_forward/add", "unscoped"),
    ("jit(local_fn)/convert_element_type", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_an_op_name(path, scope):
    assert scopes.scope_of(path) == scope


def test_op_name_from_the_event_its_stats_or_the_hlo():
    assert scopes.op_name_of(op("fusion.1", ATTN)) == ATTN
    assert scopes.op_name_of("fusion.2", {"tf_op": ATTN_BWD}) == ATTN_BWD
    hlo = scopes.hlo_op_names(
        "ENTRY %main {\n"
        f"  %fusion.3 = {BODY} fusion(%p), kind=kLoop, "
        f'metadata={{op_name="{ATTN}"}}\n'
        f"  ROOT convert_bitcast_fusion.11 = {BODY} fusion(%q), "
        'metadata={op_name="jit(local_fn)/optimizer/mul"}\n'
        f"  %param.1 = {BODY} parameter(0)\n}}")
    assert hlo == {"fusion.3": ATTN,
                   "convert_bitcast_fusion.11": "jit(local_fn)/optimizer/mul"}
    assert scopes.op_name_of(op("fusion.3"), {}, hlo) == ATTN
    assert scopes.op_name_of("convert_bitcast_fusion.11", None, hlo) \
        == "jit(local_fn)/optimizer/mul"
    assert scopes.op_name_of(op("fusion.9"), {"hlo_op": "fusion.9"}, hlo) \
        is None


def test_seconds_per_scope_are_clipped_to_the_window_and_averaged():
    devices = {
        "/device:TPU:0": [
            # a loop's event spans its body: only leaves count
            ("%while.1 = (s32[]) while(%t)", ms(10), ms(80)),
            (op("fusion.1", ATTN), ms(10), ms(30)),
            (op("fusion.2", ATTN_BWD), ms(40), ms(20)),
            (op("fusion.3", "jit(local_fn)/mlp/dot_general"), ms(60), ms(10)),
            (op("fusion.4"), ms(70), ms(15)),     # no op name: unscoped
            (op("fusion.5", "jit(local_fn)/head/exp"), ms(95), ms(10)),
        ],
        "/device:TPU:1": [
            (op("fusion.6", "jit(local_fn)/optimizer/add"), ms(20), ms(40)),
        ],
    }
    op_names = {n: scopes.op_name_of(n) for evs in devices.values()
                for n, _, _ in evs}
    got = scopes.scope_times(devices, SPANS, op_names)
    assert got["seconds"] == pytest.approx({
        "attention": 0.025, "mlp": 0.005, "head": 0.0025,
        "optimizer": 0.020, "unscoped": 0.0075})
    assert got["unscoped_top"] == [["fusion.4", pytest.approx(0.0075), None]]
    assert got["named_share"] == pytest.approx(
        (0.050 + 0.010 + 0.005 + 0.040) / (0.050 + 0.010 + 0.015 + 0.005
                                            + 0.040))


def test_idle_inside_nested_program_spans():
    # busy 20-40 and 60-95 on one device, nothing on the other
    devices = {
        "/device:TPU:0": [(op("fusion.1", ATTN), ms(20), ms(20)),
                          (op("fusion.2", ATTN), ms(60), ms(35))],
        "/device:TPU:1": [(op("fusion.3", ATTN), ms(200), ms(5))],
    }
    got = scopes.idle_in_spans(devices, SPANS)
    # device 0 idle 0-20, 40-60, 95-100; device 1 idle all window
    assert got == pytest.approx({
        "repro.data.batch_at": (0.006 + 0.006) / 2,
        "repro.partition.call": ((0.010 + 0.020) + 0.080) / 2,
        "repro.partition.lookup": (0.002 + 0.002) / 2,
        "repro.partition.dispatch": ((0.008 + 0.020) + 0.078) / 2,
    })
    assert "bench.batch" not in got


def test_no_device_or_no_window_is_an_error():
    with pytest.raises(ValueError):
        scopes.scope_times({}, SPANS, {})
    with pytest.raises(ValueError):
        scopes.idle_in_spans({"/device:TPU:0": [("fusion.1", 0, 1)]},
                             SPANS[1:])


def test_scope_report_runs_the_tiny_cell_on_the_cpu(tiny_checkout):
    import json

    import jax

    from benchmarks.chip import scope_report
    from benchmarks.chip.cell import Cell

    cell = Cell(tiny_checkout, "tiny.tiny")
    res = scope_report.report(cell, jax.devices()[:1], 2**31 + 5, 0.5,
                              log=lambda *_: None)
    # a CPU trace holds the program's spans but no device plane
    assert res["trace"]["devices"] == 0
    assert {"repro.partition.call", "repro.partition.lookup",
            "repro.partition.dispatch", "repro.data.batch_at"} <= set(
                res["trace"]["program_spans"])
    assert res["plan"]["first_call_s"] > 0
    assert {"make_jaxpr", "propagate", "compile_plan"} <= set(
        res["plan"]["phases"])
    for w in ("untraced", "traced"):
        assert res["windows"][w]["steps"] > 0
        assert res["windows"][w]["compiles"] == 0
    json.dumps(res, default=str)
