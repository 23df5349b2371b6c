"""Cell discovery and one whole run of a tiny cell, on the CPU.

The tiny cell is added to a scratch checkout as files and entries only (a
configuration, a traffic mix, limits), and the harness finds it by name.
"""
import json

import jax
import pytest

from benchmarks.chip import run as run_mod
from benchmarks.chip.cell import Cell, _module
from benchmarks.chip.tests.bench_tiny import ROOT


def test_discovery_finds_a_cell_added_as_files(tiny_checkout):
    cell = Cell(tiny_checkout, "tiny.tiny")
    assert cell.chips == 1
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["batch"] == 4
    assert set(cell.limits) == {"loss_gap", "grad_norm_gap",
                                "update_norm_gap"}
    assert cell.family.step_flops(cell.config, 4, 64) > 0
    assert [m["name"] for m in cell.metrics(trace=False)] == [
        "tokens_per_s", "setup_s"]
    names = [m["name"] for m, _ in cell.readers()]
    assert "mfu" in names and "collective_exposed_ms" not in names


def test_unknown_workload_is_an_error(tiny_checkout):
    with pytest.raises(KeyError):
        Cell(tiny_checkout, "tiny.nothing")


def test_peaks_refuse_an_unknown_device_kind(tiny_checkout):
    cell = Cell(tiny_checkout, "tiny.tiny")
    assert cell.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        cell.peaks("cpu")


def test_harness_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        run_mod.main(["--workload", "qwen1.5-0.5b.b4s1k", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_harness_refuses_a_checkout_without_the_program(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text("{}")
    with pytest.raises(SystemExit) as e:
        run_mod.require_program(tmp_path)
    assert e.value.code == 2


def test_tiny_cell_runs_and_is_correct_on_the_cpu(tiny_checkout):
    cell = Cell(tiny_checkout, "tiny.tiny")
    res = run_mod.run_cell(cell, jax.devices()[:1], 2**31 + 11, 1.0,
                           trace=False, log=lambda *_: None)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_metric_readers_on_a_record(tiny_checkout):
    cell = Cell(tiny_checkout, "tiny.tiny")
    rec = {"plan_build_s": 2.5, "fallbacks": {"gather": 2, "slice": 3},
           "flops_per_step": 1e12, "chips": 1, "peak_flops": 197e12,
           "steps_traced": 40,
           "trace": {"window_s": 1.0, "busy_s": 0.9, "idle_share": 0.1,
                     "collective_ops": 0, "exposed_collective_s": 0.0,
                     "idle_in_span_s": {"bench.batch": 0.12,
                                        "bench.dispatch": 0.03,
                                        "bench.wait": 0.0}}}
    got = {m["name"]: read(rec) for m, read in cell.readers()}
    assert got["plan_build_s"] == 2.5
    assert got["fallback_eqns"] == 5
    assert got["mfu"] == pytest.approx(100 * 40e12 / 197e12)
    assert got["device_idle"] == pytest.approx(10.0)
    assert got["input_ms"] == pytest.approx(3.0)  # 120 ms over 40 steps
    exposed = _module(ROOT / "benchmarks/chip/metrics/collective_exposed_ms.py")
    assert exposed.read(rec) is None  # no collective in the trace
    rec["trace"].update(collective_ops=8, exposed_collective_s=0.4)
    assert exposed.read(rec) == pytest.approx(10.0)
