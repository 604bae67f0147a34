"""The harness runs on a TPU or not at all: no CPU fallback, no default
peaks."""
import pytest

from bench import common
from bench import run as R


def test_no_tpu_is_refused():
    import jax

    with pytest.raises(common.BenchError, match="no TPU"):
        common.require_tpu(jax, 1)


def test_unknown_device_has_no_peaks():
    with pytest.raises(common.BenchError, match="no peaks"):
        common.peaks("cpu")
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_run_exits_without_a_result(capsys):
    rc = R.main(["--workload", "fed-cnn-k100", "--seed", "3", "--seconds", "1",
                 "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_unknown_cell_is_refused(capsys):
    assert R.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_checks_hold_numbers_to_limits():
    assert common.Check("a", 0.1, 0.2).ok
    assert not common.Check("a", 0.3, 0.2).ok
    assert not common.Check("a", float("nan"), 0.2).ok
    assert common.Check("exact", 0.0, 0.0).ok
