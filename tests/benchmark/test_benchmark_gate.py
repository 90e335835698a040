"""`run.py` measures nothing without a TPU: the gate is in `main`, in this process."""

import pytest

import bench_support


def test_main_refuses_without_a_tpu(capsys):
    run = bench_support.bench_run()
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "fleet_mix_reduced.saturated", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert stop.value.code not in (0, None)
    assert "no TPU" in str(stop.value.code)
    assert '"correct"' not in capsys.readouterr().out


def test_an_unknown_cell_is_refused():
    run = bench_support.bench_run()
    with pytest.raises(SystemExit):
        run.run_cell(bench_support.MANIFEST, "no_such_cell", 1, 1.0, False)
