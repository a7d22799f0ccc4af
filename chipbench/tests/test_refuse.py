"""Without a TPU the benchmark prints no result and exits non-zero."""
import pytest

from chipbench import run


def test_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "mistral-nemo-12b.slab24.chat", "--seed",
                   str(2 ** 33), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs 1 TPU" in out.err


def test_unknown_cell_raises():
    with pytest.raises(KeyError):
        run.main(["--workload", "no.such.cell", "--seed", "1",
                  "--seconds", "1"])
