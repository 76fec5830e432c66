"""chip_smoke.py off the chip: its phases at tiny sizes on the CPU (Pallas
in interpret mode, the smoke config of the same architecture), and its
refusal to print a result when JAX finds no TPU."""

import pytest

import chip_smoke as cs
from repro import hardware

TINY = cs.Sizes(bs_n=1 << 14, sample_n=1 << 10, dc_n=(1 << 15) + 77,
                published_widths=False, prompt_len=16, max_new=4)


@pytest.mark.parametrize(
    "phase", [cs.phase_black_scholes, cs.phase_data_cleaning, cs.phase_serving],
    ids=["black_scholes", "data_cleaning", "serving"])
def test_phase_passes_at_tiny_size(phase):
    assert phase(TINY, hardware.TPU_V5E)


def test_no_tpu_means_no_result(monkeypatch, tmp_path, capsys):
    # Set, so the entry point leaves this process's cache config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err
