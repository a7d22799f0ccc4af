"""A whole run of the harness on the CPU for the StableLM block: the
program (LayerNorm, per-head q/k norm, rotary on 10 of 40 dims, parallel
residual, SLaB 2:4 packed) proves correct against the plain reference,
and the control (the reference in the program's place at fp8) does
not."""
import time
from pathlib import Path

import pytest

from chipbench import harness, spec

FX = spec.PKG / "tests" / "fixtures"
SEED = 2 ** 35 + 29
CONFIG = "tiny.stablelm-slab24"


def _run(control=None):
    bench = {
        "configs": [{"name": CONFIG,
                     "file": str(FX / "configs" / f"{CONFIG}.json")}],
        "workloads": [{"name": "tiny", "config": CONFIG, "traffic": "tiny",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "ttft_p50_ms", "unit": "ms"},
                       {"name": "itl_p50_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    lay = spec.Layout(root=FX, repo=Path("/"))
    return harness.run_cell(bench, "tiny", SEED, 1.5, False,
                            time.monotonic(), layout=lay, control=control,
                            log=lambda *a: None)


@pytest.mark.parametrize("control", [None, "fp8"], ids=("program", "fp8"))
def test_the_program_is_correct_and_the_control_is_not(control):
    r = _run(control)
    gap = r["checks"]["widest_logit_gap"]
    assert r["failed"] == 0 and r["attempted"] > 0
    if control is None:
        assert r["correct"], r["checks"]
        assert "control" not in r
    else:
        assert not r["correct"], r["checks"]
        assert gap["value"] > gap["limit"]
        assert r["control"]["program_widest_logit_gap"] <= gap["limit"]


def test_a_configuration_of_another_block_is_refused():
    import json
    from chipbench.archs import stablelm
    cfg = json.loads((FX / "configs" / f"{CONFIG}.json").read_text())
    stablelm.program_config(cfg)
    for k in ("qk_layernorm", "use_parallel_residual"):
        with pytest.raises(ValueError, match=k):
            stablelm.program_config({**cfg, k: False})
