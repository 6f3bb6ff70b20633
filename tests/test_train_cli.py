"""The training launcher end to end at a tiny size: ``train.main(argv)``.

The persistent compilation cache stays off here (the launcher's
``compile_cache.enable`` is replaced), so nothing is written into the
checkout.
"""
import math

import pytest

from repro.launch import compile_cache, train

TINY = ["--arch", "paper-mlp", "--reduced", "--packed", "--groups", "2",
        "--per-group", "2", "--seq", "16", "--rounds", "2"]


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(compile_cache, "enable", lambda: None)


@pytest.mark.parametrize("extra", [
    # count-dependent optimizer with per-group T_i: round 0 promotes the
    # shared step count to a per-group vector, so round 1's state has new
    # shapes and the round must be retraced, not replayed
    ["--opt", "adamw", "--t-i", "1,2"],
    ["--opt", "momentum", "--t-inner", "2"],
], ids=["adamw-t_i", "momentum"])
def test_packed_rounds_run(extra):
    run = train.main(TINY + extra)
    assert len(run["history"]) == 2
    assert all(math.isfinite(h["loss"]) and math.isfinite(h["grad_sq"])
               for h in run["history"])
    assert run["compile_s"] > 0 and "HloModule" in run["hlo"]
