"""`correct` fails the control and every fault, at test sizes on the CPU.

The control is the program's int8 path; the faults break the timed path
underneath the wall clock (`chipbench/control.py`). Each run drives the
rest of the harness as a chip run does (set-up, window, sample, float32
reference). The number compared is the mean logit gap, as on the chip;
the limits here are for these test configurations, set between the
readings of sound runs and of the control on the CPU (three seeds each):
small-dense, sound 0.0003-0.0004, int8 0.0038-0.0054; tiny-moe, with
granite's multipliers, sound 0.0008-0.0029 (its 4 experts and 64
dimensions let rounding flip a route, so its int8 path, 0.0039-0.0046,
is not told apart), faults 0.48-2.82, and its weights served with no
multiplier folded in 0.85-0.93.
"""
import json
from pathlib import Path

import pytest

from chipbench import control, run, weights

TESTDATA = Path(__file__).resolve().parent / "testdata"
SEEDS = [2**31 + 11, 99]
LIMIT = {"small-dense": 0.0015, "tiny-moe": 0.05}


def _correct(config, seed, **kw):
    cj = json.loads((TESTDATA / f"{config}.json").read_text())
    mix = json.loads((TESTDATA / "tiny-backlog.json").read_text())
    cell = {"name": config, "config": config, "traffic": "tiny-backlog",
            "chips": 1}
    served = run.serve(cell, cj, mix, run.peaks_for("TPU v5 lite"),
                       seed=seed, seconds=2.0, trace=False,
                       log=lambda m: None, **kw)
    checks, _ = run.check(served, cj,
                          {"mean_logit_gap": {"limit": LIMIT[config]}}, seed,
                          log=lambda m: None)
    assert checks["sampled_requests"]["value"] >= 4
    return all(c["ok"] for c in checks.values())


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct(seed):
    assert _correct("small-dense", seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_published_multipliers_are_served(seed):
    """tiny-moe states granite's multipliers; the program serves them
    folded into its weights and the reference applies them."""
    assert _correct("tiny-moe", seed)


def test_reference_sees_the_multipliers(monkeypatch):
    """Served without the multipliers (nothing folded), the same weights
    are not the model the configuration states."""
    monkeypatch.setattr(weights, "folds", lambda cj: {})
    assert not _correct("tiny-moe", SEEDS[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_control_is_not_correct(seed):
    assert not _correct("small-dense", seed, make_engine=control.build_int8)


@pytest.mark.parametrize("config", ["small-dense", "tiny-moe"])
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_fault_is_not_correct(config, fault):
    assert not _correct(config, SEEDS[0], fault=control.FAULTS[fault])
