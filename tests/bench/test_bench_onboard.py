"""The onboarding driver end to end at CPU size, and the comparison that
decides its ``correct``: a sound run passes; the control (the reference
with float8 weights in the program's place) and the faults a training
cell can have fail it."""
import pytest

import smoke_root

from bench import harness

CELLS = {"tiny.onboard": ("onboard", smoke_root.ONBOARD,
                          smoke_root.ONBOARD_CELL)}


def _run(tmp_path, seed=5, control=False, trace=False):
    root = smoke_root.make(str(tmp_path), cells=CELLS)
    run = smoke_root.new_run(root, "tiny.onboard", seed=seed, impl="ref",
                             trace=trace)
    run.control = control
    harness.execute(run)
    return run, harness.result(run)


def test_onboard_driver(tmp_path):
    run, res = _run(tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"onboard_tokens_per_s", "setup_s"}
    assert res["metrics"]["onboard_tokens_per_s"]["value"] > 0
    assert res["attempted"] == run.e2e["steps"] > 0 and res["failed"] == 0
    assert run.info["compiles_in_window"] == 0, run.info
    # set-up ran the first graduation wave; the window graduated more
    assert run.info["graduated_in_setup"] == 2
    assert run.stats["graduated"] > 2
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad1_gap", "change3_gap"}


def test_onboard_control_and_half_rows_fail(tmp_path):
    run, res = _run(tmp_path, seed=6, control=True)
    got = run.info["check"]
    assert res["correct"], res["checks"]
    assert got["control"]["correct"] is False, got["control"]
    assert got["half_rows"]["correct"] is False, got["half_rows"]


def _unchanged(monkeypatch):
    """A gang step that returns its state unchanged."""
    from repro.train import steps

    real = steps.make_gang_step

    def make(cfg, **kw):
        step = real(cfg, **kw)

        def frozen_step(state, batch, rng):
            _, met = step(state, batch, rng)
            return state, met

        frozen_step.trace_counter = step.trace_counter
        return frozen_step

    monkeypatch.setattr(steps, "make_gang_step", make)


def _half_rows(monkeypatch):
    """A gang step that leaves half of each slot's rows out and averages
    the rest."""
    from repro.train import steps

    real = steps.make_gang_step

    def make(cfg, **kw):
        step = real(cfg, **kw)

        def half(state, batch, rng):
            m = batch["tokens"].shape[1] // 2
            return step(state, {k: v[:, :m] for k, v in batch.items()}, rng)

        half.trace_counter = step.trace_counter
        return half

    monkeypatch.setattr(steps, "make_gang_step", make)


@pytest.mark.parametrize("fault", [_unchanged, _half_rows],
                         ids=["state_unchanged", "half_rows"])
def test_onboard_fault_fails_the_check(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    run, res = _run(tmp_path)
    assert not res["correct"], res["checks"]
