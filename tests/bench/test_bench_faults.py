"""The comparison that decides ``correct`` fails a broken timed path: a run
is driven with the check for a chip skipped and a fault planted under the
engine, and ``correct`` comes out false.

Faults a serving cell can have: a token altered where it is produced (the
decode step's greedy choice), and a decode step that leaves the KV state
unchanged (the step's write-back of the new position dropped)."""
import jax.numpy as jnp
import pytest

import smoke_root

from bench import harness


def _altered_token(monkeypatch):
    from repro.serve import engine

    real = engine.greedy_next
    monkeypatch.setattr(engine, "greedy_next",
                        lambda logits: (real(logits) + 1) % logits.shape[-1])


def _state_unchanged(monkeypatch):
    from repro.serve import pages

    monkeypatch.setattr(pages, "writeback",
                        lambda data, dense_new, table, lengths, active,
                        page_size: data)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["altered_token", "state_unchanged"])
def test_fault_fails_the_check(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    root = smoke_root.make(str(tmp_path))
    run = smoke_root.new_run(root, "tiny.chat", impl="ref")
    harness.execute(run)
    res = harness.result(run)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > 3 * gap["limit"]


def test_sound_run_passes(tmp_path):
    root = smoke_root.make(str(tmp_path))
    run = smoke_root.new_run(root, "tiny.chat", impl="ref", seed=11)
    harness.execute(run)
    assert harness.result(run)["correct"], run.checks
    assert jnp.isfinite(run.checks["logit_gap_max"][0])
