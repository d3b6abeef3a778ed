"""The control — the reference computed with float8 weights in the
program's place — comes out not correct, on three seeds, while the
program passes the same limit on the same requests."""
import smoke_root

from bench import harness


def test_control_fails_the_limit(tmp_path):
    root = smoke_root.make(str(tmp_path))
    for seed in (21, 22, 23):
        run = smoke_root.new_run(root, "tiny.chat", impl="ref", seed=seed)
        run.control = True
        harness.execute(run)
        got = run.info["check"]
        limit = run.cell["limits"]["logit_gap_max"]
        assert got["logit_gap_max"] <= limit, got
        assert got["control_gap_max"] > limit, got
        # the control put through the harness's own comparison fails it
        assert got["control_correct"] is False, got
        assert harness.result(run)["correct"], run.checks
