"""Runs of each cell with the timed path broken underneath (past the
harness's look for a chip, on the CPU at a tiny size): ``correct`` has to
come out false for every break the cell can have."""
import pytest

from bench import faults
from bench.tests import cpu_run

CASES = [
    ("rnaseq20k_l1.pipeline", "runner_up", {}),
    ("rnaseq20k_l1.pipeline", "answer_altered", {}),
    ("mnist_zeros_l2.serve", "bf16", {}),
    ("mnist_zeros_l2.serve", "runner_up", {}),
    ("mnist_zeros_l2.serve", "answer_altered", {}),
    ("mnist_zeros_l2.saturate", "half_batch", {"rate_per_s": 400}),
]


@pytest.mark.parametrize("cell,brk,traffic", CASES)
def test_break_is_not_correct(cell, brk, traffic, capsys, monkeypatch):
    if traffic:
        monkeypatch.setitem(cpu_run.TINY_TRAFFIC, "medoid_server",
                            dict(cpu_run.TINY_TRAFFIC["medoid_server"],
                                 **traffic))
    faults.plant(brk)
    out, err = cpu_run.run(cell, capsys, seed=2 ** 31 + 77)
    assert out["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_program_bf16_switches_on_the_programs_own_path():
    """The control that runs the program's own bfloat16 path: both entry
    points the benchmark drives answer through it, verified in float32."""
    import jax

    from repro import api
    from repro.launch import serve_medoid

    faults.plant("program_bf16")
    x = jax.random.uniform(jax.random.key(0), (64, 8))
    res = api.find_medoid(x, jax.random.key(1), config=api.MedoidConfig(
        metric="l1", budget_per_arm=8))
    assert res.precision == "bf16" and res.verified is not None
    server = serve_medoid.MedoidServer(metric="l2", max_batch=2)
    assert server.precision == "bf16"
