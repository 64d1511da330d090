"""chip_smoke.py rehearsed on the CPU at a tiny cluster: the same phase
functions the chip run calls, with the Pallas kernel in interpret mode,
so the script's control flow and checks are guarded on every PR without
chip time. The test steers past the script's TPU check by calling the
phases directly; it also proves that check fails here."""

import pytest

import chip_smoke as cs


def test_main_fails_without_a_tpu(capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert "not 'tpu'" in out[-1]
    assert not any(line.startswith("{") for line in out)


@pytest.mark.parametrize("prune", ["off", "true"])
def test_allocate_phase(prune):
    out = cs.phase_allocate(n_nodes=48, n_gangs=24,
                            solver_args={"kernel": "pallas",
                                         "prune.enable": prune})
    assert out["tier"] == "pallas" and out["binds"] == 24 * cs.GANG


def test_allocate_phase_reports_a_wrong_tier():
    with pytest.raises(cs.SmokeFailure, match="not pallas"):
        cs.phase_allocate(n_nodes=16, n_gangs=4,
                          solver_args={"kernel": "scan"})


def test_preempt_phase():
    out = cs.phase_preempt(vn_nodes=32, n_low=4, n_high=2)
    assert out["evictions"] > 0


def test_mesh_phase():
    out = cs.phase_mesh(n_nodes=32, n_gangs=8, n_devices=4)
    assert out["binds"] == 8 * cs.GANG


def test_binds_report_the_first_difference():
    want = {"default/job0-task0": "node-0", "default/job10-task1": "node-3"}
    got = dict(want, **{"default/job2-task0": "node-9"})
    with pytest.raises(cs.SmokeFailure,
                       match="first task default/job2-task0"):
        cs.check_binds_equal(got, want, "x")
