"""Chaos acceptance for the request-lifecycle armor (ISSUE 10):

(a) cancel mid-job refunds every pending + in-flight tile with zero
    leaked assignments, and the cancel round-trips the journal — the
    shadow state at cancel time is terminally drained, the standby
    replica applies the same record, and replay is idempotent;
(b) a tile that crashes three consecutive workers is quarantined, the
    job completes degraded (quarantined region = base image, every
    other tile bit-identical to a clean run), and NO worker stays
    breaker-quarantined on account of the poison.

Same tier as test_chaos_usdu.py: CPU-only, stubbed diffusion, seconds
per scenario.
"""

import numpy as np
import pytest

from comfyui_distributed_tpu.resilience.chaos import (
    run_chaos_cancel,
    run_chaos_poison,
    run_chaos_usdu,
)

pytestmark = pytest.mark.chaos


# --------------------------------------------------------------------------
# (a) cooperative cancellation
# --------------------------------------------------------------------------


def test_cancel_mid_job_refunds_everything_and_settles_terminal(tmp_path):
    result = run_chaos_cancel(seed=11, journal_dir=str(tmp_path / "wal"))
    # the master unwound with the terminal status, carrying the reason
    assert result.raised == "JobCancelled"
    assert result.reason == "chaos"
    # the cancel actually hit a live job (non-vacuous): work had
    # completed and work was still outstanding
    assert result.completed_before_cancel >= 2
    acct = result.accounting
    assert acct["pending_refunded"] + acct["in_flight_refunded"] > 0
    # zero leaked assignments the instant the cancel returned
    assert result.stats_after["in_flight"] == 0
    assert result.stats_after["queue_depth"] == 0


def test_cancel_round_trips_journal_and_replica(tmp_path):
    result = run_chaos_cancel(seed=11, journal_dir=str(tmp_path / "wal"))
    # the shadow state at cancel time is terminally drained — this is
    # exactly what a crash-after-cancel recovery replays to
    assert result.state_after_cancel.get("cancelled") is True
    assert result.state_after_cancel.get("pending") == []
    assert result.state_after_cancel.get("assigned") == {}
    # the standby replica applied the same cancel record
    assert result.replica_saw_cancel
    # after the master's cleanup both views agree the job is gone
    assert result.journal_jobs_after == {}
    assert result.replica_jobs_after == {}
    assert result.idempotent_replay
    # reclaim speed is measured
    assert result.cancel_latency_ms > 0


def test_cancelled_job_does_not_perturb_other_runs(tmp_path):
    """A cancel in one run leaves the global determinism untouched: an
    undisturbed run before and after produces the bit-identical
    canvas."""
    before = run_chaos_usdu(seed=13, job_id="cancel-bystander-1")
    run_chaos_cancel(
        seed=11, journal_dir=str(tmp_path / "wal"), job_id="cancel-victim"
    )
    after = run_chaos_usdu(seed=13, job_id="cancel-bystander-2")
    np.testing.assert_array_equal(before.output, after.output)


# --------------------------------------------------------------------------
# (b) poison-tile quarantine
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def poison_result(tmp_path_factory):
    journal_dir = tmp_path_factory.mktemp("poison-wal")
    return run_chaos_poison(seed=11, journal_dir=str(journal_dir))


def test_poison_tile_quarantined_after_three_crashes(poison_result):
    r = poison_result
    # three consecutive workers crashed on the same tile
    assert r.crashed_workers == ["w1", "w2", "w3"]
    assert r.attempts.get(r.poison_tile) == 3
    assert r.poison_tile in r.quarantined


def test_poison_crash_not_charged_to_the_workers(poison_result):
    r = poison_result
    # the harness charges the breaker at its harshest setting
    # (failure_threshold=1), so every crash DID open a circuit...
    assert "quarantined" in r.charged_states
    # ...and the quarantine's pardon closed every one of them: no
    # worker ends up quarantined because of the poison payload
    assert sorted(r.pardons) == ["w1", "w2", "w3"]
    for wid, snap in r.health_after.items():
        assert snap["state"] == "healthy", (wid, snap)


def test_poison_job_completes_degraded_with_unaffected_tiles_identical(
    poison_result,
):
    r = poison_result
    baseline = run_chaos_usdu(
        seed=11, image_hw=(96, 96), tile=48, padding=16,
        job_id="poison-baseline",
    )
    y, x, th, tw = r.poison_rect
    mask = np.ones(r.output.shape, bool)
    mask[:, y : y + th, x : x + tw, :] = False
    # every unaffected tile is bit-identical to the clean run
    np.testing.assert_array_equal(r.output[mask], baseline.output[mask])
    # the quarantined region is DEGRADED (base image, not the sampled
    # tile): it must differ from the clean run's output there
    assert not np.array_equal(
        r.output[:, y : y + th, x : x + tw, :],
        baseline.output[:, y : y + th, x : x + tw, :],
    )


def test_poison_policy_fail_raises_terminal_error(tmp_path):
    from comfyui_distributed_tpu.utils.exceptions import JobPoisoned

    with pytest.raises(JobPoisoned) as err:
        run_chaos_poison(
            seed=11,
            journal_dir=str(tmp_path / "wal"),
            poison_policy="fail",
            job_id="poison-fail-job",
        )
    assert err.value.tiles == [0]
